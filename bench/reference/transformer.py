"""Plain decoder-only transformer (OLMo family), one sequence at a time.

x = embeddings; per layer: x += Attn(LN(x)); x += SwiGLU(LN(x)); then a
final LN. LN is OLMo's non-parametric LayerNorm (eps 1e-5, no scale or
bias). Attention is causal multi-head attention with rotary embeddings on
q and k (rotate-half form, theta from the configuration), softmax over
q.k / sqrt(head_dim). SwiGLU is ``(silu(x W_gate) * (x W_in)) W_out``.

``dot`` is the operand type of every matmul: float32 computes them at
HIGHEST; a narrower type (the control's float8) rounds both operands to it
and accumulates in float32. Everything else is float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _ln(x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    sin = jnp.sin(ang)[:, None, :]
    cos = jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def matmul(dot):
    """``a @ b`` with operands of type ``dot``, float32 result."""
    if dot == jnp.float32:
        return lambda a, b: jnp.matmul(a, b,
                                       precision=jax.lax.Precision.HIGHEST)
    return lambda a, b: jnp.matmul(a.astype(dot), b.astype(dot),
                                   preferred_element_type=jnp.float32)


def hidden(params, model: dict, x, dot=jnp.float32):
    """x [T, D] input embeddings -> final-normed hidden states [T, D]."""
    mm = matmul(dot)
    h_n = model["n_heads"]
    hd = model["head_dim"]
    t = x.shape[0]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    blocks = params["groups"]["blk0"]

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _ln(x)
        q = _rope(mm(h, a["wq"]).reshape(t, h_n, hd), pos,
                  model["rope_theta"])
        k = _rope(mm(h, a["wk"]).reshape(t, h_n, hd), pos,
                  model["rope_theta"])
        v = mm(h, a["wv"]).reshape(t, h_n, hd)
        heads = lambda z: jnp.transpose(z, (1, 0, 2))     # noqa: [H, T, hd]
        s = jax.vmap(mm)(heads(q), jnp.transpose(k, (1, 2, 0)))   # [H, T, T]
        s = jnp.where(causal[None], s / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        o = jax.vmap(mm)(jax.nn.softmax(s, axis=-1), heads(v))  # [H, T, hd]
        x = x + mm(heads(o).reshape(t, h_n * hd), a["wo"])
        h = _ln(x)
        x = x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_in"]),
                   m["w_out"])
        return x, None

    x, _ = jax.lax.scan(layer, x.astype(jnp.float32), blocks)
    return _ln(x)
