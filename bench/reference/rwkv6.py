"""Plain RWKV-6 ("Finch", arXiv:2404.05892), one sequence at a time, as a
token-by-token recurrence.

Per layer, with LN a LayerNorm with weight (1 + scale) and bias, eps 1e-5:

time mix on h = LN1(x), h_prev the previous token's h (0 at the start):
  d = h_prev - h;  m = tanh((h + d * mu0) A) (5 LoRA slices of rank 32)
  x_i = h + d * (mu_i + m_i B_i)              for i in r, k, v, g, w
  r, k, v = x_r W_r, x_k W_k, x_v W_v (heads of 64); g = silu(x_g W_g)
  w = exp(-exp(clip(w0 + tanh(x_w A_w) B_w, -8, 1)))
  per head: o = r . (S + diag(u) k^T v);  S <- diag(w) S + k^T v
  o = per-head RMS norm (eps 1e-5) * gn_scale;  x += (o * g) W_o
channel mix on c = LN2(x), c_prev the previous token's c:
  x += sigmoid((c m_r + c_prev (1 - m_r)) W_r)
       * (relu((c m_k + c_prev (1 - m_k)) W_in)^2 W_out)
then a final LayerNorm.

Departures from the paper, stated in the configuration file: the decay
exponent is clipped to [-8, 1]; the per-head output norm has no mean
subtraction and no bias; there is no LayerNorm right after the embedding.

``dot`` is the operand type of every matmul: float32 computes them at
HIGHEST; a narrower type (the control's float8) rounds both operands to it
and accumulates in float32. Everything else, the recurrence included, is
float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.transformer import matmul

TS_RANK = 32


def _ln(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1 + p["scale"]) + p["bias"]


def _shift(h):
    return jnp.concatenate([jnp.zeros_like(h[:1]), h[:-1]], axis=0)


def hidden(params, model: dict, x, dot=jnp.float32):
    """x [T, D] input embeddings -> final-normed hidden states [T, D]."""
    mm = matmul(dot)
    hd = model["head_dim"]
    d = model["d_model"]
    nh = d // hd
    t = x.shape[0]

    def layer(x, p):
        tm, cm = p["tmix"], p["cmix"]
        h = _ln(x, p["norm1"])
        delta = _shift(h) - h
        lora = jnp.tanh(mm(h + delta * tm["ts_mu0"], tm["ts_lora_a"]))
        lora = lora.reshape(t, 5, TS_RANK)
        xs = [h + delta * (tm["ts_mu"][i]
                           + mm(lora[:, i], tm["ts_lora_b"][i]))
              for i in range(5)]
        r = mm(xs[0], tm["w_r"]).reshape(t, nh, hd)
        k = mm(xs[1], tm["w_k"]).reshape(t, nh, hd)
        v = mm(xs[2], tm["w_v"]).reshape(t, nh, hd)
        g = jax.nn.silu(mm(xs[3], tm["w_g"]))
        raw = tm["decay_w0"] + mm(jnp.tanh(mm(xs[4], tm["decay_lora_a"])),
                                  tm["decay_lora_b"])
        w = jnp.exp(-jnp.exp(jnp.clip(raw, -8.0, 1.0))).reshape(t, nh, hd)
        u = tm["bonus_u"]

        def step(s, inp):
            r_t, k_t, v_t, w_t = inp
            kv = k_t[:, :, None] * v_t[:, None, :]           # [H, c, d]
            o_t = jnp.einsum("hc,hcd->hd", r_t, s + u[:, :, None] * kv,
                             precision=jax.lax.Precision.HIGHEST)
            return w_t[:, :, None] * s + kv, o_t

        s0 = jnp.zeros((nh, hd, hd), x.dtype)
        _, o = jax.lax.scan(step, s0, (r, k, v, w))
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + 1e-5)
        o = o.reshape(t, d) * tm["gn_scale"]
        x = x + mm(o * g, tm["w_o"])
        c = _ln(x, p["norm2"])
        cp = _shift(c)
        ck = c * cm["mix_k"] + cp * (1 - cm["mix_k"])
        cr = c * cm["mix_r"] + cp * (1 - cm["mix_r"])
        x = x + jax.nn.sigmoid(mm(cr, cm["w_r"])) * mm(
            jnp.square(jax.nn.relu(mm(ck, cm["w_in"]))), cm["w_out"])
        return x, None

    x, _ = jax.lax.scan(layer, x.astype(jnp.float32), params["groups"]["blk0"])
    return _ln(x, params["final_norm"])
