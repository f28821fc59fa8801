"""Operations and bytes of the work done, counted from the configuration's
shapes, whatever implements them.

Model FLOPs count each multiply-add as 2 operations and cover what the model
needs per token: every weight matmul, attention over the positions a token
really sees (not the masked cache), RWKV's state update, and the unembed.
The engine's float32 matmuls run as one bfloat16 pass at the default
precision (the unembed read at HIGHEST is a small share), so utilization is
taken against the bfloat16 peak.
"""
from __future__ import annotations


def per_token(model: dict, family: str, pos: int) -> float:
    """FLOPs of one token at context position ``pos`` (0-based)."""
    d, v, L = model["d_model"], model["vocab_size"], model["n_layers"]
    f = model["d_ff"]
    hd = model["head_dim"]
    if family == "transformer":
        h, kv = model["n_heads"], model["n_kv_heads"]
        macs = d * hd * (2 * h + 2 * kv) + 3 * d * f
        attn = 2 * h * hd * (pos + 1)               # q.k and p.v, MACs
        return 2.0 * L * (macs + attn) + 2.0 * d * v
    if family == "rwkv6":
        # r, k, v, g, o and the channel mix's receptance: 6 D^2; token-shift
        # LoRA 2 * 5 * 32 * D; decay LoRA 2 * 64 * D; channel mix 2 D F
        macs = 6 * d * d + 320 * d + 128 * d + 2 * d * f
        # state: k v^T, decay, add, bonus (2), readout (2): 7 D hd flops
        return L * (2.0 * macs + 7.0 * d * hd) + 2.0 * d * v
    raise ValueError(f"unknown family {family!r}")


def tokens(model: dict, family: str, positions) -> float:
    return sum(per_token(model, family, int(p)) for p in positions)


def cim_read(m: int, k: int, j: int) -> tuple:
    """(flops, bytes) of one fused read ``x[m, K] @ W[K, J]`` off the packed
    One4N image: 10-bit mantissas in 16-bit words, plus per 8x16 block two
    112-bit codewords in four 32-bit words each (K*J/4 bytes), plus x and
    the float32 output. Counted on the logical shape; tile padding is not
    work."""
    image = 2 * k * j + k * j // 4
    return 2.0 * m * k * j, float(image + 4 * m * k + 4 * m * j)
