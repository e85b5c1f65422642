"""Operations and bytes the work needs, counted from shapes and masks.

These are the yardstick of every utilisation and roofline share: what the
algorithm has to do, not what a kernel happens to do. Recomputed work
(rematerialisation, a backward pass that recomputes scores twice) is not
counted, so a kernel that does extra work reads lower, and one that skips
needed work cannot read more than the chip's peak.
"""
from __future__ import annotations

import numpy as np

from weights import dims

BF16 = 2
F32 = 4


def matmul_params(c: dict, head: bool = True) -> int:
    """Weights that take part in a matmul per token: every layer's
    projections and MLP, and the output head (embedding lookups do not)."""
    m = dims(c)
    d, hd = m["d"], m["hd"]
    per_layer = (d * (m["Hq"] + 2 * m["Hkv"]) * hd + m["Hq"] * hd * d
                 + 3 * d * m["F"])
    return m["L"] * per_layer + (d * m["V"] if head else 0)


def causal_pairs(seg=None, T: int = 0) -> int:
    """(query, key) pairs a causal mask keeps over one row: T(T+1)/2, or
    with document ids ``seg`` the sum of n(n+1)/2 over its documents."""
    if seg is None:
        return T * (T + 1) // 2
    _, n = np.unique(np.asarray(seg), return_counts=True)
    return int(np.sum(n * (n + 1) // 2))


def attn_fwd_flops(c: dict, pairs: int) -> int:
    """One layer's attention forward over ``pairs``: QK^T and PV, each
    2 * head_dim operations per pair and query head."""
    m = dims(c)
    return 4 * m["Hq"] * m["hd"] * pairs


def attn_bwd_flops(c: dict, pairs: int) -> int:
    """The memory-efficient backward: QK^T again (P is not stored), dP =
    dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q: five matmuls."""
    return 5 * attn_fwd_flops(c, pairs) // 2


def attn_fwd_bytes(c: dict, tokens: int) -> int:
    """q, k, v read and o written once, bf16, and the f32 log-sum-exp."""
    m = dims(c)
    return tokens * (BF16 * m["hd"] * (2 * m["Hq"] + 2 * m["Hkv"])
                     + F32 * m["Hq"])


def attn_bwd_bytes(c: dict, tokens: int) -> int:
    """q, o, dO read and dQ written; k, v read and dK, dV written; the
    f32 log-sum-exp and the row sums of dO * O read."""
    m = dims(c)
    return tokens * (BF16 * m["hd"] * (4 * m["Hq"] + 4 * m["Hkv"])
                     + 2 * F32 * m["Hq"])


def train_step_flops(c: dict, tokens: int, pairs: int) -> int:
    """Model FLOPs of one training step: 6 per matmul weight and token,
    and the attention forward and backward of every layer (the backward
    counted as twice the forward, as the model, not the kernel, needs)."""
    L = dims(c)["L"]
    return 6 * matmul_params(c) * tokens + 3 * L * attn_fwd_flops(c, pairs)


def roofline(flops: float, nbytes: float, seconds: float, peaks: dict):
    """(share of the roofline in %, the bound that applies)."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
