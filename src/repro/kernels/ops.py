"""jit-ready wrappers around the Pallas flash-attention kernels.

Public layout is (B, T, H, D) (matching the model code); the kernels use
(B, H, T, D). Masking is a static :class:`repro.core.mask.MaskSpec`
(hashable — it rides through jit as a static argument); document segment
IDs are (B, T) int32 operands. The legacy ``causal``/``rel_offset``/
``window`` kwargs still build the equivalent spec. Tiles left unset
(``block_q``/``block_kv`` None) come from ``flash_tiles``, per kernel, from
the call's lengths, head dims and mask; a tile passed explicitly wins on
its axis, and one that does not divide T shrinks to a divisor.
``prune`` (default on) enables the static block-sparse grid pruning;
``prune=False`` forces the dense ``nq × nk`` sweep (benchmark baseline /
differential testing).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.mask import MaskSpec, as_spec
from repro.kernels import flash_attention as fa


def _to_bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


@partial(jax.jit, static_argnames=("mask", "causal", "rel_offset", "window",
                                   "scale", "block_q", "block_kv",
                                   "interpret", "prune"))
def flash_fwd(q, k, v, *, mask=None, causal=False, rel_offset=0, window=0,
              scale=None, block_q=None, block_kv=None, interpret=False,
              prune=True, q_segments=None, kv_segments=None):
    """(B,T,H,D) partial attention -> (o (B,T,H,D), lse (B,T,H))."""
    mask = as_spec(mask, causal=causal, window=window,
                   rel_offset=rel_offset)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    o, lse = fa.flash_fwd_bhtd(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), scale=scale, mask=mask,
        block_q=block_q, block_kv=block_kv, interpret=interpret, prune=prune,
        q_segments=q_segments, kv_segments=kv_segments)
    return _to_bhtd(o), jnp.transpose(lse, (0, 2, 1))


@partial(jax.jit, static_argnames=("mask", "causal", "rel_offset", "window",
                                   "scale", "block_q", "block_kv",
                                   "interpret", "prune"))
def flash_bwd(q, k, v, o, lse, do, *, mask=None, causal=False, rel_offset=0,
              window=0, scale=None, block_q=None, block_kv=None,
              interpret=False, delta=None, prune=True, q_segments=None,
              kv_segments=None):
    """Backward from saved (o, lse). Returns (dq, dk, dv)."""
    mask = as_spec(mask, causal=causal, window=window,
                   rel_offset=rel_offset)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    dq, dk, dv = fa.flash_bwd_bhtd(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), _to_bhtd(o),
        jnp.transpose(lse, (0, 2, 1)), _to_bhtd(do), scale=scale, mask=mask,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        delta=None if delta is None else jnp.transpose(delta, (0, 2, 1)),
        prune=prune, q_segments=q_segments, kv_segments=kv_segments)
    return _to_bhtd(dq), _to_bhtd(dk), _to_bhtd(dv)
