"""Static block-sparsity ranges for the chunk-attention kernels.

The schedules guarantee the :class:`repro.core.mask.MaskSpec` of every step
is static (DESIGN.md §2), so for a fixed block tiling the set of (q-block,
kv-block) pairs the mask can reach is computable at trace time. This module
is the single source of truth for those ranges — the Pallas kernels
(``flash_attention.py``) and the ``chunked-lax`` backend (``chunked.py``)
derive their iteration spaces from the same functions, so CPU CI exercises
the identical block-range logic the TPU kernels run.

Conventions. Q block ``i`` covers absolute query positions
``[mask.q_offset + i*br, mask.q_offset + (i+1)*br - 1]``; KV block ``j``
covers ``[mask.kv_offset + j*bc, mask.kv_offset + (j+1)*bc - 1]``. All
bounds are **inclusive**; an empty range is returned as ``hi < lo``
(callers clamp ``count = max(hi - lo + 1, 0)``).

Mask kinds: causal bounds the high side, the sliding window the low side,
and a ``document`` spec with static ``boundaries`` bounds both — a Q block
can only reach keys in ``[doc_start(qs), doc_end(qe)]``, so cross-document
blocks of a packed batch are pruned at trace time. A ``prefix_lm`` prefix
re-opens blocks the causal/window bounds would drop (the returned range is
the contiguous hull). ``document`` with *dynamic* segment arrays cannot be
bounded statically (``mask.prunable`` is False) — callers fall back to the
dense sweep and mask at runtime.

Every function accepts either Python ints (grid sizing, ``chunked-lax``)
or traced int32 scalars (Pallas kernel bodies and index maps): ``//`` is
floor division in both worlds, and min/max/where dispatch on operand type.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.mask import MaskSpec


def _static(*xs) -> bool:
    return all(isinstance(x, (int, bool, np.integer, np.bool_)) for x in xs)


def _mn(a, b):
    if _static(a, b):
        return min(a, b)
    import jax.numpy as jnp
    return jnp.minimum(a, b)


def _mx(a, b):
    if _static(a, b):
        return max(a, b)
    import jax.numpy as jnp
    return jnp.maximum(a, b)


def _where(cond, a, b):
    if _static(cond):
        return a if cond else b
    import jax.numpy as jnp
    return jnp.where(cond, a, b)


def _cdiv(a, b):
    """Ceil division with floor-div semantics shared by int and traced."""
    return -(-a // b)


def pick_block(T: int, block: int) -> int:
    """Largest divisor of T that is ≤ ``block`` (grids and scans need equal
    blocks, so a non-dividing tile argument is shrunk, not crashed on). When T
    has no useful divisor near the target (prime-ish lengths), blocking
    would degenerate into a near-token-level sweep — return T itself so the
    caller takes the single-block path."""
    b = min(block, T)
    while T % b:
        b -= 1
    if b < min(32, T):
        return T
    return b


def _prefix_blocks(mask: MaskSpec, bc: int) -> int:
    """Number of KV blocks overlapping the bidirectional prefix (static)."""
    if not mask.prefix_len or mask.prefix_len <= mask.kv_offset:
        return 0
    return _cdiv(mask.prefix_len - mask.kv_offset, bc)


def kv_block_bounds(i, *, br, bc, nk, mask: MaskSpec):
    """Inclusive (lo, hi) of KV blocks that q block ``i`` can attend to.

    A KV block is in range iff *some* (qp, kp) pair in the (br × bc) tile is
    unmasked. ``hi < lo`` means the whole row is masked. ``lo >= 0`` and
    ``hi <= nk - 1`` always; ``hi`` may be negative when even KV block 0 is
    above the causal diagonal.
    """
    qs = mask.q_offset + i * br              # first q position of the block
    qe = qs + br - 1                         # last
    ko = mask.kv_offset
    # causal: block j reachable iff its first key ko + j*bc <= qe
    hi = _mn(nk - 1, (qe - ko) // bc) if mask.causal else nk - 1
    # window: block j reachable iff its last key ko+(j+1)*bc-1 >= qs-window+1
    lo = (_mx(0, _cdiv(qs - mask.window + 2 - ko, bc) - 1)
          if mask.window and mask.window > 0 else 0)
    # prefix re-opens the leading blocks (contiguous hull)
    pb = _prefix_blocks(mask, bc)
    if pb > 0:
        lo = 0
        hi = _mx(hi, _mn(nk - 1, pb - 1))
    # document (static layout): keys confined to [doc_start(qs), doc_end(qe)]
    if mask.document and mask.boundaries is not None:
        lo = _mx(lo, _mx(0, (mask.doc_start(qs) - ko) // bc))
        hi = _mn(hi, (mask.doc_end(qe) - ko) // bc)
    return lo, hi


def q_block_bounds(j, *, br, bc, nq, mask: MaskSpec):
    """Inclusive (lo, hi) of Q blocks that can attend to KV block ``j`` —
    the transpose of :func:`kv_block_bounds`, used by the dkv kernel (grid
    over KV blocks, sequential over Q blocks)."""
    ks = mask.kv_offset + j * bc             # first key position of the block
    ke = ks + bc - 1                         # last
    qo = mask.q_offset
    # causal: q block i reachable iff its last query >= ks
    lo = _mx(0, _cdiv(ks - qo + 1, br) - 1) if mask.causal else 0
    # window: q block i reachable iff its first query <= ke + window - 1
    hi = (_mn(nq - 1, (ke + mask.window - 1 - qo) // br)
          if mask.window and mask.window > 0 else nq - 1)
    # a key inside the prefix is visible to every query (hull)
    if mask.prefix_len and _static(ks) and ks < mask.prefix_len:
        return 0, nq - 1
    elif mask.prefix_len and not _static(ks):
        pre = ks < mask.prefix_len
        lo = _where(pre, 0, lo)
        hi = _where(pre, nq - 1, hi)
    if mask.document and mask.boundaries is not None:
        lo = _mx(lo, _mx(0, _cdiv(mask.doc_start(ks) - qo + 1, br) - 1))
        hi = _mn(hi, (mask.doc_end(ke) - qo) // br)
    return lo, hi


# --------------------------------------------------------------- profiles


@dataclasses.dataclass(frozen=True)
class GridProfile:
    """Static work profile of one pruned kernel launch.

    ``rows`` is the parallel grid dimension (q blocks for fwd/dq, kv blocks
    for dkv); ``row_counts[r]`` the number of valid sequential blocks for
    row ``r``. The pruned kernel launches ``rows × seq_grid`` steps and
    executes compute on ``executed_steps`` of them; the dense sweep runs
    ``full_steps``.
    """
    rows: int
    cols: int
    row_counts: tuple
    seq_grid: int          # pruned sequential trip count: max(row_counts)
    full_steps: int        # rows * cols — the dense sweep
    launched_steps: int    # rows * seq_grid
    executed_steps: int    # sum(row_counts) — steps that do MXU work

    @property
    def work_ratio(self) -> float:
        """Dense grid steps per executed pruned step (≥ 1)."""
        if self.executed_steps == 0:
            return float("inf") if self.full_steps else 1.0
        return self.full_steps / self.executed_steps


def _profile(rows, cols, counts) -> GridProfile:
    counts = tuple(int(max(0, c)) for c in counts)
    seq = max(counts) if counts else 0
    return GridProfile(rows=rows, cols=cols, row_counts=counts, seq_grid=seq,
                       full_steps=rows * cols, launched_steps=rows * seq,
                       executed_steps=sum(counts))


def kv_profile(*, nq, nk, br, bc, mask: MaskSpec) -> GridProfile:
    """Work profile of the fwd/dq orientation (rows = q blocks)."""
    counts = []
    for i in range(nq):
        lo, hi = kv_block_bounds(i, br=br, bc=bc, nk=nk, mask=mask)
        counts.append(hi - lo + 1)
    return _profile(nq, nk, counts)


def q_profile(*, nq, nk, br, bc, mask: MaskSpec) -> GridProfile:
    """Work profile of the dkv orientation (rows = kv blocks)."""
    counts = []
    for j in range(nk):
        lo, hi = q_block_bounds(j, br=br, bc=bc, nq=nq, mask=mask)
        counts.append(hi - lo + 1)
    return _profile(nk, nq, counts)
