"""Pallas TPU FlashAttention-2 chunk kernels (forward + backward),
block-sparse over the statically-maskable grid.

TARGET: TPU MXU/VMEM. Layout inside the kernels is (B, H, T, D); blocks are
``(block_q × head_dim)`` / ``(block_kv × head_dim)`` VMEM tiles with 128-
aligned matmul dims (MXU-native). Validated on CPU with ``interpret=True``
against ``ref.py`` (tests/test_kernels.py).

Chunk semantics match ``repro.core.attention.chunk_attn``: partial attention
under a static :class:`repro.core.mask.MaskSpec` (see DESIGN.md §2 — in the
ring/balanced schedules every step's mask depends only on the static chunk
distance, so no scalar prefetch is required). Document (packed-sequence)
masking is supported two ways:

  * dynamic ``q_segments``/``kv_segments`` (B, T) int32 arrays enter the
    kernels as (B, 1, T) with narrow ``(1, 1, block)`` blocks next to their
    q/kv tiles and are compared elementwise inside the mask;
  * a static ``mask.boundaries`` layout needs no arrays at all — segment
    IDs become trace-time iota comparisons AND the grid pruning below drops
    cross-document blocks entirely.

Block-sparse grid pruning (README §Block-sparse kernel pruning). Because
the MaskSpec is static, the valid KV-block range of every Q block — and its
transpose for the dkv kernel — is computed at trace time by
``block_sparse.kv_block_bounds`` / ``q_block_bounds``:

  * the sequential grid dimension is **shrunk** to ``max_i count(i)`` (the
    widest row of the trapezoid), not the dense ``nk``;
  * the index map remaps pruned step ``jj`` of row ``i`` to real block
    ``lo(i) + jj``, clamped to the row's last valid block so out-of-range
    steps revisit an already-resident block (no extra DMA) and skip compute
    under ``pl.when``;
  * every executed tile of a masked call pays the position mask + where,
    in line (:func:`_apply_mask`).

The backward follows FA2: ``delta = rowsum(do ⊙ o)`` precomputed, then a
dq-kernel (grid over q blocks, sequential kv) and a dkv-kernel (grid over kv
blocks, sequential q) recompute ``p = exp(s − lse)`` blockwise from the saved
logsumexp. ``lse``/``delta`` enter the kernels as (B, H, 1, T) arrays in
narrow ``(1, 1, 1, block_q)`` blocks — not lane-replicated (B, H, T, 128)
float32 broadcasts materialized in HBM. Mosaic requires a block's last two
dims to be (8k or full, 128k or full): the unit axis is full, and T sits on
the lanes, so ``block_q`` must be a multiple of 128 or all of T.
``tests/test_tpu_compile.py`` compiles the kernels for a described TPU v5e.

Tile shapes. A call without ``block_q``/``block_kv`` takes its tiles from
:func:`flash_tiles`, per kernel, from the call's own shape: the target
tile, narrowed where a causal diagonal crosses a short call, to a sliding
window and to :data:`VMEM_BUDGET`, then fitted to a divisor of T. Each grid step carries a fixed cost (pipeline
bookkeeping, DMA issue and wait, the index-map arithmetic) that a 128×128
tile is too small to hide; the targets come from a sweep of tile shapes on
a TPU v5e (``tools/flash_tile_sweep.py``, PERF.md).

Bodies. Each grid step does as little beside its matmuls as the math
allows: the dots take q, k, v and dO in their own dtype (bf16 inputs fill
the MXU's 16-row bf16 vregs; f32 inputs stay f32) and accumulate in f32,
with p and dS cast to that dtype just before the dot that consumes them;
``exp``, the softmax statistics and the accumulators stay f32. A
power-of-two scale rides the (block, d) q or k tile (exact), any other
multiplies the logits once; the gradient's scale is applied once to the
dq/dk accumulators. Fully masked rows are guarded on the (br,)
statistics, not the (br, bc) tile. The dkv kernel works kv-major
(sᵀ = k·qᵀ), so every dot contracts a tile's last dim and no (br, bc)
tile is transposed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mask import MaskSpec
from repro.kernels.block_sparse import (kv_block_bounds, kv_profile,
                                        pick_block, q_block_bounds, q_profile)

NEG_INF = -1e30
LANES = 128  # TPU lane width; stat scratch is lane-replicated
# tile edge every kernel aims for before the mask, the VMEM budget and T
# narrow it (the sweep on a TPU v5e that chose it: PERF.md)
TILE_TARGET = 1024
# scoped VMEM one grid step may take by ``_vmem_bytes``: Mosaic scopes
# 16 MiB for a kernel on v5e by default; the rest is the model's margin
VMEM_BUDGET = 14 * 2 ** 20


def _vmem_bytes(br, bc, d, dv, op, itemsize=2):
    """Scoped VMEM of a grid step of kernel ``op`` at tiles (br, bc): the
    double-buffered operand and result tiles, the f32 scratch, f32 copies
    of the tiles that change every step (kv for fwd and dq, q and dO for
    dkv) and two live f32 (br, bc) temporaries. From 0.9 MiB under to
    3.1 MiB over what the TPU compiler allocates at head dims 64–512 and
    tiles of 512–2048 (PERF.md)."""
    q_rows, kv_rows = br * (d + dv), bc * (d + dv)
    if op == "fwd":
        io, step = br * d + kv_rows + br * dv, kv_rows
        scratch = br * (dv + 2 * LANES)              # acc, m, l
    elif op == "dq":
        io, scratch, step = q_rows + kv_rows + br * d, br * d, kv_rows
    else:
        io, scratch, step = q_rows + 2 * kv_rows, kv_rows, q_rows
    return 2 * itemsize * io + 4 * (scratch + step + 2 * br * bc)


def _fit(T, block):
    """The tile edge for length T nearest under ``block``: all of T when
    it fits, else the largest multiple of 128 dividing T, else (no such
    divisor) :func:`pick_block`'s."""
    if T <= block:
        return T
    for b in range(block - block % LANES, 0, -LANES):
        if T % b == 0:
            return b
    return pick_block(T, block)


def flash_tiles(Tq, Tk, D, Dv, mask: MaskSpec, op, itemsize=2):
    """(block_q, block_kv) of flash kernel ``op`` (``"fwd"``, ``"dq"`` or
    ``"dkv"``) for a call over Tq queries and Tk keys at head dims D, Dv
    and operands of ``itemsize`` bytes: :data:`TILE_TARGET` on each edge,
    cut to a quarter of the shorter length where the causal diagonal
    crosses the call (a tile it cuts is computed whole, so four tiles a
    side hold that to a quarter more than the triangle) and to the
    sliding window where one is narrower (each rounded down to 128, at
    least 128), halved (the larger edge first) until :func:`_vmem_bytes`
    fits :data:`VMEM_BUDGET`, then fitted to T (:func:`_fit`)."""
    bq = bk = TILE_TARGET
    if mask.causal and mask.kv_offset + Tk - 1 > mask.q_offset:
        bq = bk = min(bq, max(LANES, min(Tq, Tk) // 4 // LANES * LANES))
    if mask.window and mask.window > 0:
        bq = bk = min(bq, max(LANES, mask.window // LANES * LANES))
    while (_vmem_bytes(bq, bk, D, Dv, op, itemsize) > VMEM_BUDGET
           and max(bq, bk) > LANES):
        if bk >= bq:
            bk //= 2
        else:
            bq //= 2
    return _fit(Tq, bq), _fit(Tk, bk)


def _tiles(q, k, v, mask, op, block_q, block_kv):
    """Tiles of one kernel call on (B, H, T, D) operands: an explicit hint
    wins on its axis (a non-dividing hint shrinks to a divisor of T);
    :func:`flash_tiles` gives the rest."""
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = flash_tiles(Tq, Tk, q.shape[3], v.shape[3], mask, op,
                         q.dtype.itemsize)
    return (bq if block_q is None else pick_block(Tq, block_q),
            bk if block_kv is None else pick_block(Tk, block_kv))


def _pos_mask(i, j, br, bc, mask: MaskSpec, q_seg=None, kv_seg=None,
              transpose=False):
    """(br, bc) boolean attend-mask for q block i, kv block j (static args
    except the traced program ids i, j and the segment vectors); with
    ``transpose``, the (bc, br) mask of the kv-major tile."""
    shape, qa, ka = ((bc, br), 1, 0) if transpose else ((br, bc), 0, 1)
    qp = (mask.q_offset + i * br
          + jax.lax.broadcasted_iota(jnp.int32, shape, qa))
    kp = (mask.kv_offset + j * bc
          + jax.lax.broadcasted_iota(jnp.int32, shape, ka))
    qs = None if q_seg is None else jnp.expand_dims(q_seg, ka)
    ks = None if kv_seg is None else jnp.expand_dims(kv_seg, qa)
    return mask.allow(qp, kp, qs, ks)


def _apply_mask(s, i, j, mask: MaskSpec, q_seg=None, kv_seg=None,
                transpose=False):
    """Mask score tile ``s`` for block (i, j): (br, bc), or (bc, br) with
    ``transpose``. Interior tiles, whose mask is all-True, pay the compare
    and select too: a branch that skipped them would carry the tile through
    it, and Mosaic stores and copies a branch's (br, bc) result on every
    step, which costs more than the mask (PERF.md §6)."""
    br, bc = s.shape[::-1] if transpose else s.shape
    return jnp.where(_pos_mask(i, j, br, bc, mask, q_seg, kv_seg, transpose),
                     s, NEG_INF)


def _row_span(i, br, bc, nk, mask, prune):
    """(first block, executed count) of the sequential sweep for row ``i``."""
    if not (prune and mask.prunable):
        return 0, nk
    lo, hi = kv_block_bounds(i, br=br, bc=bc, nk=nk, mask=mask)
    return lo, jnp.maximum(hi - lo + 1, 0)


def _kv_index(i, jj, br, bc, nk, mask, prune):
    """Index-map remap: pruned step jj of q-row i → real KV block. Steps
    past the row's range revisit the last valid block (no new DMA)."""
    if not (prune and mask.prunable):
        return jj
    lo, hi = kv_block_bounds(i, br=br, bc=bc, nk=nk, mask=mask)
    return jnp.clip(lo + jj, 0, jnp.maximum(hi, 0))


def _q_row_span(j, br, bc, nq, mask, prune):
    """Transpose of :func:`_row_span` for the dkv orientation: (first q
    block, executed count) of the sequential sweep for kv row ``j``."""
    if not (prune and mask.prunable):
        return 0, nq
    lo, hi = q_block_bounds(j, br=br, bc=bc, nq=nq, mask=mask)
    return lo, jnp.maximum(hi - lo + 1, 0)


def _q_index(j, ii, br, bc, nq, mask, prune):
    """Transpose of :func:`_kv_index`: pruned step ii of kv-row j → real Q
    block, clamped to revisit the row's last valid block."""
    if not (prune and mask.prunable):
        return ii
    lo, hi = q_block_bounds(j, br=br, bc=bc, nq=nq, mask=mask)
    return jnp.clip(lo + ii, 0, jnp.maximum(hi, 0))


def _check_segs(mask: MaskSpec, q_segments, kv_segments) -> bool:
    """True iff segment operands ride this launch; a half-supplied pair or
    a dynamic-document spec without one raises up front (not deep in the
    Pallas setup)."""
    if (q_segments is None) != (kv_segments is None):
        raise ValueError("q_segments and kv_segments must be passed "
                         "together")
    if mask.needs_segments and q_segments is None:
        raise ValueError("document mask without boundaries needs "
                         "q_segments/kv_segments")
    return q_segments is not None


def _seg_specs(br, bc, kv_block, *, dkv=False, q_block=None):
    """BlockSpecs of the (B, 1, T) segment-ID arrays for each grid
    orientation: narrow (1, 1, block) tiles riding next to their q/kv
    tiles."""
    if not dkv:
        return [pl.BlockSpec((1, 1, br), lambda b, h, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, bc),
                             lambda b, h, i, j: (b, 0, kv_block(i, j)))]
    return [pl.BlockSpec((1, 1, br),
                         lambda b, h, j, i: (b, 0, q_block(j, i))),
            pl.BlockSpec((1, 1, bc), lambda b, h, j, i: (b, 0, j))]


def _seg_operands(q_segments, kv_segments):
    """(B, T) segment IDs as the (B, 1, T) int32 kernel operands."""
    return [jnp.asarray(s, jnp.int32)[:, None, :]
            for s in (q_segments, kv_segments)]


def _stat_operand(x):
    """(B, H, T) row statistic as the (B, H, 1, T) float32 operand."""
    return x.astype(jnp.float32)[:, :, None, :]


def _grid_work(prof, pruned, n, br, bc, dtype, scale):
    """``metadata=`` of a flash call over ``n`` (batch, head) pairs at
    tiles (br, bc): the grid steps it launches, the steps whose compute
    body runs, the tiles (``block_q``, ``block_kv``), the dtype its MXU
    operands take (``operand_dtype``, the inputs') and whether the softmax
    scale rode an operand tile (``scale_folded``). Pruned, the grid
    is the profile's shrunk one and the body runs where the static mask
    reaches the tile; unpruned, the body runs on every step of the full
    grid. Tiles that only dynamic segment IDs mask still run (their scores
    are masked), so they count as executed. The compiled call carries
    these as ``kernel_metadata``, so a trace event can be matched to its
    own call's counts, tiling and body."""
    steps = ((prof.launched_steps, prof.executed_steps) if pruned
             else (prof.full_steps, prof.full_steps))
    return {"launched": n * steps[0], "executed": n * steps[1],
            "block_q": br, "block_kv": bc,
            "operand_dtype": jnp.dtype(dtype).name,
            "scale_folded": _scale_folds(scale)}


def _scale_folds(scale) -> bool:
    """True iff ``scale`` is a power of two, so multiplying a (block, d)
    operand tile by it before the MXU is exact and the logits' (br, bc)
    ``* scale`` can go (head_dim 64 and 256; not 128)."""
    return math.frexp(scale)[0] == 0.5


def _lse_safe(lse):
    """Saved row logsumexp with a fully masked row's ``NEG_INF`` turned to
    ``-NEG_INF``: ``exp(s - lse)`` is then 0 across that row whatever its
    scores, with the guard on the (br,) vector and not the (br, bc) tile."""
    return jnp.where(lse <= NEG_INF / 2, -NEG_INF, lse)


def _dot(a, b):
    """a·b on the MXU: ``a`` cast to ``b``'s dtype (a bf16 call pushes bf16
    operands; an f32 call keeps f32), accumulated in f32."""
    return jax.lax.dot(a.astype(b.dtype), b,
                       preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a·bᵀ, contracting both operands' last (head) dim, in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _logits(a, b, scale):
    """a·bᵀ × ``scale`` in f32. Where the scale folds (:func:`_scale_folds`)
    it multiplies ``a``'s (block, d) tile in f32 before the cast back,
    exactly, and no (br, bc) pass is spent on it."""
    if _scale_folds(scale):
        return _dot_nt((a.astype(jnp.float32) * scale).astype(a.dtype), b)
    return _dot_nt(a, b) * scale


# ---------------------------------------------------------------- forward


def _fwd_kernel(*refs, scale, mask, nk, prune, has_segs):
    if has_segs:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qs_ref = ks_ref = None
    i, jj = pl.program_id(2), pl.program_id(3)
    br, bc = q_ref.shape[2], k_ref.shape[2]
    lo, count = _row_span(i, br, bc, nk, mask, prune)
    j = lo + jj

    @pl.when(jj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jj < count)
    def _compute():
        q = q_ref[0, 0]                                  # (br, d)
        k = k_ref[0, 0]                                  # (bc, d)
        v = v_ref[0, 0]

        s = _logits(q, k, scale)
        if mask.needs_mask:
            q_seg = None if qs_ref is None else qs_ref[0, 0]
            kv_seg = None if ks_ref is None else ks_ref[0, 0]
            s = _apply_mask(s, i, j, mask, q_seg, kv_seg)

        m_prev = m_ref[:, 0]                             # (br,)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[:, None])         # masked rows: exp(-5e29) = 0
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + _dot(p, v)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    # all-masked rows (count == 0) finalize straight from the init state
    @pl.when(jj == jnp.maximum(count - 1, 0))
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = jnp.where(l == 0.0, NEG_INF, m_ref[:, 0] +
                                     jnp.log(l_safe))


def flash_fwd_bhtd(q, k, v, *, scale, mask: MaskSpec, block_q=None,
                   block_kv=None, interpret=False, prune=True,
                   q_segments=None, kv_segments=None):
    """q,k: (B,Hq/Hkv,T,Dk); v: (B,Hkv,Tk,Dv) -> o (B,Hq,Tq,Dv), lse.
    Dv may differ from Dk (MLA). ``q_segments``/``kv_segments`` are (B, T)
    int32 document IDs (document kind). ``block_q``/``block_kv`` left None
    come from :func:`flash_tiles`. ``prune=False`` forces the dense sweep
    (benchmark baseline / differential testing)."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    g = Hq // Hkv
    br, bc = _tiles(q, k, v, mask, "fwd", block_q, block_kv)
    nq, nk = Tq // br, Tk // bc
    has_segs = _check_segs(mask, q_segments, kv_segments)

    seq = nk
    pruned = prune and mask.prunable
    prof = kv_profile(nq=nq, nk=nk, br=br, bc=bc, mask=mask)
    if pruned:
        seq = prof.seq_grid
        if seq == 0:                      # statically fully masked chunk
            return (jnp.zeros((B, Hq, Tq, Dv), q.dtype),
                    jnp.full((B, Hq, Tq), NEG_INF, jnp.float32))
    grid = (B, Hq, nq, seq)

    def kv_block(i, j):
        return _kv_index(i, j, br, bc, nk, mask, prune)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, mask=mask, nk=nk, prune=prune,
        has_segs=has_segs)
    in_specs = [
        pl.BlockSpec((1, 1, br, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bc, D),
                     lambda b, h, i, j: (b, h // g, kv_block(i, j), 0)),
        pl.BlockSpec((1, 1, bc, Dv),
                     lambda b, h, i, j: (b, h // g, kv_block(i, j), 0)),
    ]
    operands = [q, k, v]
    if has_segs:
        in_specs += _seg_specs(br, bc, kv_block)
        operands += _seg_operands(q_segments, kv_segments)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, br, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, br), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, Dv), jnp.float32),
            pltpu.VMEM((br, LANES), jnp.float32),
            pltpu.VMEM((br, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
        metadata=_grid_work(prof, pruned, B * Hq, br, bc, q.dtype, scale),
    )(*operands)
    return o, lse.reshape(B, Hq, Tq)


# ---------------------------------------------------------------- backward


def _dq_kernel(*refs, scale, mask, nk, prune, has_segs):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
        qs_ref = ks_ref = None
    i, jj = pl.program_id(2), pl.program_id(3)
    br, bc = q_ref.shape[2], k_ref.shape[2]
    lo, count = _row_span(i, br, bc, nk, mask, prune)
    j = lo + jj

    @pl.when(jj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jj < count)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = _lse_safe(lse_ref[0, 0, 0])                # (br,)
        delta = delta_ref[0, 0, 0]

        s = _logits(q, k, scale)
        if mask.needs_mask:
            q_seg = None if qs_ref is None else qs_ref[0, 0]
            kv_seg = None if ks_ref is None else ks_ref[0, 0]
            s = _apply_mask(s, i, j, mask, q_seg, kv_seg)
        p = jnp.exp(s - lse[:, None])
        dp = _dot_nt(do, v)
        ds = p * (dp - delta[:, None])
        acc_ref[...] += _dot(ds, k)

    @pl.when(jj == jnp.maximum(count - 1, 0))
    def _finalize():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, mask, nq, prune, has_segs):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    j, ii = pl.program_id(2), pl.program_id(3)       # kv block j, q step ii
    br, bc = q_ref.shape[2], k_ref.shape[2]
    lo_q, count = _q_row_span(j, br, bc, nq, mask, prune)
    i = lo_q + ii

    @pl.when(ii == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ii < count)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = _lse_safe(lse_ref[0, 0, 0])
        delta = delta_ref[0, 0, 0]

        # kv-major: every (bc, br) tile is used as computed, none transposed
        st = _logits(k, q, scale)                        # sᵀ
        if mask.needs_mask:
            q_seg = None if qs_ref is None else qs_ref[0, 0]
            kv_seg = None if ks_ref is None else ks_ref[0, 0]
            st = _apply_mask(st, i, j, mask, q_seg, kv_seg, transpose=True)
        pt = jnp.exp(st - lse[None, :])
        dv_acc[...] += _dot(pt, do)
        dpt = _dot_nt(v, do)
        dst = pt * (dpt - delta[None, :])
        dk_acc[...] += _dot(dst, q)

    @pl.when(ii == jnp.maximum(count - 1, 0))
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd_bhtd(q, k, v, o, lse, do, *, scale, mask: MaskSpec,
                   block_q=None, block_kv=None, interpret=False, delta=None,
                   prune=True, q_segments=None, kv_segments=None):
    """Backward from saved (o, lse). Layout (B,H,T,D). Returns dq, dk, dv
    (dk/dv summed over the GQA group). ``delta`` (B,H,Tq) may be passed
    precomputed (distributed helper path). The dq and dkv kernels take
    their tiles each from :func:`flash_tiles` where ``block_q``/
    ``block_kv`` are None; a hint applies to both."""
    has_segs = _check_segs(mask, q_segments, kv_segments)
    if delta is None:
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)
    operands = [q, k, v, do, _stat_operand(lse), _stat_operand(delta)]
    if has_segs:
        operands += _seg_operands(q_segments, kv_segments)
    kw = dict(scale=scale, mask=mask, interpret=interpret, prune=prune,
              has_segs=has_segs)
    dq = _dq_call(operands, *_tiles(q, k, v, mask, "dq", block_q, block_kv),
                  **kw)
    dk, dv = _dkv_call(operands,
                       *_tiles(q, k, v, mask, "dkv", block_q, block_kv), **kw)
    return dq, dk, dv


def _dq_call(operands, br, bc, *, scale, mask, interpret, prune, has_segs):
    """dq kernel at tiles (br, bc): grid over q blocks, sequential over the
    valid kv blocks."""
    q, k, v = operands[:3]
    B, Hq, Tq, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    g = Hq // Hkv
    nq, nk = Tq // br, Tk // bc
    pruned = prune and mask.prunable
    prof = kv_profile(nq=nq, nk=nk, br=br, bc=bc, mask=mask)
    seq = prof.seq_grid if pruned else nk
    if seq == 0:                          # statically fully masked chunk
        return jnp.zeros(q.shape, q.dtype)

    def kv_block(i, j):
        return _kv_index(i, j, br, bc, nk, mask, prune)

    q_spec = pl.BlockSpec((1, 1, br, D), lambda b, h, i, j: (b, h, i, 0))
    stat_spec = pl.BlockSpec((1, 1, 1, br), lambda b, h, i, j: (b, h, 0, i))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, 1, bc, D),
                     lambda b, h, i, j: (b, h // g, kv_block(i, j), 0)),
        pl.BlockSpec((1, 1, bc, Dv),
                     lambda b, h, i, j: (b, h // g, kv_block(i, j), 0)),
        pl.BlockSpec((1, 1, br, Dv), lambda b, h, i, j: (b, h, i, 0)),
        stat_spec, stat_spec]
    if has_segs:
        in_specs += _seg_specs(br, bc, kv_block)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, mask=mask, nk=nk,
                          prune=prune, has_segs=has_segs),
        grid=(B, Hq, nq, seq),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((br, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bwd.dq",
        metadata=_grid_work(prof, pruned, B * Hq, br, bc, q.dtype, scale),
    )(*operands)


def _dkv_call(operands, br, bc, *, scale, mask, interpret, prune,
              has_segs):
    """dkv kernel at tiles (br, bc): grid over kv blocks, sequential over
    the valid q blocks. Output per *query* head, then group-summed (GQA).
    """
    q, k, v = operands[:3]
    B, Hq, Tq, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    g = Hq // Hkv
    nq, nk = Tq // br, Tk // bc
    pruned = prune and mask.prunable
    prof = q_profile(nq=nq, nk=nk, br=br, bc=bc, mask=mask)
    seq = prof.seq_grid if pruned else nq
    if seq == 0:                          # statically fully masked chunk
        return jnp.zeros(k.shape, k.dtype), jnp.zeros(v.shape, v.dtype)

    def q_block(j, i):
        return _q_index(j, i, br, bc, nq, mask, prune)

    stat_spec = pl.BlockSpec((1, 1, 1, br),
                             lambda b, h, j, i: (b, h, 0, q_block(j, i)))
    in_specs = [
        pl.BlockSpec((1, 1, br, D),
                     lambda b, h, j, i: (b, h, q_block(j, i), 0)),
        pl.BlockSpec((1, 1, bc, D), lambda b, h, j, i: (b, h // g, j, 0)),
        pl.BlockSpec((1, 1, bc, Dv), lambda b, h, j, i: (b, h // g, j, 0)),
        pl.BlockSpec((1, 1, br, Dv),
                     lambda b, h, j, i: (b, h, q_block(j, i), 0)),
        stat_spec, stat_spec]
    if has_segs:
        in_specs += _seg_specs(br, bc, None, dkv=True, q_block=q_block)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, mask=mask, nq=nq,
                          prune=prune, has_segs=has_segs),
        grid=(B, Hq, nk, seq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bc, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bc, Dv), lambda b, h, j, i: (b, h, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hq, Tk, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bc, D), jnp.float32),
                        pltpu.VMEM((bc, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bwd.dkv",
        metadata=_grid_work(prof, pruned, B * Hq, br, bc, q.dtype, scale),
    )(*operands)
    if g > 1:
        dk_h = dk_h.reshape(B, Hkv, g, Tk, D).sum(axis=2)
        dv_h = dv_h.reshape(B, Hkv, g, Tk, Dv).sum(axis=2)
    return dk_h.astype(k.dtype), dv_h.astype(v.dtype)
