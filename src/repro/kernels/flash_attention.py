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
  * blocks the mask cannot touch (``interior_kv_bounds``) take a mask-free
    fast path — only diagonal/window-edge/document-boundary tiles pay the
    position mask + where.

The backward follows FA2: ``delta = rowsum(do ⊙ o)`` precomputed, then a
dq-kernel (grid over q blocks, sequential kv) and a dkv-kernel (grid over kv
blocks, sequential q) recompute ``p = exp(s − lse)`` blockwise from the saved
logsumexp. ``lse``/``delta`` enter the kernels as (B, H, 1, T) arrays in
narrow ``(1, 1, 1, block_q)`` blocks — not lane-replicated (B, H, T, 128)
float32 broadcasts materialized in HBM. Mosaic requires a block's last two
dims to be (8k or full, 128k or full): the unit axis is full, and T sits on
the lanes, so ``block_q`` must be a multiple of 128 or all of T.
``tests/test_tpu_compile.py`` compiles the kernels for a described TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mask import MaskSpec
from repro.kernels.block_sparse import (interior_kv_bounds, kv_block_bounds,
                                        kv_profile, pick_block,
                                        q_block_bounds, q_profile)

NEG_INF = -1e30
LANES = 128  # TPU lane width; stat scratch is lane-replicated


def _pos_mask(i, j, br, bc, mask: MaskSpec, q_seg=None, kv_seg=None):
    """(br, bc) boolean attend-mask for q block i, kv block j (static args
    except the traced program ids i, j and the segment vectors)."""
    qp = (mask.q_offset + i * br
          + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0))
    kp = (mask.kv_offset + j * bc
          + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1))
    qs = None if q_seg is None else q_seg[:, None]
    ks = None if kv_seg is None else kv_seg[None, :]
    return mask.allow(qp, kp, qs, ks)


def _apply_mask(s, i, j, mask: MaskSpec, prune, q_seg=None, kv_seg=None):
    """Mask score tile ``s`` for block (i, j). With pruning, interior blocks
    (mask provably all-True) skip the iota/compare/where entirely via a
    runtime branch — only edge tiles pay for ``_pos_mask``."""
    br, bc = s.shape

    def _m(x):
        return jnp.where(_pos_mask(i, j, br, bc, mask, q_seg, kv_seg),
                         x, NEG_INF)

    if not prune:
        return _m(s)
    lo_f, hi_f = interior_kv_bounds(i, br=br, bc=bc, nk=2 ** 30, mask=mask)
    return jax.lax.cond((j < lo_f) | (j > hi_f), _m, lambda x: x, s)


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


def _grid_work(prof, pruned, n):
    """``metadata=`` of a flash call over ``n`` (batch, head) pairs: the
    grid steps it launches and the steps whose compute body runs. Pruned,
    the grid is the profile's shrunk one and the body runs where the
    static mask reaches the tile; unpruned, the body runs on every step
    of the full grid. Tiles that only dynamic segment IDs mask still run
    (their scores are masked), so they count as executed. The compiled
    call carries the counts as ``kernel_metadata``, so a trace event can
    be matched to its own call's counts."""
    if pruned:
        return {"launched": n * prof.launched_steps,
                "executed": n * prof.executed_steps}
    return {"launched": n * prof.full_steps, "executed": n * prof.full_steps}


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
        q = q_ref[0, 0].astype(jnp.float32)              # (br, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bc, d)
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if mask.needs_mask:
            q_seg = None if qs_ref is None else qs_ref[0, 0]
            kv_seg = None if ks_ref is None else ks_ref[0, 0]
            s = _apply_mask(s, i, j, mask, prune, q_seg, kv_seg)

        m_prev = m_ref[:, 0]                             # (br,)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(m_new[:, None] <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)
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


def flash_fwd_bhtd(q, k, v, *, scale, mask: MaskSpec, block_q=128,
                   block_kv=128, interpret=False, prune=True,
                   q_segments=None, kv_segments=None):
    """q,k: (B,Hq/Hkv,T,Dk); v: (B,Hkv,Tk,Dv) -> o (B,Hq,Tq,Dv), lse.
    Dv may differ from Dk (MLA). ``q_segments``/``kv_segments`` are (B, T)
    int32 document IDs (document kind). ``prune=False`` forces the dense
    sweep (benchmark baseline / differential testing)."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    g = Hq // Hkv
    br = pick_block(Tq, block_q)      # non-dividing hints shrink to a divisor
    bc = pick_block(Tk, block_kv)
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
        metadata=_grid_work(prof, pruned, B * Hq),
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
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]                           # (br,)
        delta = delta_ref[0, 0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if mask.needs_mask:
            q_seg = None if qs_ref is None else qs_ref[0, 0]
            kv_seg = None if ks_ref is None else ks_ref[0, 0]
            s = _apply_mask(s, i, j, mask, prune, q_seg, kv_seg)
        p = jnp.where(lse[:, None] <= NEG_INF / 2, 0.0,
                      jnp.exp(s - lse[:, None]))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += jax.lax.dot(ds, k)

    @pl.when(jj == jnp.maximum(count - 1, 0))
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


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
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if mask.needs_mask:
            q_seg = None if qs_ref is None else qs_ref[0, 0]
            kv_seg = None if ks_ref is None else ks_ref[0, 0]
            s = _apply_mask(s, i, j, mask, prune, q_seg, kv_seg)
        p = jnp.where(lse[:, None] <= NEG_INF / 2, 0.0,
                      jnp.exp(s - lse[:, None]))
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(ii == jnp.maximum(count - 1, 0))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd_bhtd(q, k, v, o, lse, do, *, scale, mask: MaskSpec,
                   block_q=128, block_kv=128, interpret=False, delta=None,
                   prune=True, q_segments=None, kv_segments=None):
    """Backward from saved (o, lse). Layout (B,H,T,D). Returns dq, dk, dv
    (dk/dv summed over the GQA group). ``delta`` (B,H,Tq) may be passed
    precomputed (distributed helper path)."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    g = Hq // Hkv
    br = pick_block(Tq, block_q)      # non-dividing hints shrink to a divisor
    bc = pick_block(Tk, block_kv)
    nq, nk = Tq // br, Tk // bc
    has_segs = _check_segs(mask, q_segments, kv_segments)

    if delta is None:
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)
    stats = [_stat_operand(lse), _stat_operand(delta)]

    pruned = prune and mask.prunable
    kv_prof = kv_profile(nq=nq, nk=nk, br=br, bc=bc, mask=mask)
    q_prof = q_profile(nq=nq, nk=nk, br=br, bc=bc, mask=mask)
    seq_kv, seq_q = nk, nq
    if pruned:
        seq_kv, seq_q = kv_prof.seq_grid, q_prof.seq_grid
    if pruned and (seq_kv == 0 or seq_q == 0):   # statically fully masked
        return (jnp.zeros(q.shape, q.dtype),
                jnp.zeros((B, Hkv, Tk, D), k.dtype),
                jnp.zeros((B, Hkv, Tk, Dv), v.dtype))

    seg_ops = _seg_operands(q_segments, kv_segments) if has_segs else []

    def kv_block(i, j):
        return _kv_index(i, j, br, bc, nk, mask, prune)

    q_spec = pl.BlockSpec((1, 1, br, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bc, D), lambda b, h, i, j: (b, h // g, kv_block(i, j), 0))
    v_spec = pl.BlockSpec(
        (1, 1, bc, Dv), lambda b, h, i, j: (b, h // g, kv_block(i, j), 0))
    do_spec = pl.BlockSpec((1, 1, br, Dv), lambda b, h, i, j: (b, h, i, 0))
    stat_spec = pl.BlockSpec((1, 1, 1, br), lambda b, h, i, j: (b, h, 0, i))

    in_specs = [q_spec, kv_spec, v_spec, do_spec, stat_spec, stat_spec]
    if has_segs:
        in_specs += _seg_specs(br, bc, kv_block)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, mask=mask, nk=nk,
                          prune=prune, has_segs=has_segs),
        grid=(B, Hq, nq, seq_kv),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((br, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bwd.dq",
        metadata=_grid_work(kv_prof, pruned, B * Hq),
    )(q, k, v, do, *stats, *seg_ops)

    # dkv: grid over kv blocks, sequential over the valid q blocks. Output
    # per *query* head, then group-summed below (GQA).
    def q_block(j, i):
        return _q_index(j, i, br, bc, nq, mask, prune)

    q_spec2 = pl.BlockSpec((1, 1, br, D),
                           lambda b, h, j, i: (b, h, q_block(j, i), 0))
    kv_spec2 = pl.BlockSpec((1, 1, bc, D), lambda b, h, j, i: (b, h // g, j, 0))
    v_spec2 = pl.BlockSpec((1, 1, bc, Dv), lambda b, h, j, i: (b, h // g, j, 0))
    do_spec2 = pl.BlockSpec((1, 1, br, Dv),
                            lambda b, h, j, i: (b, h, q_block(j, i), 0))
    k_out2 = pl.BlockSpec((1, 1, bc, D), lambda b, h, j, i: (b, h, j, 0))
    v_out2 = pl.BlockSpec((1, 1, bc, Dv), lambda b, h, j, i: (b, h, j, 0))
    stat_spec2 = pl.BlockSpec((1, 1, 1, br),
                              lambda b, h, j, i: (b, h, 0, q_block(j, i)))
    in_specs2 = [q_spec2, kv_spec2, v_spec2, do_spec2, stat_spec2,
                 stat_spec2]
    if has_segs:
        in_specs2 += _seg_specs(br, bc, kv_block, dkv=True, q_block=q_block)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, mask=mask, nq=nq,
                          prune=prune, has_segs=has_segs),
        grid=(B, Hq, nk, seq_q),
        in_specs=in_specs2,
        out_specs=[k_out2, v_out2],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hq, Tk, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bc, D), jnp.float32),
                        pltpu.VMEM((bc, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bwd.dkv",
        metadata=_grid_work(q_prof, pruned, B * Hq),
    )(q, k, v, do, *stats, *seg_ops)
    if g > 1:
        dk_h = dk_h.reshape(B, Hkv, g, Tk, D).sum(axis=2)
        dv_h = dv_h.reshape(B, Hkv, g, Tk, Dv).sum(axis=2)
    return dq, dk_h.astype(k.dtype), dv_h.astype(v.dtype)
