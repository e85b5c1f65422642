"""``chunked-lax`` attention backend: a ``lax.scan``-blocked online-softmax
implementation that needs no Pallas — fast on CPU/GPU, exact everywhere.

The KV sequence is split into ``block_kv``-sized chunks; a scan walks the
chunks carrying the float32 ``(o, lse)`` accumulator and folds each chunk's
partial result in with the FlashAttention-2 rescale (``merge_ref``). Peak
score memory is O(Tq · block_kv) per step instead of the reference
implementation's O(Tq · Tk) — the same blocking the Pallas kernel does in
VMEM, expressed at the XLA level.

Masking is a :class:`repro.core.mask.MaskSpec`; document segment IDs ride
the scan as per-chunk slices next to K/V. Block-sparse pruning mirrors the
Pallas kernels: the scan only visits the KV chunks inside
``block_sparse.kv_block_bounds`` (the whole query chunk is one q block
here) — including the document-boundary pruning of packed batches — so CPU
CI exercises the identical block-range logic the TPU grid pruning uses.
Statically all-masked requests short-circuit to the empty partial.

Backward mirrors FA2: dq accumulates across the chunk scan while per-chunk
(dk, dv) are emitted as scan outputs and reassembled (zeros for pruned
chunks), all from the saved ``(o, lse)`` — no forward recompute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.mask import MaskSpec, as_spec, fold_offsets
from repro.kernels.block_sparse import kv_block_bounds
from repro.kernels.block_sparse import pick_block as _pick_block
from repro.kernels.ref import (NEG_INF, chunk_attn_bwd_ref, chunk_attn_ref,
                               merge_ref)

DEFAULT_BLOCK_KV = 128


def _blocked(x, nb, bc):
    """(B, Tk, ...) -> (nb, B, bc, ...) scan-leading chunk layout."""
    B = x.shape[0]
    return x.reshape(B, nb, bc, *x.shape[2:]).swapaxes(0, 1)


def _valid_span(Tq, Tk, bc, mask: MaskSpec, prune):
    """Inclusive (lo, hi) KV-chunk range for the whole query chunk (one
    br=Tq q block) — the same static range logic the Pallas grids use."""
    nb = Tk // bc
    if not (prune and mask.prunable):
        return 0, nb - 1
    return kv_block_bounds(0, br=Tq, bc=bc, nk=nb, mask=mask)


def _seg_chunks(seg, sl, nv, bc):
    if seg is None:
        return None
    return _blocked(jnp.asarray(seg)[:, sl], nv, bc)


def chunked_fwd(q, k, v, *, mask=None, causal=False, rel_offset=0, window=0,
                scale=None, block_kv=DEFAULT_BLOCK_KV, prune=True,
                q_segments=None, kv_segments=None, q_offset=0, kv_offset=0):
    """Partial attention, chunk_attn semantics: returns (o, lse). Only
    keys are blocked (``block_kv``); queries are not. ``q_offset``/
    ``kv_offset`` are extra position operands (ints fold into the mask;
    traced values ride through to the reference kernel and disable static
    pruning)."""
    mask = as_spec(mask, causal=causal, window=window,
                   rel_offset=rel_offset)
    mask, q_offset, kv_offset, dyn = fold_offsets(mask, q_offset, kv_offset)
    B, Tq, Hq, _ = q.shape
    Tk = k.shape[1]
    Dv = v.shape[-1]
    bc = _pick_block(Tk, block_kv)
    # traced offsets leave the band location unknown: no static pruning
    lo, hi = _valid_span(Tq, Tk, bc, mask, prune and not dyn)
    if hi < lo:                                  # statically fully masked
        return (jnp.zeros((B, Tq, Hq, Dv), q.dtype),
                jnp.full((B, Tq, Hq), NEG_INF, jnp.float32))
    nv = hi - lo + 1
    if nv == 1:
        return chunk_attn_ref(q, k[:, lo * bc:(lo + 1) * bc],
                              v[:, lo * bc:(lo + 1) * bc], mask=mask,
                              q_offset=q_offset,
                              kv_offset=kv_offset + lo * bc, scale=scale,
                              q_segments=q_segments,
                              kv_segments=None if kv_segments is None else
                              jnp.asarray(kv_segments)[:,
                                                       lo * bc:(lo + 1) * bc])
    sl = slice(lo * bc, (hi + 1) * bc)
    blocks = (_blocked(k[:, sl], nv, bc), _blocked(v[:, sl], nv, bc),
              (lo + jnp.arange(nv, dtype=jnp.int32)) * bc,
              _seg_chunks(kv_segments, sl, nv, bc))

    def body(carry, blk):
        o_acc, l_acc = carry
        kj, vj, off, sj = blk
        o_j, l_j = chunk_attn_ref(q, kj, vj, mask=mask, q_offset=q_offset,
                                  kv_offset=kv_offset + off,
                                  scale=scale, q_segments=q_segments,
                                  kv_segments=sj)
        o_n, l_n = merge_ref(o_acc, l_acc, o_j.astype(jnp.float32), l_j)
        return (o_n, l_n), None

    init = (jnp.zeros((B, Tq, Hq, Dv), jnp.float32),
            jnp.full((B, Tq, Hq), NEG_INF, jnp.float32))
    (o, lse), _ = lax.scan(body, init, blocks)
    return o.astype(q.dtype), lse


def chunked_bwd(q, k, v, o, lse, do, *, mask=None, causal=False,
                rel_offset=0, window=0, scale=None, delta=None,
                block_kv=DEFAULT_BLOCK_KV, prune=True,
                q_segments=None, kv_segments=None, q_offset=0, kv_offset=0):
    """FA2 backward from saved (o, lse), blocked over KV chunks.
    Returns (dq, dk, dv); dk/dv are zeros on statically-masked chunks."""
    mask = as_spec(mask, causal=causal, window=window,
                   rel_offset=rel_offset)
    mask, q_offset, kv_offset, dyn = fold_offsets(mask, q_offset, kv_offset)
    B, Tq, Hq, _ = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    bc = _pick_block(Tk, block_kv)
    lo, hi = _valid_span(Tq, Tk, bc, mask, prune and not dyn)
    if hi < lo:                                  # statically fully masked
        return (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v))
    if delta is None:
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)
    nv = hi - lo + 1
    sl = slice(lo * bc, (hi + 1) * bc)
    if nv == 1:
        dq, dk_s, dv_s = chunk_attn_bwd_ref(
            q, k[:, sl], v[:, sl], o, lse, do, mask=mask, q_offset=q_offset,
            kv_offset=kv_offset + lo * bc,
            scale=scale, delta=delta, q_segments=q_segments,
            kv_segments=None if kv_segments is None else
            jnp.asarray(kv_segments)[:, sl])
        dk = jnp.zeros_like(k).at[:, sl].set(dk_s)
        dv = jnp.zeros_like(v).at[:, sl].set(dv_s)
        return dq, dk, dv
    blocks = (_blocked(k[:, sl], nv, bc), _blocked(v[:, sl], nv, bc),
              (lo + jnp.arange(nv, dtype=jnp.int32)) * bc,
              _seg_chunks(kv_segments, sl, nv, bc))

    def body(dq_acc, blk):
        kj, vj, off, sj = blk
        dq_j, dk_j, dv_j = chunk_attn_bwd_ref(
            q, kj, vj, o, lse, do, mask=mask, q_offset=q_offset,
            kv_offset=kv_offset + off, scale=scale,
            delta=delta, q_segments=q_segments, kv_segments=sj)
        return dq_acc + dq_j.astype(jnp.float32), (dk_j, dv_j)

    dq, (dk_b, dv_b) = lax.scan(body, jnp.zeros(q.shape, jnp.float32),
                                blocks)
    dk_s = dk_b.swapaxes(0, 1).reshape(B, nv * bc, Hkv, -1)
    dv_s = dv_b.swapaxes(0, 1).reshape(B, nv * bc, Hkv, -1)
    dk = jnp.zeros_like(k).at[:, sl].set(dk_s.astype(k.dtype))
    dv = jnp.zeros_like(v).at[:, sl].set(dv_s.astype(v.dtype))
    return dq.astype(q.dtype), dk, dv
