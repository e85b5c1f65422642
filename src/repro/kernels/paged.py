"""Paged flash-decode kernels: one-token decode attention that gathers its
KV context through a *block table* instead of a contiguous cache.

The serving engine (serve/engine.py) stores KV in a fixed pool of
``block_size``-token blocks (serve/cache.py); each request owns an ordered
list of (arbitrarily located) block ids.  The new token's K/V is scattered
into the request's current block *before* attention, so the kernels see one
uniform layout:

  q            (B, Tq, Hq, Dq)      the decode-step queries — ``Tq = 1``
                                    for vanilla decode; ``Tq = K+1`` for a
                                    speculative verification chunk (the
                                    queries are the *last Tq tokens* of the
                                    context: row ``t`` sits at position
                                    ``lengths[b] − Tq + t``)
  k_pool       (N, bs, Hkv, Dk)     one layer's key pool (N = pool blocks)
  v_pool       (N, bs, Hkv, Dv)     value pool (MLA: a narrow view of k)
  block_table  (B, nb) int32        request b's i-th block id (0 = the
                                    reserved null block for unused entries)
  lengths      (B,) int32           attendable tokens incl. the new ones;
                                    request b's last query sits at
                                    lengths[b]−1

Masking reuses :class:`repro.core.mask.MaskSpec`, restricted to the two
kinds a decode step can express — ``causal`` (whole context) and
``sliding_window`` — evaluated per batch row against ``lengths`` (token
``j`` of the virtual contiguous context is attendable iff ``j < len_b`` and,
windowed, ``len_b − 1 − j < w``).  Out-of-range table entries point at the
null block and are masked by ``lengths``, so fragmented / out-of-order /
partially-filled tables need no special cases.

Three implementations, registered on the existing backends via the
``paged`` capability flag (kernels/registry.py):

  * :func:`paged_attn_ref`      — pure-jnp oracle: gathers the whole table
                                  and materializes the (B, H, T) scores.
  * :func:`paged_attn_chunked`  — ``lax.scan`` over table entries with the
                                  FA2 online-softmax merge; peak score
                                  memory O(B · block_size).
  * :func:`paged_attn_pallas`   — Pallas TPU kernel; the block table rides
                                  as a scalar-prefetch operand and the KV
                                  BlockSpec index maps gather pool blocks
                                  directly (one DMA per table entry).
                                  ``interpret=True`` validates it anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mask as mk
from repro.core.mask import MaskSpec
from repro.kernels.ref import NEG_INF, merge_ref

LANES = 128


def _check(q, k_pool, v_pool, block_table, lengths, mask: MaskSpec):
    if q.shape[1] < 1:
        raise ValueError(f"paged decode takes >= 1 query tokens, got "
                         f"Tq={q.shape[1]}")
    if mask.kinds - {"causal", "sliding_window"}:
        raise ValueError(
            f"paged decode serves causal/sliding_window masks only "
            f"(got {mask.kind!r})")
    if mask.q_offset or mask.kv_offset:
        raise ValueError("paged decode mask must be offset-free — positions "
                         "come from `lengths`")
    if k_pool.shape[:3] != (v_pool.shape[0], v_pool.shape[1],
                            v_pool.shape[2]):
        raise ValueError(f"k_pool/v_pool disagree: {k_pool.shape} vs "
                         f"{v_pool.shape}")
    if q.shape[2] % k_pool.shape[2]:
        raise ValueError(f"Hq={q.shape[2]} not a multiple of "
                         f"Hkv={k_pool.shape[2]}")


def _allow_tokens(mask: MaskSpec, kpos, lengths, Tq: int = 1):
    """(B, Tq, T) attendability of virtual context position ``kpos`` (T,)
    for per-request ``lengths`` (B,): query row ``t`` sits at context
    position ``lengths[b] − Tq + t`` and attends causally (optionally
    windowed) from there."""
    qpos = (lengths[:, None] - Tq
            + jnp.arange(Tq, dtype=jnp.int32)[None, :])       # (B, Tq)
    ok = kpos[None, None, :] <= qpos[:, :, None]
    if mask.window and mask.window > 0:
        ok = ok & (kpos[None, None, :] > qpos[:, :, None] - mask.window)
    return ok


# --------------------------------------------------------------- reference

def paged_attn_ref(q, k_pool, v_pool, block_table, lengths, *, mask=None,
                   scale=None):
    """Oracle: gather the whole table, materialize the scores. Returns
    o (B, Tq, Hq, Dv)."""
    mask = mask if mask is not None else mk.causal()
    _check(q, k_pool, v_pool, block_table, lengths, mask)
    B, Tq, Hq, Dq = q.shape
    nb = block_table.shape[1]
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / (Dq ** 0.5)
    kg = k_pool[block_table].reshape(B, nb * bs, Hkv, -1)
    vg = v_pool[block_table].reshape(B, nb * bs, Hkv, -1)
    if g > 1:
        kg = jnp.repeat(kg, g, axis=2)
        vg = jnp.repeat(vg, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * sc
    ok = _allow_tokens(mask, jnp.arange(nb * bs), lengths, Tq)
    s = jnp.where(ok[:, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0,
                  jnp.exp(s - m_safe[..., None]))
    den = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vg.astype(jnp.float32))
    o = o / jnp.where(den == 0.0, 1.0, den).transpose(0, 2, 1)[..., None]
    o = jnp.where((den == 0.0).transpose(0, 2, 1)[..., None], 0.0, o)
    return o.astype(q.dtype)


# ------------------------------------------------------------- chunked-lax

def paged_attn_chunked(q, k_pool, v_pool, block_table, lengths, *,
                       mask=None, scale=None):
    """``lax.scan`` over the table entries with the online-softmax merge —
    the memory-efficient CPU/GPU path (and the reference for the Pallas
    kernel's loop structure)."""
    mask = mask if mask is not None else mk.causal()
    _check(q, k_pool, v_pool, block_table, lengths, mask)
    B, Tq, Hq, Dq = q.shape
    nb = block_table.shape[1]
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    Dv = v_pool.shape[-1]
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / (Dq ** 0.5)
    qf = q.astype(jnp.float32)
    bt = jnp.swapaxes(jnp.asarray(block_table, jnp.int32), 0, 1)  # (nb, B)
    offs = jnp.arange(nb, dtype=jnp.int32) * bs

    def body(carry, xs):
        o_acc, l_acc = carry
        ids, off = xs
        kj = k_pool[ids]                             # (B, bs, Hkv, Dk)
        vj = v_pool[ids]
        if g > 1:
            kj = jnp.repeat(kj, g, axis=2)
            vj = jnp.repeat(vj, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj.astype(jnp.float32)) * sc
        ok = _allow_tokens(mask, off + jnp.arange(bs), lengths, Tq)
        s = jnp.where(ok[:, None, :, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.maximum(m, NEG_INF / 2)
        p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0,
                      jnp.exp(s - m_safe[..., None]))
        den = jnp.sum(p, axis=-1)                     # (B, H, 1)
        o_j = jnp.einsum("bhqk,bkhd->bqhd", p, vj.astype(jnp.float32))
        o_j = o_j / jnp.where(den == 0.0, 1.0,
                              den).transpose(0, 2, 1)[..., None]
        o_j = jnp.where((den == 0.0).transpose(0, 2, 1)[..., None], 0.0, o_j)
        lse_j = jnp.where(den == 0.0, NEG_INF,
                          m_safe + jnp.log(jnp.where(den == 0.0, 1.0, den))
                          ).transpose(0, 2, 1)        # (B, 1, H)
        return merge_ref(o_acc, l_acc, o_j, lse_j), None

    init = (jnp.zeros((B, Tq, Hq, Dv), jnp.float32),
            jnp.full((B, Tq, Hq), NEG_INF, jnp.float32))
    (o, _), _ = lax.scan(body, init, (bt, offs))
    return o.astype(q.dtype)


# ------------------------------------------------------------------ pallas

def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, mask: MaskSpec, bs, nb,
                  Tq):
    b, i = pl.program_id(0), pl.program_id(2)
    gT = q_ref.shape[2]                  # g · Tq rows: row r = gi·Tq + t

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (gT, Dq)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bs, Dk)
    v = v_ref[0, 0].astype(jnp.float32)                     # (bs, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    lb = len_ref[b]
    kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (gT, bs), 1)
    # row r's query position: lengths[b] − Tq + (r mod Tq)
    qpos = lb - Tq + jax.lax.broadcasted_iota(jnp.int32, (gT, bs), 0) % Tq
    ok = kpos <= qpos
    if mask.window and mask.window > 0:
        ok = ok & (kpos > qpos - mask.window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(m_new[:, None] <= NEG_INF / 2, 0.0, p)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_new = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(i == nb - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def paged_attn_pallas(q, k_pool, v_pool, block_table, lengths, *, mask=None,
                      scale=None, interpret=False):
    """Pallas paged decode: grid (B, Hkv, nb); the block table and lengths
    are scalar-prefetch operands, so each KV block's DMA source address is
    computed from ``block_table[b, i]`` in the BlockSpec index map — the
    gather never materializes outside VMEM."""
    mask = mask if mask is not None else mk.causal()
    _check(q, k_pool, v_pool, block_table, lengths, mask)
    B, Tq, Hq, Dq = q.shape
    nb = block_table.shape[1]
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    Dv = v_pool.shape[-1]
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / (Dq ** 0.5)

    # head h ↦ kv head h//g; query rows flatten (g, Tq) → row gi·Tq + t
    q_r = q.transpose(0, 2, 1, 3).reshape(B, Hkv, g * Tq, Dq)
    k_r = jnp.swapaxes(k_pool, 1, 2)               # (N, Hkv, bs, Dk)
    v_r = jnp.swapaxes(v_pool, 1, 2)               # (N, Hkv, bs, Dv)

    kernel = functools.partial(_paged_kernel, scale=sc, mask=mask, bs=bs,
                               nb=nb, Tq=Tq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # block_table, lengths
        grid=(B, Hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g * Tq, Dq), lambda b, h, i, bt, ln:
                         (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, k_pool.shape[-1]),
                         lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dv),
                         lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g * Tq, Dv), lambda b, h, i, bt, ln:
                               (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * Tq, Dv), jnp.float32),
            pltpu.VMEM((g * Tq, LANES), jnp.float32),
            pltpu.VMEM((g * Tq, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g * Tq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q_r, k_r, v_r)
    return (o.reshape(B, Hkv, g, Tq, Dv).transpose(0, 3, 1, 2, 4)
            .reshape(B, Tq, Hq, Dv))
