"""Chunk-attention API used by the distributed schedules.

A *partial* attention op returns ``(o, lse)`` for one (q-chunk, kv-chunk)
pair; partials merge exactly with :func:`merge` (the paper's ``rescale``).

Key property exploited by the schedules (DESIGN.md §2): in the ring /
balanced schedules, the mask of every step depends only on the **relative**
offset between the q and kv chunks (0 for the local step, ``t·Tc`` for step
``t``), which is static per step. The mask is passed as a declarative
:class:`repro.core.mask.MaskSpec` (full / causal / sliding_window /
prefix_lm / document) — so the Pallas kernels never need dynamic position
scalars, and the block-sparse pruner can reason about the whole spec.
Per-token document segment IDs are dynamic and travel as
``q_segments``/``kv_segments`` operands next to q/k/v.

The pre-MaskSpec ``causal``/``rel_offset``/``window`` kwargs remain as
**deprecated shims** (mapped onto a MaskSpec, one DeprecationWarning per
process); new call sites should pass ``mask=``.

``impl`` names a backend in :mod:`repro.kernels.registry` (``ref``,
``chunked-lax``, ``pallas``, ``pallas-interpret``, ``null``); resolution
matches the MaskSpec's kinds against each backend's capability set and
platform support, falling back down the registry's chain (with a logged
downgrade) instead of crashing — e.g. ``pallas`` on a CPU host runs
``chunked-lax``. ``impl=None`` follows the platform: ``pallas`` on TPU,
``ref`` elsewhere.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import mask as mk
from repro.core.mask import MaskSpec
from repro.kernels import registry
from repro.kernels.ref import NEG_INF, merge_ref


def _resolve_mask(mask, causal, rel_offset, window) -> MaskSpec:
    """Only ``mask=`` remains: the legacy kwarg triple was removed after
    five PRs as warning shims with zero in-repo callers — passing any of
    them raises with the migration hint.  ``mask=None`` keeps its
    long-standing meaning (full attention, :func:`mk.full`)."""
    if causal is not None or rel_offset is not None or window is not None:
        raise TypeError(
            "chunk_attn(causal=, rel_offset=, window=) was removed; pass "
            "mask=repro.core.mask.{full,causal,sliding_window,prefix_lm,"
            "document}(...)")
    return mk.full() if mask is None else mask


def _tuning_kw(be, block_q, block_kv, *, mask=None, q=None, op="fwd"):
    """block_q/block_kv hints are forwarded only to backends that declare
    ``tunable_blocks`` (Pallas tile shapes, chunked-lax scan chunk); other
    backends silently ignore the hints rather than erroring.  When the
    caller passes no hints, the call context (backend, mask kind, shape)
    lets ``block_tuning_kw`` consult the env overrides and the active
    tuning table (repro.tune) before the kernels' built-in defaults."""
    if not be.tunable_blocks:
        return {}
    return registry.block_tuning_kw(
        block_q, block_kv, backend=be.name,
        mask_kind=mask.kind if mask is not None else None,
        head_dim=int(q.shape[-1]) if q is not None else None,
        seq=int(q.shape[1]) if q is not None else None, op=op)


def _offset_kw(mask, q_offset, kv_offset):
    """Reconcile the dynamic position operands via ``mk.fold_offsets``:
    static ints fold into the (static) MaskSpec — pruning and the Pallas
    kernels keep working — while traced values become backend kwargs that
    only ``dynamic_offsets`` backends accept (resolve() falls back for
    the others). Returns (mask, backend_kwargs, needs_dynamic)."""
    if q_offset is None and kv_offset is None:
        return mask, {}, False
    mask, qo, ko, dyn = mk.fold_offsets(mask, q_offset, kv_offset)
    return mask, (dict(q_offset=qo, kv_offset=ko) if dyn else {}), dyn


def chunk_attn(q, k, v, *, mask: MaskSpec | None = None, causal=None,
               rel_offset=None, window=None, scale=None, impl=None,
               block_q=None, block_kv=None, q_segments=None,
               kv_segments=None, q_offset=None, kv_offset=None):
    """Partial attention under a static ``mask`` (MaskSpec).
    ``q_segments``/``kv_segments`` are (B, Tq)/(B, Tk) int32 document IDs
    (document kind). ``block_q``/``block_kv`` are optional tile-shape hints
    for tunable backends. ``q_offset``/``kv_offset`` are *dynamic position
    operands* added to the mask's own offsets — python ints fold into the
    spec; traced scalars (schedule steps whose chunk distance depends on
    the device index) restrict resolution to ``dynamic_offsets`` backends.
    Returns (o, lse)."""
    mask = _resolve_mask(mask, causal, rel_offset, window)
    mask, okw, dyn = _offset_kw(mask, q_offset, kv_offset)
    be = registry.resolve(impl, mask=mask, dtype=q.dtype,
                          dynamic_offsets=dyn)
    return be.fwd(q, k, v, mask=mask, scale=scale, q_segments=q_segments,
                  kv_segments=kv_segments, **okw,
                  **_tuning_kw(be, block_q, block_kv, mask=mask, q=q))


def chunk_attn_bwd(q, k, v, o, lse, do, *, mask: MaskSpec | None = None,
                   causal=None, rel_offset=None, window=None, scale=None,
                   impl=None, delta=None, block_q=None, block_kv=None,
                   q_segments=None, kv_segments=None, q_offset=None,
                   kv_offset=None):
    """FA2 backward for one chunk using the saved (o, lse) — no forward
    recompute. ``delta = rowsum(o⊙do)`` may be precomputed (the distributed
    helper path ships delta instead of o). ``q_offset``/``kv_offset`` as in
    :func:`chunk_attn`. Returns (dq, dk, dv)."""
    mask = _resolve_mask(mask, causal, rel_offset, window)
    mask, okw, dyn = _offset_kw(mask, q_offset, kv_offset)
    be = registry.resolve(impl, mask=mask, dtype=q.dtype,
                          dynamic_offsets=dyn)
    return be.bwd(q, k, v, o, lse, do, mask=mask, scale=scale, delta=delta,
                  q_segments=q_segments, kv_segments=kv_segments, **okw,
                  **_tuning_kw(be, block_q, block_kv, mask=mask, q=q,
                               op="bwd"))


def paged_decode_attn(q, k_pool, v_pool, block_table, lengths, *,
                      mask: MaskSpec | None = None, scale=None, impl=None):
    """Decode attention through a paged KV cache (serving), T >= 1 query
    tokens per request (T = 1 vanilla decode; T = K + 1 speculative
    verification).

    ``q``: (B, T, Hq, Dq) — query row t of request b sits at context
    position ``lengths[b] - T + t``; ``k_pool``/``v_pool``:
    (N, block_size, Hkv, D) block pools; ``block_table``: (B, nb) int32
    block ids per request; ``lengths``: (B,) int32 attendable context
    lengths (all T tokens' K/V must already be written — serve/cache.py's
    write-then-attend contract). ``mask`` is a causal/sliding_window
    MaskSpec (the decode tokens are last, so those are the only kinds with
    decode meaning); resolution requires the backend's ``paged``
    capability and walks the usual fallback chain (``pallas`` on CPU runs
    ``pallas-interpret`` / ``chunked-lax``). Returns o (B, T, Hq, Dv)."""
    mask = mk.causal() if mask is None else mask
    be = registry.resolve(impl, mask=mask, dtype=q.dtype, paged=True)
    return be.paged_fwd(q, k_pool, v_pool, block_table, lengths, mask=mask,
                        scale=scale)


merge = merge_ref  # (o1, lse1, o2, lse2) -> (o, lse)


def empty_partial(q):
    """Identity element of ``merge`` for a query chunk."""
    B, T, H, _ = q.shape
    o = jnp.zeros(q.shape, q.dtype)
    lse = jnp.full((B, T, H), NEG_INF, jnp.float32)
    return o, lse


def mask_partial(pred, o, lse):
    """Nullify a partial result where ``pred`` is False (e.g. on devices for
    which a schedule step is invalid). pred is a scalar bool."""
    o = jnp.where(pred, o, jnp.zeros_like(o))
    lse = jnp.where(pred, lse, jnp.full_like(lse, NEG_INF))
    return o, lse
