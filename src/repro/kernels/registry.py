"""Attention backend registry.

Every chunk-attention implementation is a named :class:`BackendSpec` with a
uniform call signature and explicit capability flags. ``chunk_attn`` /
``chunk_attn_bwd`` (core/attention.py) resolve their ``impl`` string here,
so all schedule / model / launch code selects backends by name only.

Registered backends (see README §Backend registry):

  * ``ref``              — pure-jnp oracle. Ground truth; materializes the
                           full score matrix (O(Tq·Tk) memory).
  * ``chunked-lax``      — ``lax.scan``-blocked online-softmax rescale.
                           Exact, Pallas-free, fast on CPU/GPU.
  * ``pallas``           — compiled Pallas TPU kernel (TPU only).
  * ``pallas-interpret`` — the same kernel body run by the Pallas
                           interpreter; validates the kernel on any host.
  * ``null``             — O(T) shape-correct stub for dry-run cost
                           isolation. NOT exact (never resolves via
                           fallback; must be requested explicitly).

Capabilities are **mask-kind sets**: each backend declares which
:class:`repro.core.mask.MaskSpec` kinds it can serve (``causal``,
``sliding_window``, ``prefix_lm``, ``document``), and
``resolve(impl, platform, mask=spec)`` matches the spec's required kinds
against them, walking each backend's fallback chain when the requested
backend can't run (wrong platform, unsupported mask kind, wrong dtype) and
logging the downgrade — requesting ``pallas`` on CPU runs ``chunked-lax``
instead of crashing.

The backend follows the platform: ``impl=None`` resolves to the compiled
``pallas`` kernels on TPU and to ``ref`` elsewhere. The interpreter runs
only when a caller names ``pallas-interpret``. On TPU a fallback may land
on ``chunked-lax`` (exact and compiled) but never on ``ref`` or the
interpreter: such a request raises instead of running the O(T²) oracle.

Backend names are normalized (``pallas_interpret`` == ``pallas-interpret``)
so the pre-registry spelling keeps working.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mask import KINDS, MaskSpec

log = logging.getLogger(__name__)

ALL_PLATFORMS = ("cpu", "gpu", "tpu")
ALL_DTYPES = ("float32", "bfloat16", "float16")
ALL_MASK_KINDS = frozenset(k for k in KINDS if k != "full")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One attention implementation plus its capability envelope.

    ``fwd(q, k, v, *, mask, scale, q_segments, kv_segments) -> (o, lse)``
    ``bwd(q, k, v, o, lse, do, *, mask, scale, delta, q_segments,
        kv_segments) -> (dq, dk, dv)``

    (``mask`` is a static MaskSpec; tunable backends additionally take
    ``block_q``/``block_kv`` hints.)
    """
    name: str
    fwd: Callable
    bwd: Callable
    # capability flags
    mask_kinds: FrozenSet[str] = ALL_MASK_KINDS  # MaskSpec kinds served
    dtypes: Tuple[str, ...] = ALL_DTYPES
    platforms: Tuple[str, ...] = ALL_PLATFORMS
    exact: bool = True             # numerically exact (vs stub)
    # accepts block_q/block_kv tuning hints (fwd/bwd take them as kwargs);
    # chunk_attn only forwards the hints to backends with this flag set, so
    # schedules can pick block shapes per step without knowing the backend
    tunable_blocks: bool = False
    # accepts *traced* q_offset/kv_offset position operands (fwd/bwd take
    # them as kwargs). Needed by schedule steps whose chunk distance
    # depends on the device index (zigzag window bands); static int offsets
    # are folded into the MaskSpec and never reach the backend.
    dynamic_offsets: bool = False
    # paged flash-decode entry point (serving): block-table-gathering
    # one-token decode attention with signature
    # ``paged_fwd(q, k_pool, v_pool, block_table, lengths, *, mask, scale)
    # -> o``; None = backend has no paged path (resolve(paged=True) walks
    # the fallback chain past it).
    paged_fwd: Optional[Callable] = None
    fallback: Tuple[str, ...] = ()  # tried in order when this can't run
    description: str = ""

    def __post_init__(self):
        unknown = frozenset(self.mask_kinds) - ALL_MASK_KINDS
        if unknown:
            raise ValueError(f"unknown mask kinds {sorted(unknown)}; "
                             f"valid: {sorted(ALL_MASK_KINDS)}")
        object.__setattr__(self, "mask_kinds", frozenset(self.mask_kinds))

    # legacy capability views (pre-MaskSpec flag names)
    @property
    def causal(self) -> bool:
        return "causal" in self.mask_kinds

    @property
    def window(self) -> bool:
        return "sliding_window" in self.mask_kinds

    @property
    def rel_offset(self) -> bool:
        return True    # every backend handles static chunk offsets

    @property
    def paged(self) -> bool:
        """Capability flag: serves block-table (paged KV cache) decode."""
        return self.paged_fwd is not None

    def unsupported_reason(self, *, platform: str,
                           mask: Optional[MaskSpec] = None,
                           dtype=None,
                           dynamic_offsets: bool = False,
                           paged: bool = False) -> Optional[str]:
        """None if this backend can serve the request, else why not."""
        if platform not in self.platforms:
            return f"platform {platform!r} not in {self.platforms}"
        if mask is not None:
            missing = mask.kinds - self.mask_kinds
            if missing:
                return (f"mask kind(s) {sorted(missing)} unsupported "
                        f"(has {sorted(self.mask_kinds)})")
        if dtype is not None and jnp.dtype(dtype).name not in self.dtypes:
            return f"dtype {jnp.dtype(dtype).name} not in {self.dtypes}"
        if dynamic_offsets and not self.dynamic_offsets:
            return "traced q_offset/kv_offset operands unsupported"
        if paged and not self.paged:
            return "no paged (block-table) decode path"
        return None


_REGISTRY: Dict[str, BackendSpec] = {}
_WARNED = set()   # (requested, resolved, platform) — log each downgrade once
# backends a TPU request may never fall back to (uncompiled or O(T²))
_NO_TPU_FALLBACK = frozenset({"ref", "pallas-interpret"})


def _norm(name: str) -> str:
    return name.replace("_", "-").lower()


def register(spec: BackendSpec, overwrite: bool = False) -> BackendSpec:
    key = _norm(spec.name)
    if key in _REGISTRY and not overwrite:
        raise ValueError(f"backend {key!r} already registered")
    _REGISTRY[key] = spec
    return spec


def names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get(name: str) -> BackendSpec:
    key = _norm(name)
    if key not in _REGISTRY:
        raise ValueError(f"unknown attention backend {name!r}; "
                         f"registered: {names()}")
    return _REGISTRY[key]


def current_platform() -> str:
    return jax.default_backend()


def default_name(platform: Optional[str] = None) -> str:
    """The backend ``impl=None`` resolves to on ``platform``."""
    return "pallas" if (platform or current_platform()) == "tpu" else "ref"


def resolve(impl: Optional[str] = None, platform: Optional[str] = None, *,
            mask: Optional[MaskSpec] = None, dtype=None,
            dynamic_offsets: bool = False,
            paged: bool = False) -> BackendSpec:
    """Return a runnable backend for the request, walking fallbacks.

    ``impl=None`` uses the platform's default (:func:`default_name`);
    ``mask`` is the MaskSpec the call site will pass; ``dynamic_offsets``
    marks a call that carries traced position-offset operands;
    ``paged=True`` requires the backend's block-table decode path. A
    downgrade (requested backend can't serve the request) is logged once
    per (requested, resolved, platform) triple; an exhausted fallback
    chain raises, and so does one that would leave TPU for ``ref`` or the
    interpreter."""
    platform = platform or current_platform()
    want = get(impl if impl is not None else default_name(platform))
    caps = dict(platform=platform, mask=mask, dtype=dtype,
                dynamic_offsets=dynamic_offsets, paged=paged)
    reason = want.unsupported_reason(**caps)
    if reason is None:
        return want
    # transitive breadth-first walk of the fallback chain (cycle-safe)
    seen = {_norm(want.name)}
    queue = [fb for fb in want.fallback]
    tried = [want.name]
    while queue:
        cand = get(queue.pop(0))
        if _norm(cand.name) in seen:
            continue
        seen.add(_norm(cand.name))
        if platform == "tpu" and _norm(cand.name) in _NO_TPU_FALLBACK:
            continue
        tried.append(cand.name)
        if cand.unsupported_reason(**caps) is None:
            key = (want.name, cand.name, platform)
            if key not in _WARNED:
                _WARNED.add(key)
                log.warning("attention backend %r unavailable (%s); "
                            "downgrading to %r on %s", want.name, reason,
                            cand.name, platform)
            return cand
        queue.extend(cand.fallback)
    raise ValueError(
        f"no runnable attention backend for impl={want.name!r} on "
        f"{platform!r} (mask={mask!r}): {reason}; tried {tried}")


# ==========================================================================
# Built-in backends
# ==========================================================================

def _ref_fwd(q, k, v, *, mask, scale=None, q_segments=None,
             kv_segments=None, q_offset=0, kv_offset=0):
    from repro.kernels.ref import chunk_attn_ref
    return chunk_attn_ref(q, k, v, mask=mask, scale=scale,
                          q_offset=q_offset, kv_offset=kv_offset,
                          q_segments=q_segments, kv_segments=kv_segments)


def _ref_bwd(q, k, v, o, lse, do, *, mask, scale=None, delta=None,
             q_segments=None, kv_segments=None, q_offset=0, kv_offset=0):
    from repro.kernels.ref import chunk_attn_bwd_ref
    return chunk_attn_bwd_ref(q, k, v, o, lse, do, mask=mask, scale=scale,
                              q_offset=q_offset, kv_offset=kv_offset,
                              delta=delta, q_segments=q_segments,
                              kv_segments=kv_segments)


def _chunked_fwd(q, k, v, **kw):
    from repro.kernels.chunked import chunked_fwd
    return chunked_fwd(q, k, v, **kw)


def _chunked_bwd(q, k, v, o, lse, do, **kw):
    from repro.kernels.chunked import chunked_bwd
    return chunked_bwd(q, k, v, o, lse, do, **kw)


def block_tuning_kw(block_q, block_kv, *, backend=None, platform=None,
                    mask_kind=None, head_dim=None, seq=None, op="fwd"):
    """None-filtered {block_q, block_kv} kwargs for tunable backends (shared
    by chunk_attn's hint forwarding and the pallas closures below).

    When the caller passes *neither* block, the tuning chain kicks in:
    ``REPRO_TUNE_BLOCK_Q``/``REPRO_TUNE_BLOCK_KV`` env overrides first,
    then the active tuning table's nearest-bucket winner for the call
    context (requires ``backend`` + shape context — the bare two-arg form
    used inside backend closures never re-consults the table).  Explicit
    kwargs always win wholesale; with no env, no table, and no kwargs the
    kernels keep their built-in defaults."""
    if block_q is None and block_kv is None:
        from repro.tune import table as _tt
        block_q = _tt.env_int("REPRO_TUNE_BLOCK_Q")
        block_kv = _tt.env_int("REPRO_TUNE_BLOCK_KV")
        if block_q is None and block_kv is None and backend is not None:
            tab = _tt.active_table()
            if tab is not None:
                hit = tab.best_blocks(
                    backend=backend, platform=platform or current_platform(),
                    mask_kind=mask_kind or "causal",
                    head_dim=head_dim or 64, seq=seq or 0, op=op)
                if hit is not None:
                    block_q, block_kv = hit
    kw = {}
    if block_q is not None:
        kw["block_q"] = block_q
    if block_kv is not None:
        kw["block_kv"] = block_kv
    return kw


def _pallas_fwd(interpret):
    def fwd(q, k, v, *, mask, scale=None, q_segments=None, kv_segments=None,
            block_q=None, block_kv=None):
        from repro.kernels import ops
        return ops.flash_fwd(q, k, v, mask=mask, scale=scale,
                             interpret=interpret, q_segments=q_segments,
                             kv_segments=kv_segments,
                             **block_tuning_kw(block_q, block_kv))
    return fwd


def _pallas_bwd(interpret):
    def bwd(q, k, v, o, lse, do, *, mask, scale=None, delta=None,
            q_segments=None, kv_segments=None, block_q=None, block_kv=None):
        from repro.kernels import ops
        return ops.flash_bwd(q, k, v, o, lse, do, mask=mask, scale=scale,
                             interpret=interpret, delta=delta,
                             q_segments=q_segments, kv_segments=kv_segments,
                             **block_tuning_kw(block_q, block_kv))
    return bwd


def _paged_ref(q, k_pool, v_pool, block_table, lengths, *, mask, scale=None):
    from repro.kernels.paged import paged_attn_ref
    return paged_attn_ref(q, k_pool, v_pool, block_table, lengths,
                          mask=mask, scale=scale)


def _paged_chunked(q, k_pool, v_pool, block_table, lengths, *, mask,
                   scale=None):
    from repro.kernels.paged import paged_attn_chunked
    return paged_attn_chunked(q, k_pool, v_pool, block_table, lengths,
                              mask=mask, scale=scale)


def _paged_pallas(interpret):
    def fwd(q, k_pool, v_pool, block_table, lengths, *, mask, scale=None):
        from repro.kernels.paged import paged_attn_pallas
        return paged_attn_pallas(q, k_pool, v_pool, block_table, lengths,
                                 mask=mask, scale=scale, interpret=interpret)
    return fwd


def _null_fwd(q, k, v, *, mask=None, scale=None, q_segments=None,
              kv_segments=None):
    # dry-run cost-isolation stub: shape-correct, data-dependent (so XLA
    # cannot fold it away), but O(T) instead of O(T²). The kernel's ideal
    # FLOPs/bytes are added analytically (analysis/roofline.attention_sites).
    B, Tq, Hq, _ = q.shape
    vm = jnp.mean(v.astype(jnp.float32), axis=(1, 2), keepdims=True)
    o = jnp.broadcast_to(vm, (B, Tq, Hq, v.shape[-1])).astype(q.dtype)
    o = o + 0.0 * q[..., :1] * jnp.mean(k)
    lse = jnp.mean(q.astype(jnp.float32), axis=-1)
    return o, lse


def _null_bwd(q, k, v, o, lse, do, *, mask=None, scale=None, delta=None,
              q_segments=None, kv_segments=None):
    s_do = jnp.mean(do.astype(jnp.float32))
    dq = (q.astype(jnp.float32) * 0.0 + s_do).astype(q.dtype)
    dk = (k.astype(jnp.float32) * 0.0 + s_do).astype(k.dtype)
    dv = (v.astype(jnp.float32) * 0.0 + s_do).astype(v.dtype)
    return dq, dk, dv


register(BackendSpec(
    name="ref", fwd=_ref_fwd, bwd=_ref_bwd,
    dynamic_offsets=True, paged_fwd=_paged_ref,
    description="pure-jnp oracle; full score matrix"))

register(BackendSpec(
    name="chunked-lax", fwd=_chunked_fwd, bwd=_chunked_bwd,
    tunable_blocks=True, dynamic_offsets=True, paged_fwd=_paged_chunked,
    fallback=("ref",),
    description="lax.scan-blocked online softmax; Pallas-free"))

register(BackendSpec(
    name="pallas", fwd=_pallas_fwd(False), bwd=_pallas_bwd(False),
    platforms=("tpu",), dtypes=("float32", "bfloat16"),
    tunable_blocks=True, paged_fwd=_paged_pallas(False),
    fallback=("chunked-lax", "ref"),
    description="compiled Pallas TPU FlashAttention-2 kernel"))

register(BackendSpec(
    name="pallas-interpret", fwd=_pallas_fwd(True), bwd=_pallas_bwd(True),
    dtypes=("float32", "bfloat16"),
    tunable_blocks=True, paged_fwd=_paged_pallas(True),
    fallback=("chunked-lax", "ref"),
    description="Pallas kernel body under the interpreter (validation)"))

register(BackendSpec(
    name="null", fwd=_null_fwd, bwd=_null_bwd, exact=False,
    description="O(T) dry-run cost-isolation stub (not exact)"))
