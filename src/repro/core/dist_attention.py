"""DISTFLASHATTN — the paper's core contribution, as JAX shard_map code.

Sequence-parallel exact attention over the ``model`` mesh axis (the paper's
``P`` workers).  Since the schedule-plan IR rewrite, the ring / balanced /
zigzag schedules (and the MLA latent ring) are **~30-line plan builders**
in :mod:`repro.core.schedule`: each builds a static
:class:`~repro.core.schedule.SchedulePlan` — per ring step, a declarative
list of Work items (q/kv chunk sources, the step's static MaskSpec,
validity predicates, result routing) — and one shared forward executor and
one shared backward executor run any plan with the ppermute-prefetch
overlap, traveling-``dkv`` accumulators, and segment-ID machinery
implemented exactly once.  Schedules (validated in
``DistAttnSpec.__post_init__`` — unknown names raise instead of silently
running the ring):

* ``balanced`` — the paper's load-balanced schedule (§3.2, Alg. 2):
  ``⌊P/2⌋`` ring steps; workers with unfinished causal work compute
  ``attn(q_p, kv_{p−t})`` while *helpers* (workers whose causal prefix is
  done) compute ``attn(q_{(h−t) mod P}, kv_h)`` on behalf of heavy workers
  and ship the partial ``(o, lse)`` back for a ``rescale`` merge. Idle
  fraction ``1/(2P)`` (even P) / ``0`` (odd P). Causal-kind masks
  (document and — new with the plan IR — sliding windows, which truncate
  the plan to its needed steps).
* ``ring`` — vanilla DISTFLASHATTN (§3.1, Alg. 1): ``P−1`` steps, workers
  idle once their causal prefix is exhausted (idle fraction → 1/2). Also
  used for bidirectional encoders (where causal imbalance doesn't exist —
  paper §F discussion); sliding windows truncate the ring tail
  (Appendix F: "change the end condition of the for loop").
* ``zigzag`` — beyond-paper balanced placement (2P half-chunks, device p
  holds (p, 2P−1−p)): exact balance with only the KV ring.  Windowed
  masks run through dynamic-offset step masks and skip the *middle* ring
  steps (both sequence ends are local under the mirror placement).
  Contract: global arrays are pre-permuted with :func:`zigzag_perm`.
* ``ulysses`` — DeepSpeed-Ulysses head-parallel baseline (all-to-all);
  raises on head counts not divisible by P (paper §4.2/§4.6).
* ``rsa`` — Ring Self-Attention baseline (Li et al., 2021): all-gathers
  K and V and materializes the full score matrix (no memory-efficient
  attention). Benchmark baseline only.
* ``auto`` — pick the cheapest capable schedule for the (MaskSpec, P,
  shapes) at trace time via the plans' static comm/compute cost model
  (:func:`repro.core.schedule.choose_schedule`, wired into
  ``analysis/roofline.py``).  Candidates: balanced, ring, and — when the
  head counts divide P — ulysses.  zigzag is excluded (its global-layout
  permutation is a caller contract) and rsa is benchmark-only.

Masking is a declarative :class:`repro.core.mask.MaskSpec` carried by
``DistAttnSpec.mask``; the plan builders derive each step's spec
statically and **skip provably all-masked steps**.  Packed-sequence
(document) masking is first-class: dynamic per-token ``segments`` travel
the ring alongside K/V, while static ``document(boundaries=…)`` layouts
need no arrays at all — the executor derives each chunk's segment IDs
from the boundaries at trace time and the builders prune ring steps no
document spans.  Prefix-LM masks need absolute positions on every chunk —
they are served by ``ulysses``/``rsa`` or a single-shard axis, and
rejected elsewhere at spec-construction time.

Communication/computation overlap (§3.2, Eq. 3) is expressed in dataflow:
the ``ppermute`` producing step ``t+1``'s chunk is issued *before* step
``t``'s compute and has no data dependence on it, so XLA's latency-hiding
scheduler overlaps the ICI transfer with the attention kernel (the TPU
analogue of the paper's second CUDA stream).

The backward pass is hand-written (exposed as :func:`dist_attn_bwd`) so the
rematerialization-aware checkpointing combinator (core/remat.py) can invoke
it directly from saved ``(o, lse)`` — the FlashAttention forward is never
recomputed, and neither is its forward communication (§3.3).

All functions here are *local* (per-shard) code meant to run inside
``jax.shard_map``; :func:`dist_flash_attn` is the user-facing wrapper that
applies shard_map and registers the custom VJP.  The frozen seed
implementations of the hand-written loops live in
``core/legacy_schedules.py`` purely as differential-test references.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import mask as mk
from repro.core import schedule as sp
from repro.core.attention import chunk_attn, chunk_attn_bwd
from repro.core.mask import MaskSpec
from repro.kernels.ref import NEG_INF


# --------------------------------------------------------------------------
# Schedule configuration
# --------------------------------------------------------------------------

SCHEDULES = ("auto", "balanced", "ring", "rsa", "ulysses", "zigzag")

_MASK_HINT = ("mask=repro.core.mask.{full,causal,sliding_window,prefix_lm,"
              "document}(...)")


@dataclasses.dataclass(frozen=True)
class Mesh2DSpec:
    """Factored 2D (sequence × head) mesh axis pair for one distributed-
    attention call: the ``axis_size = r·u`` sequence-parallel workers form
    an (``seq_axis`` = r) × (``head_axis`` = u) grid.  The global sequence
    is sharded over the *pair* (seq major, head minor); the executor
    head-scatters q/k/v over ``head_axis`` (ulysses-style, GQA-aware) and
    runs a ring-family SchedulePlan over ``seq_axis`` — BurstAttention's
    inter-node ring / intra-node head split as a plan wrapper (see
    core/schedule.Plan2D)."""
    r: int
    u: int
    seq_axis: str = "seq"
    head_axis: str = "head"

    def __post_init__(self):
        if self.r < 1 or self.u < 1:
            raise ValueError(f"Mesh2DSpec needs r, u >= 1 "
                             f"(got r={self.r}, u={self.u})")
        if self.seq_axis == self.head_axis:
            raise ValueError("Mesh2DSpec seq_axis and head_axis must be "
                             "distinct mesh axes")


@dataclasses.dataclass(frozen=True)
class DistAttnSpec:
    """Static description of one distributed-attention call site.

    ``schedule`` ∈ ``auto | balanced | ring | rsa | ulysses | zigzag``
    (validated — a typo raises instead of silently running the ring
    schedule).  ``auto`` defers the choice to trace time, where the
    shapes are known and the plans' cost model ranks the candidates.
    ``mask`` is the MaskSpec of the *whole* (unsharded) attention; the
    plan builders derive per-step specs from it.

    ``mesh2d`` factors the ``axis_size`` workers into a (seq = r,
    head = u) grid (:class:`Mesh2DSpec`): the ring-family schedules then
    run on the ``seq`` sub-axis after a head scatter on the ``head``
    sub-axis, and ``axis``/``seq_axes`` is ignored in favor of the pair.
    At ``r == 1`` the inner plan is one local full-sequence kernel, so
    *any* mask kind is servable — including prefix_lm backward, which no
    1D multi-shard schedule can express.

    The pre-MaskSpec ``causal=``/``window=`` constructor kwargs are
    **removed** — passing them raises ``TypeError`` with the migration
    hint (they survived five PRs as deprecation shims with zero in-repo
    callers).
    """
    axis: str = "model"            # sequence-parallel mesh axis
    axis_size: int = 1             # P (= r·u with mesh2d)
    schedule: str = "balanced"     # see SCHEDULES
    mask: Optional[MaskSpec] = None
    # removed legacy kwargs — kept as init-only slots so passing them by
    # name raises our TypeError with the migration hint
    causal: dataclasses.InitVar[Optional[bool]] = None
    window: dataclasses.InitVar[Optional[int]] = None
    scale: Optional[float] = None
    # attention backend name resolved via repro.kernels.registry (None =
    # the platform's default); capability/platform fallback happens at
    # resolve time
    impl: Optional[str] = None
    mesh2d: Optional[Mesh2DSpec] = None

    def __post_init__(self, causal, window):
        if causal is not None or window is not None:
            raise TypeError(
                "DistAttnSpec(causal=, window=) was removed; pass "
                + _MASK_HINT)
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; valid: {SCHEDULES}")
        if self.mask is None:
            # the spec-level default mask is causal (unlike chunk_attn's)
            object.__setattr__(self, "mask", mk.causal())
        m = self.mask
        if m.q_offset or m.kv_offset:
            raise ValueError("DistAttnSpec.mask must be offset-free — the "
                             "schedules derive per-step offsets")
        ring_P = self.axis_size
        if self.mesh2d is not None:
            md = self.mesh2d
            if md.r * md.u != self.axis_size:
                raise ValueError(
                    f"mesh2d r·u = {md.r * md.u} must equal "
                    f"axis_size = {self.axis_size}")
            if self.schedule not in ("auto",) + sp.PLAN_SCHEDULES:
                raise ValueError(
                    f"2D (seq×head) attention runs ring-family plans only "
                    f"(got {self.schedule!r}); the ulysses/rsa baselines "
                    f"have their own 1D topology")
            # capability follows the *seq* sub-axis: at r == 1 the inner
            # plan is one local full-sequence kernel — any mask kind goes
            ring_P = md.r
        if ring_P > 1:
            if self.schedule in ("balanced", "zigzag") and \
                    not (m.causal and not m.prefix_len):
                raise ValueError(
                    f"{self.schedule!r} handles causal-kind masks only "
                    f"(got {m.kind!r}); use ring/ulysses")
            # rsa/ulysses serve prefix_lm forward-only (absolute positions
            # exist there); their backward — the ring — rejects it below
            if m.prefix_len and self.schedule == "ring":
                raise ValueError(
                    "prefix_lm needs absolute kv positions, which the "
                    "ring schedule's per-shard chunks don't have; use "
                    "ulysses/rsa, a 2D mesh with r == 1, or a "
                    "single-shard axis")
            if m.window and self.schedule == "rsa":
                raise ValueError("rsa baseline has no sliding-window path")
            if m.window and not m.causal and self.schedule == "ring":
                raise ValueError(
                    "a non-causal sliding window needs future-direction "
                    "band steps the ring's strictly-past step masks can't "
                    "express; use ulysses, a 2D mesh with r == 1, or a "
                    "single-shard axis")

    @property
    def seq_entry(self):
        """The PartitionSpec sequence entry: the 2D axis pair (seq major,
        head minor) when factored, else the single ``axis``."""
        if self.mesh2d is not None:
            return (self.mesh2d.seq_axis, self.mesh2d.head_axis)
        return self.axis


def _tune(spec: DistAttnSpec) -> dict:
    """chunk_attn kwargs carried by the spec (scale and backend)."""
    return dict(scale=spec.scale, impl=spec.impl)


def _seg_kw(mask: MaskSpec, q_seg, kv_seg) -> dict:
    """Segment operands, only when the mask consumes them."""
    if not mask.document or q_seg is None:
        return {}
    return dict(q_segments=q_seg, kv_segments=kv_seg)


def resolve_schedule(spec: DistAttnSpec, q, k, v, seg=None, *,
                     for_bwd: bool = False) -> str:
    """Concrete schedule for this call.  ``auto`` ranks the capable
    candidates by the static plan cost model; ``for_bwd`` tells the
    capability filter whether the choice must also serve the distributed
    backward (the forward-only baselines are then excluded — the filter
    mirrors the runtime raise conditions exactly, so a resolved name
    never raises at execution time).  On a 2D mesh the factorization is
    fixed by the spec and only the inner seq-axis schedule is chosen."""
    if spec.schedule != "auto":
        return spec.schedule
    kw = dict(B=q.shape[0], Hq=q.shape[2], Hkv=k.shape[2], Dqk=q.shape[3],
              Dv=v.shape[3], bpe=q.dtype.itemsize,
              dynamic_seg=seg is not None, include_bwd=for_bwd)
    if spec.mesh2d is not None:
        return sp.choose_inner_schedule(spec.mask, spec.mesh2d.r,
                                        spec.mesh2d.u, Tl_dev=q.shape[1],
                                        **kw)
    return sp.choose_schedule(spec.mask, spec.axis_size, Tl=q.shape[1],
                              **kw)


# --------------------------------------------------------------------------
# Bespoke baselines (not plan-based: different communication topology)
# --------------------------------------------------------------------------

def _fwd_ulysses(spec, q, k, v, seg=None):
    """DeepSpeed-Ulysses baseline (Jacobs et al., 2023): all-to-all the
    sequence-sharded q/k/v into head-sharded layout, run ordinary (local)
    FlashAttention over the full sequence, all-to-all back. Requires the
    head counts to be divisible by P — exactly the limitation the paper
    targets (§4.2, §4.6); we raise otherwise (Megatron would pad heads)."""
    P_ = spec.axis_size
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq % P_ or Hkv % P_:
        raise ValueError(
            f"ulysses needs heads % P == 0 (got Hq={Hq}, Hkv={Hkv}, P={P_})"
            " — the head-divisibility limitation of head-parallel attention")
    def a2a(x, fwd=True):
        if fwd:   # scatter heads, gather sequence
            return lax.all_to_all(x, spec.axis, split_axis=2, concat_axis=1,
                                  tiled=True)
        return lax.all_to_all(x, spec.axis, split_axis=1, concat_axis=2,
                              tiled=True)
    qh, kh, vh = a2a(q), a2a(k), a2a(v)          # (B, T_glob, H/P, D)
    m = spec.mask
    skw = {}
    if seg is not None and m.document:
        seg_g = lax.all_gather(seg, spec.axis, axis=1, tiled=True)
        skw = dict(q_segments=seg_g, kv_segments=seg_g)
    o, s = chunk_attn(qh, kh, vh, mask=m, **skw, **_tune(spec))
    # lse (B, T_glob, H/P) -> (B, T_loc, H): split seq, concat heads
    s_back = lax.all_to_all(s, spec.axis, split_axis=1, concat_axis=2,
                            tiled=True)
    return a2a(o, fwd=False), s_back


def _fwd_rsa(spec, q, k, v, seg=None):
    """Ring Self-Attention baseline: all-gather KV, materialize scores."""
    if spec.mask.needs_segments and seg is None:
        raise ValueError("document mask without boundaries needs segments=")
    kg = lax.all_gather(k, spec.axis, axis=1, tiled=True)
    vg = lax.all_gather(v, spec.axis, axis=1, tiled=True)
    p = lax.axis_index(spec.axis)
    Tc = q.shape[1]
    B, Tq, Hq, D = q.shape
    Hkv = kg.shape[2]
    g = Hq // Hkv
    m = spec.mask
    scale = spec.scale or 1.0 / (D ** 0.5)
    kf = jnp.repeat(kg, g, axis=2) if g > 1 else kg
    vf = jnp.repeat(vg, g, axis=2) if g > 1 else vg
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    kf.astype(jnp.float32)) * scale
    if m.needs_mask:
        # same MaskSpec.allow semantics as the kernels, with this shard's
        # traced absolute query positions and the gathered global keys
        qpos = p * Tc + jnp.arange(Tq)
        kpos = jnp.arange(kg.shape[1])
        qs = ks = None
        if m.document and seg is not None:
            sg = lax.all_gather(seg, spec.axis, axis=1, tiled=True)
            qs, ks = seg[:, :, None], sg[:, None, :]
        allow = m.allow(qpos[:, None], kpos[None, :], qs, ks)
        allow = allow[None, None] if allow.ndim == 2 else allow[:, None]
        sc = jnp.where(allow, sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)                  # full P×-size matrix
    o = jnp.einsum("bhqk,bkhd->bqhd", w, vf.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(sc, axis=-1).transpose(0, 2, 1)
    return o.astype(q.dtype), lse


# --------------------------------------------------------------------------
# Public API: explicit fwd/bwd + custom-VJP wrapper, shard_mapped
# --------------------------------------------------------------------------

def _plan2d(spec, sched, q, k):
    md = spec.mesh2d
    # at r == 1 every ring-family schedule degenerates to the same local
    # full-sequence kernel — canonicalize so build stays capability-exact
    sched = "ring" if md.r == 1 else sched
    return sp.build_plan2d(sched, spec.mask, md.r, md.u, q.shape[1],
                           Hq=q.shape[2], Hkv=k.shape[2])


def _fwd_local(spec, q, k, v, seg=None):
    if spec.axis_size == 1:
        m = spec.mask
        return chunk_attn(q, k, v, mask=m, **_seg_kw(m, seg, seg),
                          **_tune(spec))
    sched = resolve_schedule(spec, q, k, v, seg)
    if spec.mesh2d is not None:
        md = spec.mesh2d
        if md.u == 1:       # degenerate factorization: plain 1D seq plan
            plan = sp.build_plan(sched, spec.mask, md.r, q.shape[1])
            return sp.execute_fwd(plan, q, k, v, seg, axis=md.seq_axis,
                                  tune=_tune(spec))
        return sp.execute2d_fwd(_plan2d(spec, sched, q, k), q, k, v, seg,
                                seq_axis=md.seq_axis,
                                head_axis=md.head_axis, tune=_tune(spec))
    if sched == "rsa":
        return _fwd_rsa(spec, q, k, v, seg)
    if sched == "ulysses":
        return _fwd_ulysses(spec, q, k, v, seg)
    plan = sp.build_plan(sched, spec.mask, spec.axis_size, q.shape[1])
    return sp.execute_fwd(plan, q, k, v, seg, axis=spec.axis,
                          tune=_tune(spec))


def _bwd_local(spec, q, k, v, o, s, do, seg=None):
    if spec.axis_size == 1:
        m = spec.mask
        return chunk_attn_bwd(q, k, v, o, s, do, mask=m,
                              **_seg_kw(m, seg, seg), **_tune(spec))
    sched = resolve_schedule(spec, q, k, v, seg, for_bwd=True)
    if spec.mesh2d is not None:
        md = spec.mesh2d
        if md.u == 1:
            plan = sp.build_plan(sched, spec.mask, md.r, q.shape[1])
            return sp.execute_bwd(plan, q, k, v, o, s, do, seg,
                                  axis=md.seq_axis, tune=_tune(spec))
        return sp.execute2d_bwd(_plan2d(spec, sched, q, k), q, k, v, o, s,
                                do, seg, seq_axis=md.seq_axis,
                                head_axis=md.head_axis, tune=_tune(spec))
    if sched in ("rsa", "ulysses"):
        # the baselines reuse the exact ring backward — which cannot
        # express absolute coordinates (prefix masks) in its per-shard
        # chunks; static document boundaries ARE expressible (the plan
        # executor derives per-shard segment IDs from them)
        if spec.mask.prefix_len:
            raise ValueError("prefix_lm distributed backward needs "
                             "axis_size == 1 (fwd-only baselines "
                             "support it)")
        if spec.mask.window and not spec.mask.causal:
            raise ValueError("non-causal sliding-window distributed "
                             "backward needs axis_size == 1 (the ring "
                             "backward the baselines reuse can't see "
                             "future-direction bands)")
        sched = "ring"
    plan = sp.build_plan(sched, spec.mask, spec.axis_size, q.shape[1])
    return sp.execute_bwd(plan, q, k, v, o, s, do, seg, axis=spec.axis,
                          tune=_tune(spec))


def _specs(batch_axes, seq):
    """``seq`` is the sequence-dim PartitionSpec entry: one axis name or
    the 2D (seq, head) axis pair."""
    b = tuple(batch_axes) if batch_axes else None
    qkv = P(b, seq, None, None)
    lse = P(b, seq, None)
    seg = P(b, seq)
    return qkv, lse, seg


def dist_attn_fwd(q, k, v, *, mesh, spec: DistAttnSpec,
                  batch_axes=("data",), segments=None):
    """Distributed forward → (o, lse). Global-array in/out (GSPMD land).
    ``segments`` is a (B, T) int32 document-ID array sharded like the
    activations (document masks only)."""
    qkv_s, lse_s, seg_s = _specs(batch_axes, spec.seq_entry)
    in_specs, args = [qkv_s] * 3, [q, k, v]
    if segments is not None:
        in_specs.append(seg_s)
        args.append(segments)
    fn = jax.shard_map(partial(_fwd_local, spec), mesh=mesh,
                       in_specs=tuple(in_specs),
                       out_specs=(qkv_s, lse_s), check_vma=False)
    return fn(*args)


def dist_attn_bwd(q, k, v, o, lse, do, *, mesh, spec: DistAttnSpec,
                  batch_axes=("data",), segments=None):
    """Distributed backward from saved (o, lse) → (dq, dk, dv)."""
    qkv_s, lse_s, seg_s = _specs(batch_axes, spec.seq_entry)
    in_specs = [qkv_s, qkv_s, qkv_s, qkv_s, lse_s, qkv_s]
    args = [q, k, v, o, lse, do]
    if segments is not None:
        in_specs.append(seg_s)
        args.append(segments)
    fn = jax.shard_map(partial(_bwd_local, spec), mesh=mesh,
                       in_specs=tuple(in_specs),
                       out_specs=(qkv_s, qkv_s, qkv_s), check_vma=False)
    return fn(*args)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dist_flash_attn(q, k, v, mesh, spec, batch_axes):
    return dist_attn_fwd(q, k, v, mesh=mesh, spec=spec,
                         batch_axes=batch_axes)


def _cvjp_fwd(q, k, v, mesh, spec, batch_axes):
    o, lse = dist_attn_fwd(q, k, v, mesh=mesh, spec=spec,
                           batch_axes=batch_axes)
    return (o, lse), (q, k, v, o, lse)


def _cvjp_bwd(mesh, spec, batch_axes, res, cts):
    q, k, v, o, lse = res
    do, _ = cts
    dq, dk, dv = dist_attn_bwd(q, k, v, o, lse, do, mesh=mesh, spec=spec,
                               batch_axes=batch_axes)
    return dq, dk, dv


_dist_flash_attn.defvjp(_cvjp_fwd, _cvjp_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _dist_flash_attn_seg(q, k, v, segments, mesh, spec, batch_axes):
    return dist_attn_fwd(q, k, v, mesh=mesh, spec=spec,
                         batch_axes=batch_axes, segments=segments)


def _cvjp_seg_fwd(q, k, v, segments, mesh, spec, batch_axes):
    o, lse = dist_attn_fwd(q, k, v, mesh=mesh, spec=spec,
                           batch_axes=batch_axes, segments=segments)
    return (o, lse), (q, k, v, segments, o, lse)


def _cvjp_seg_bwd(mesh, spec, batch_axes, res, cts):
    q, k, v, segments, o, lse = res
    do, _ = cts
    dq, dk, dv = dist_attn_bwd(q, k, v, o, lse, do, mesh=mesh, spec=spec,
                               batch_axes=batch_axes, segments=segments)
    # integer segment IDs take a float0 cotangent
    dseg = np.zeros(segments.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_dist_flash_attn_seg.defvjp(_cvjp_seg_fwd, _cvjp_seg_bwd)


def dist_flash_attn(q, k, v, mesh, spec, batch_axes=("data",),
                    segments=None):
    """DISTFLASHATTN with autodiff. Returns (o, lse); lse is a residual
    output (its cotangent is ignored, as in the paper's kernel).
    ``segments`` (document masks) is non-differentiable."""
    if segments is None:
        return _dist_flash_attn(q, k, v, mesh, spec, batch_axes)
    return _dist_flash_attn_seg(q, k, v, segments, mesh, spec, batch_axes)


# --------------------------------------------------------------------------
# Decode-time distributed attention (flash-decoding over sequence shards)
# --------------------------------------------------------------------------

def _decode_local(seq_axes, shard_len, window, scale, has_pos, q, kc, vc,
                  k1, v1, pos=None):
    """q: (B,1,Hq,D) replicated over seq axes; kc/vc: (B,S_loc,Hkv,Dk/Dv)
    local cache shards; k1/v1: (B,1,...) the new token's k/v (replicated).
    ``pos`` (B,) — per-request valid-context lengths: request b's new token
    sits at position pos[b] and only cache slots < pos[b] are attendable
    (window measured from pos[b]). Without ``pos`` (legacy), the whole
    cache is context: S_global cached + 1 new token at position S_global."""
    # linearized shard index over (possibly multiple) sequence axes
    idx = jnp.int32(0)
    for ax in seq_axes:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    n_shards = 1
    for ax in seq_axes:
        n_shards *= lax.axis_size(ax)
    S_total = n_shards * shard_len
    offset = idx * shard_len
    B, _, Hq, Dq = q.shape
    Hkv = kc.shape[2]
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / (Dq ** 0.5)
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(kc, g, axis=2) if g > 1 else kc
    vf = jnp.repeat(vc, g, axis=2) if g > 1 else vc
    s_loc = jnp.einsum("bqhd,bkhd->bhqk", qf, kf.astype(jnp.float32)) * sc
    kpos = (offset + jnp.arange(shard_len))[None, None, None, :]
    if has_pos:
        # per-request masking: slot j attendable iff j < pos_b (and inside
        # the sliding window measured from the new token at pos_b)
        pb = pos[:, None, None, None]
        ok = kpos < pb
        if window and window > 0:
            ok = ok & (kpos > pb - window)
        s_loc = jnp.where(ok, s_loc, NEG_INF)
    elif window and window > 0:
        # legacy: new token position = S_total; attendable cache slots are
        # those with pos > S_total − window
        s_loc = jnp.where(kpos > S_total - window, s_loc, NEG_INF)
    m_loc = jnp.max(s_loc, axis=-1)                      # (B,H,1)
    m_glb = lax.pmax(m_loc, seq_axes)
    m_safe = jnp.maximum(m_glb, NEG_INF / 2)
    p_loc = jnp.exp(s_loc - m_safe[..., None])
    p_loc = jnp.where(m_loc[..., None] <= NEG_INF / 2,
                      jnp.zeros_like(p_loc), p_loc)
    num = jnp.einsum("bhqk,bkhd->bhqd", p_loc, vf.astype(jnp.float32))
    den = jnp.sum(p_loc, axis=-1)                        # (B,H,1)
    num = lax.psum(num, seq_axes)
    den = lax.psum(den, seq_axes)
    lse_c = jnp.where(den == 0.0, NEG_INF, m_safe + jnp.log(
        jnp.where(den == 0.0, 1.0, den)))                # (B,H,1) cache lse
    o_c = num / jnp.where(den == 0.0, 1.0, den)[..., None]
    o_c = jnp.where((den == 0.0)[..., None], 0.0, o_c)
    # merge with the new token's self-attention (replicated, added once —
    # after the cross-shard psum so it isn't multiply counted)
    k1r = jnp.repeat(k1, g, axis=2) if g > 1 else k1
    v1r = jnp.repeat(v1, g, axis=2) if g > 1 else v1
    s1 = jnp.einsum("bqhd,bkhd->bhqk", qf, k1r.astype(jnp.float32)) * sc
    lse1 = s1[..., 0]                                    # (B,H,1): one key
    o1 = v1r.astype(jnp.float32).transpose(0, 2, 1, 3)   # (B,Hq,1,Dv)
    o_m, _ = _merge_bh(o_c, lse_c, o1, lse1)
    return o_m.transpose(0, 2, 1, 3).astype(q.dtype)     # (B,1,Hq,Dv)


def _merge_bh(o1, lse1, o2, lse2):
    """merge in (B,H,1,D)/(B,H,1) layout."""
    mx = jnp.maximum(jnp.maximum(lse1, lse2), NEG_INF)
    w1 = jnp.exp(lse1 - mx)
    w2 = jnp.exp(lse2 - mx)
    den = w1 + w2
    den_s = jnp.where(den == 0.0, 1.0, den)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / den_s[..., None]
    return o, mx + jnp.log(den_s)


def dist_decode_attn(q, k_cache, v_cache, k_new, v_new, *, mesh,
                     seq_axes=("model",), batch_axes=("data",),
                     mask: Optional[MaskSpec] = None, window=None,
                     scale=None, shard_len=None, pos=None):
    """One-token decode against a sequence-sharded KV cache.

    The cache's sequence dim is sharded over ``seq_axes`` (supports the 2D
    (data, model) sharding used by long_500k); the query and the new token's
    k/v are replicated across them. Exact lse-weighted combine across shards
    (distributed flash-decoding), then a final merge with the new token's
    self-attention.

    ``mask`` is a :class:`~repro.core.mask.MaskSpec` of kind ``causal``
    (attend the whole cache — the default) or ``sliding_window``; the new
    token always sits at the end of the context, so those are the only
    kinds decode can express.  The pre-MaskSpec ``window=`` kwarg is
    removed — passing it raises ``TypeError`` with the migration hint.

    ``pos`` (B,) int32 — per-request valid-context lengths (continuous
    batching admits requests at different times, so each batch row has its
    own position): cache slots ≥ pos[b] are masked for request b and the
    sliding window is measured from pos[b].  ``pos=None`` keeps the legacy
    whole-cache semantics; a scalar ``pos`` is broadcast over the batch
    with a one-shot DeprecationWarning (it silently mis-masks mixed-length
    batches).
    """
    if window is not None:
        raise TypeError(
            "dist_decode_attn(window=) was removed; pass "
            "mask=repro.core.mask.{causal,sliding_window}(...)")
    if mask is None:
        mask = mk.causal()
    if mask.kinds - {"causal", "sliding_window"}:
        raise ValueError(
            f"dist_decode_attn serves causal/sliding_window masks only "
            f"(got {mask.kind!r}) — the decode token is last, so other "
            f"kinds have no decode meaning")
    if mask.q_offset or mask.kv_offset:
        raise ValueError("dist_decode_attn mask must be offset-free — "
                         "decode positions are derived from the cache "
                         "layout")
    w = mask.window
    n = 1
    for ax in seq_axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
    if shard_len is None:
        shard_len = k_cache.shape[1] // n
    b = tuple(batch_axes) if batch_axes else None
    seq = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
    rep = P(b, None, None, None)
    shd = P(b, seq, None, None)
    in_specs = [rep, shd, shd, rep, rep]
    args = [q, k_cache, v_cache, k_new, v_new]
    if pos is not None:
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            mk.warn_legacy_once(
                "dist_decode_attn(pos=<scalar>)",
                "a (B,) per-request position vector")
            pos = jnp.broadcast_to(pos, (q.shape[0],))
        in_specs.append(P(b))
        args.append(pos)
    fn = jax.shard_map(
        partial(_decode_local, tuple(seq_axes), shard_len, w, scale,
                pos is not None),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=rep, check_vma=False)
    return fn(*args)


# --------------------------------------------------------------------------
# Zigzag layout helpers + the MLA latent ring (plan-based)
# --------------------------------------------------------------------------

def zigzag_perm(T: int, P: int):
    """Natural→zigzag permutation: new global array order is
    [chunk 0, chunk 2P−1 | chunk 1, chunk 2P−2 | …] so contiguous device
    shards hold (p, 2P−1−p). Returns an index array of length T."""
    c = T // (2 * P)
    order = []
    for p in range(P):
        order.append(np.arange(p * c, (p + 1) * c))
        q = 2 * P - 1 - p
        order.append(np.arange(q * c, (q + 1) * c))
    return np.concatenate(order)


# BEYOND-PAPER: MLA latent ring. For DeepSeek MLA the materialized per-head
# K/V chunk is n_heads·(d_qk+d_v) wide (v3: 128·320 = 40960/token) while the
# latent it is deterministically derived from is kv_lora+rope = 576/token —
# a 71× comm reduction if the ring ships the latent and every worker
# up-projects locally (recompute-over-communicate, the same trade the
# paper's §3.3 makes for time). Composed with the zigzag placement the
# schedule is also load-balanced with no helper sends.  Since the plan IR
# rewrite this is the *same zigzag plan* run with a latent payload on the
# KV ring (``execute_fwd(..., latent=...)``).

def _fwd_latent_local(spec, expand, q, k, v, payload, w_up):
    plan = sp.build_plan("zigzag", spec.mask, spec.axis_size, q.shape[1])
    return sp.execute_fwd(plan, q, k, v, None, axis=spec.axis,
                          tune=_tune(spec), latent=(payload, w_up, expand))


def dist_attn_fwd_latent(q, k, v, payload, w_up, expand, *, mesh, spec,
                         batch_axes=("data",)):
    """Latent-ring forward (zigzag schedule). ``payload``: (B, T, d_lat)
    sharded like activations; ``w_up``: replicated up-projection weights;
    ``expand(payload_chunk, w_up) -> (k, v)`` pure."""
    if spec.mask.kinds - {"causal"}:
        raise ValueError("latent ring supports plain causal masks only "
                         f"(got {spec.mask.kind!r})")
    b = tuple(batch_axes) if batch_axes else None
    qkv_s = P(b, spec.axis, None, None)
    pl_s = P(b, spec.axis, None)
    lse_s = P(b, spec.axis, None)
    w_s = jax.tree.map(lambda a: P(*(None,) * a.ndim), w_up)
    fn = jax.shard_map(
        partial(_fwd_latent_local, spec, expand), mesh=mesh,
        in_specs=(qkv_s, qkv_s, qkv_s, pl_s, w_s),
        out_specs=(qkv_s, lse_s), check_vma=False)
    return fn(q, k, v, payload, w_up)
