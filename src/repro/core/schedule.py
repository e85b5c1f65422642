"""Schedule-plan IR: one step engine for every distributed-attention
schedule.

DISTFLASHATTN's schedule family (ring, load-balanced, zigzag, the MLA
latent ring) differs only in *placement and per-step routing* — which
(q-chunk, kv-chunk) pair each device computes at each ring step, and where
the partial result / its gradients are merged.  That structure is static
at trace time, so this module captures it once as a declarative
:class:`SchedulePlan` and runs any plan through **one forward executor**
(:func:`execute_fwd`) and **one backward executor** (:func:`execute_bwd`)
that implement the shared machinery — ppermute prefetch overlap, traveling
``(dk, dv)`` / ``dq``-bundle accumulators, segment-ID shipping (or
trace-time derivation from static document ``boundaries``), and
``mask_partial``/``merge`` result routing — exactly once.

The IR
------
* :class:`Ref` — one operand chunk: ``src`` ∈ ``local`` (this device's
  shard) | ``ring`` (the traveling KV container) | ``bundle`` (the
  traveling query bundle of the balanced schedule); ``chunk`` indexes the
  shard's ``n_chunks`` sub-chunks (zigzag holds two).
* :class:`Operand` — a Ref, optionally predicate-selected against an
  alternative (``jnp.where`` on the device index — the balanced schedule's
  worker/helper fusion runs one kernel per step).
* :class:`Route` — where one kernel result goes: merge into a local output
  chunk gated by a device predicate, optionally after a ``ship`` ppermute
  (the balanced helper sending ``(o, lse)`` home).
* :class:`Work` — one chunk-attention kernel call: q/kv operands, the
  step's static :class:`~repro.core.mask.MaskSpec`, result routes, and
  whether the mask needs *dynamic position offsets* (zigzag window bands,
  whose chunk distance depends on the device index).
* :class:`Step` — the Work items at one ring step plus the ring ``shift``
  (hops advanced since the previous executed step — >1 when intermediate
  steps were statically skipped).

Step skipping
-------------
Because every Work item's mask and chunk placement are static, the plan
builders prove per step (enumerating the P device indices in python)
whether *any* device has an unmasked (q, kv) pair —
:func:`repro.core.mask.chunk_pair_needed` — and drop provably all-masked
items/steps: sliding windows truncate the ring tail (and, for zigzag,
carve out the middle steps; mirror chunks make both sequence ends local),
and static document ``boundaries`` prune steps no document spans.

The backward pass interprets the *same plan*: each Work's gradient sinks
follow its operand sources (local q → local ``dq``; bundle q → traveling
``dq`` bundle; ring kv → traveling ``(dk, dv)``; local kv → home
``(dk, dv)``), so a schedule is written once and gets both passes.

:func:`plan_coverage` is a pure-numpy simulator of the executor used by
the property tests (every causal pair computed exactly once; skipped steps
provably all-masked), and :func:`plan_cost` is the static comm/compute
model behind ``DistAttnSpec(schedule="auto")`` (see
:func:`choose_schedule`), with time conversion wired into
``analysis/roofline.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import mask as mk
from repro.core.attention import (chunk_attn, chunk_attn_bwd, empty_partial,
                                  mask_partial, merge)
from repro.core.mask import MaskSpec

# ---------------------------------------------------------------------------
# Predicates on the (traced) device index p — static tuples
# ---------------------------------------------------------------------------

ALWAYS = ("always",)


def _ge(t):
    return ("ge", int(t))


def _lt(t):
    return ("lt", int(t))


def _neg(pred):
    if pred == ALWAYS:
        return ("never",)
    kind, t = pred
    return ("lt", t) if kind == "ge" else ("ge", t)


def _pred_val(pred, p):
    """Traced bool for ``pred`` at device index ``p`` (None = statically
    true)."""
    if pred == ALWAYS:
        return None
    kind, t = pred
    return (p >= t) if kind == "ge" else (p < t)


def _pred_int(pred, p: int) -> bool:
    """Python evaluation (plan simulator)."""
    if pred == ALWAYS:
        return True
    kind, t = pred
    return (p >= t) if kind == "ge" else (p < t)


# ---------------------------------------------------------------------------
# IR dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ref:
    """One operand chunk: which container, which sub-chunk."""
    src: str                        # "local" | "ring" | "bundle"
    chunk: int = 0                  # sub-chunk index (< plan.n_chunks)


@dataclasses.dataclass(frozen=True)
class Operand:
    """A Ref, optionally predicate-selected against an alternative:
    devices where ``pred`` holds use ``ref``, others use ``alt``."""
    ref: Ref
    alt: Optional[Ref] = None
    pred: Tuple = ALWAYS


@dataclasses.dataclass(frozen=True)
class Route:
    """Routing of one kernel result: merge into local output ``chunk``
    where ``pred`` holds; ``ship`` != 0 first ppermutes the raw (o, lse)
    by that shift and gates the merge with ``recv_pred`` on the receiving
    device (the balanced helper send-home)."""
    pred: Tuple = ALWAYS
    chunk: int = 0
    ship: int = 0
    recv_pred: Tuple = ALWAYS


@dataclasses.dataclass(frozen=True)
class Work:
    """One chunk-attention kernel call and its result routing.
    ``dyn_offsets`` marks masks whose chunk distance depends on the device
    index: the executor passes traced absolute q/kv position offsets
    (zigzag window bands) and resolution is restricted to
    ``dynamic_offsets`` backends."""
    q: Operand
    kv: Operand
    mask: MaskSpec
    routes: Tuple[Route, ...]
    dyn_offsets: bool = False


@dataclasses.dataclass(frozen=True)
class Step:
    """Ring step: advance the traveling containers by ``shift`` hops
    (>1 when skipped steps were folded in), then run ``work``."""
    shift: int
    work: Tuple[Work, ...]


@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """Static trace-time description of one distributed-attention
    schedule.  ``steps[0]`` is the local step (shift 0); ``mask`` is the
    *global* MaskSpec (may carry static ``boundaries`` — work masks are
    always boundary-stripped, the executor derives per-shard segment
    arrays instead)."""
    name: str
    P: int
    Tl: int                          # local shard length (tokens)
    n_chunks: int                    # local shard viewed as n sub-chunks
    layout: str                      # "natural" | "zigzag"
    mask: MaskSpec
    steps: Tuple[Step, ...]
    total_steps: int                 # ring steps before static skipping

    @property
    def chunk_len(self) -> int:
        return self.Tl // self.n_chunks

    @property
    def exec_steps(self) -> int:
        """Ring steps actually executed (local step excluded)."""
        return len(self.steps) - 1

    @property
    def skipped_steps(self) -> int:
        return self.total_steps - self.exec_steps

    @property
    def kernel_calls(self) -> int:
        return sum(len(s.work) for s in self.steps)

    def _uses(self, src: str) -> bool:
        for s in self.steps:
            for w in s.work:
                for op in (w.q, w.kv):
                    if op.ref.src == src or (op.alt and op.alt.src == src):
                        return True
        return False

    @property
    def ship_q(self) -> bool:
        """A query bundle travels the ring (balanced helpers)."""
        return self._uses("bundle")

    @property
    def uses_ring(self) -> bool:
        return self._uses("ring")

    def cost(self, **kw) -> "PlanCost":
        return plan_cost(self, **kw)


# ---------------------------------------------------------------------------
# Plan builders
# ---------------------------------------------------------------------------

PLAN_SCHEDULES = ("ring", "balanced", "zigzag")

_L0 = Operand(Ref("local", 0))
_L1 = Operand(Ref("local", 1))
_R0 = Operand(Ref("ring", 0))
_R1 = Operand(Ref("ring", 1))
_B0 = Operand(Ref("bundle", 0))


def _exec_mask(m: MaskSpec) -> MaskSpec:
    """Kernel-facing variant of the global mask: static ``boundaries`` are
    absolute coordinates the per-shard kernels can't see — strip them (the
    executor derives per-shard segment arrays from them instead)."""
    return m.replace(boundaries=None) if m.boundaries is not None else m


def _any_pair(m: MaskSpec, c: int, pairs) -> bool:
    """Does any device's (q-chunk, kv-chunk) global-index pair have a
    possibly-unmasked position pair?  ``pairs`` iterates (qg, kg) global
    chunk indices; chunks span ``c`` tokens."""
    return any(mk.chunk_pair_needed(m, qg * c, (qg + 1) * c - 1,
                                    kg * c, (kg + 1) * c - 1)
               for qg, kg in pairs)


def _assemble(name, m, P, Tl, n_chunks, layout, local_work, executed,
              total_steps) -> SchedulePlan:
    """Fold the executed (t, works) list into Steps with cumulative
    shifts over skipped ring steps."""
    steps = [Step(0, tuple(local_work))]
    prev = 0
    for t, works in executed:
        steps.append(Step(t - prev, tuple(works)))
        prev = t
    return SchedulePlan(name=name, P=P, Tl=Tl, n_chunks=n_chunks,
                        layout=layout, mask=m, steps=tuple(steps),
                        total_steps=total_steps)


def _ring_plan(m: MaskSpec, P: int, Tl: int) -> SchedulePlan:
    """Vanilla ring (paper Alg. 1): P−1 steps, device p computes
    (q_p × kv_{p−t}); causal devices p < t idle.  Sliding windows truncate
    the tail; static document boundaries prune steps no document spans."""
    me = _exec_mask(m)
    local = [Work(_L0, _L0, me, (Route(),))]
    executed = []
    for t in range(1, P):
        devs = range(t, P) if m.causal else range(P)
        if not _any_pair(m, Tl, [(p, (p - t) % P) for p in devs]):
            continue
        pred = _ge(t) if m.causal else ALWAYS
        executed.append((t, [Work(_L0, _R0, mk.ring_step(me, t * Tl),
                                  (Route(pred=pred),))]))
    return _assemble("ring", m, P, Tl, 1, "natural", local, executed, P - 1)


def _balanced_plan(m: MaskSpec, P: int, Tl: int) -> SchedulePlan:
    """Load-balanced schedule (paper Alg. 2): ⌊P/2⌋ steps; workers with
    causal work left compute (q_p × kv_{p−t}) while helpers compute
    (q_{(p−t) mod P} × kv_p) for distance-(P−t) pairs and ship (o, lse)
    home.  Plain causal (± dynamic document) fuses both roles into one
    predicate-selected kernel per step, as the paper's implementation
    does; windowed / boundary-pruned variants split into separately
    skippable worker and helper items (worker distance t, helper distance
    P−t — a small window truncates to a helper-free, balanced-by-
    construction band)."""
    me = _exec_mask(m)
    local = [Work(_L0, _L0, me, (Route(),))]
    T = P // 2
    fused = m.window == 0 and m.boundaries is None
    executed = []
    for t in range(1, T + 1):
        helpers = (t != T) or (P % 2 == 1)
        if fused:
            routes = [Route(pred=_ge(t))]
            if helpers:
                routes.append(Route(pred=_lt(t), ship=-t,
                                    recv_pred=_ge(P - t)))
            executed.append((t, [Work(
                Operand(Ref("local", 0), Ref("bundle", 0), _ge(t)),
                Operand(Ref("ring", 0), Ref("local", 0), _ge(t)),
                mk.strict_causal_pair(me), tuple(routes))]))
            continue
        works = []
        if _any_pair(m, Tl, [(p, p - t) for p in range(t, P)]):
            works.append(Work(_L0, _R0, mk.ring_step(me, t * Tl),
                              (Route(pred=_ge(t)),)))
        if helpers and _any_pair(m, Tl, [(p + P - t, p) for p in range(t)]):
            works.append(Work(_B0, _L0, mk.ring_step(me, (P - t) * Tl),
                              (Route(pred=_lt(t), ship=-t,
                                     recv_pred=_ge(P - t)),)))
        if works:
            executed.append((t, works))
    return _assemble("balanced", m, P, Tl, 1, "natural", local, executed, T)


def _zigzag_plan(m: MaskSpec, P: int, Tl: int) -> SchedulePlan:
    """Zigzag placement (beyond-paper): 2P half-chunks, device p holds
    (p, 2P−1−p); exact balance with only the KV ring.  At step t the
    received container holds chunks (r, 2P−1−r) of r = (p−t) mod P and
    each device computes two strictly-causal pairs.  Mirror-chunk pair
    distances depend on the device index, so windowed variants use
    dynamic-offset masks — and skipping carves out the *middle* steps
    (both sequence ends are ring-local under the mirror placement)."""
    if Tl % 2:
        raise ValueError(f"zigzag needs an even local shard length, "
                         f"got {Tl}")
    c = Tl // 2
    G = 2 * P

    def gl(p, i):                      # global half-chunk of (device, slot)
        return p if i == 0 else G - 1 - p

    me = _exec_mask(m)
    m_x = mk.strict_causal_pair(me)
    m_dyn = mk.offdiag_step(me)
    win = m.window > 0
    local = [Work(_L0, _L0, me, (Route(chunk=0),))]
    if _any_pair(m, c, [(gl(p, 1), gl(p, 0)) for p in range(P)]):
        local.append(Work(_L1, _L0, m_dyn if win else m_x,
                          (Route(chunk=1),), dyn_offsets=win))
    local.append(Work(_L1, _L1, me, (Route(chunk=1),)))
    fused = m.window == 0 and m.boundaries is None
    executed = []
    for t in range(1, P):
        if fused:
            w1 = Work(Operand(Ref("local", 0), Ref("local", 1), _ge(t)),
                      _R0, m_x,
                      (Route(pred=_ge(t), chunk=0),
                       Route(pred=_lt(t), chunk=1)))
            w2 = Work(_L1,
                      Operand(Ref("ring", 0), Ref("ring", 1), _ge(t)),
                      m_x, (Route(chunk=1),))
            executed.append((t, [w1, w2]))
            continue
        works = []
        # worker a×a_r — static distance t
        if _any_pair(m, c, [(p, p - t) for p in range(t, P)]):
            works.append(Work(_L0, _R0, mk.ring_step(me, t * c),
                              (Route(pred=_ge(t), chunk=0),)))
        # b̄×a_r — distances P−1−2p+t (helpers) / 2P−1−2p+t (workers),
        # device-dependent; both branches are the *same* kernel call
        # (q=local1, kv=ring0, dynamic-offset mask), so when both survive
        # pruning they fuse into one always-routed Work
        need_h = _any_pair(m, c, [(gl(p, 1), p + P - t) for p in range(t)])
        need_w = _any_pair(m, c, [(gl(p, 1), p - t) for p in range(t, P)])
        if need_h or need_w:
            pred = ALWAYS if (need_h and need_w) else \
                (_lt(t) if need_h else _ge(t))
            works.append(Work(_L1, _R0, m_dyn,
                              (Route(pred=pred, chunk=1),),
                              dyn_offsets=True))
        # helper b̄×b̄_r — static distance P−t
        if _any_pair(m, c, [(gl(p, 1), gl(p + P - t, 1))
                            for p in range(t)]):
            works.append(Work(_L1, _R1, mk.ring_step(me, (P - t) * c),
                              (Route(pred=_lt(t), chunk=1),)))
        if works:
            executed.append((t, works))
    return _assemble("zigzag", m, P, Tl, 2, "zigzag", local, executed,
                     P - 1)


_BUILDERS = {"ring": _ring_plan, "balanced": _balanced_plan,
             "zigzag": _zigzag_plan}


def build_plan(schedule: str, mask: MaskSpec, P: int, Tl: int) \
        -> SchedulePlan:
    """Build the SchedulePlan for one schedule × mask × P × shard length.
    Pure python over static ints — runs at trace time."""
    if schedule not in _BUILDERS:
        raise ValueError(f"no plan builder for schedule {schedule!r}; "
                         f"plan schedules: {PLAN_SCHEDULES}")
    return _BUILDERS[schedule](mask, P, Tl)


# ---------------------------------------------------------------------------
# Shared executor machinery
# ---------------------------------------------------------------------------

def _shift(x, axis, shift, size):
    """ppermute by ``shift`` hops: device p receives from (p − shift) mod
    P.  Multi-hop shifts (skipped steps folded together) are one
    collective."""
    perm = [(i, (i + shift) % size) for i in range(size)]
    return jax.tree.map(lambda a: lax.ppermute(a, axis, perm), x)


def _gchunk(layout, P, owner, i):
    """Global chunk index of (owner device, local sub-chunk i); works for
    python ints and traced owners."""
    if layout == "zigzag" and i == 1:
        return 2 * P - 1 - owner
    return owner


class _Ctx:
    """Per-trace executor state: local shards, the traveling containers at
    the current ring distance, and the static plan."""

    def __init__(self, plan, axis, tune, q, k, v, seg, latent=None):
        self.plan, self.axis, self.tune = plan, axis, tune
        self.P = plan.P
        self.p = lax.axis_index(axis)
        self.nc = plan.n_chunks
        self.c = q.shape[1] // self.nc
        self.B = q.shape[0]
        self.q, self.k, self.v, self.seg = q, k, v, seg
        self.latent = latent                  # (payload, w_up, expand)
        m = plan.mask
        self.doc = m.document
        self.derive_seg = (m.document and seg is None
                          and m.boundaries is not None)
        self.d = 0                            # current ring distance
        self.ring_kv = None                   # (k, v) at distance d
        self.ring_seg = None
        self.bundle = None                    # fwd: q; bwd: (q, do, lse, Δ)

    # ------------------------------------------------------------ chunks
    def _cut(self, x, i):
        return x[:, i * self.c:(i + 1) * self.c]

    def owner(self, src):
        return self.p if src == "local" else (self.p - self.d) % self.P

    def offset(self, ref):
        """Traced absolute token offset of a ref's chunk."""
        g = _gchunk(self.plan.layout, self.P, self.owner(ref.src), ref.chunk)
        return (g * self.c).astype(jnp.int32) if hasattr(g, "astype") \
            else jnp.int32(g * self.c)

    def seg_for(self, ref):
        """(B, c) int32 segment IDs for a ref's chunk, or None."""
        if not self.doc:
            return None
        if self.derive_seg:
            g = _gchunk(self.plan.layout, self.P, self.owner(ref.src),
                        ref.chunk)
            pos = g * self.c + jnp.arange(self.c)
            row = self.plan.mask.segment_of(pos)
            return jnp.broadcast_to(row[None, :], (self.B, self.c))
        if self.seg is None:
            return None
        arr = self.seg if ref.src == "local" else self.ring_seg
        return self._cut(arr, ref.chunk)

    # ---------------------------------------------------------- containers
    def data_containers(self, bwd_bundle=None):
        """The pytree of traveling data (built once, before the first
        shift).  ``bwd_bundle`` supplies (do, lse, delta) so the backward
        bundle carries the helper-side statistics next to q."""
        plan = self.plan
        data = {}
        if plan.uses_ring:
            data["kv"] = self.latent[0] if self.latent else (self.k, self.v)
        if plan.ship_q:
            data["bundle"] = (self.q,) if bwd_bundle is None \
                else (self.q,) + tuple(bwd_bundle)
        if self.doc and not self.derive_seg and self.seg is not None \
                and (plan.uses_ring or plan.ship_q):
            data["seg"] = self.seg
        return data

    def install(self, data):
        """Point the ctx at a (shifted) container pytree."""
        if "kv" in data:
            if self.latent:
                _, w_up, expand = self.latent
                self.ring_kv = expand(data["kv"], w_up)
            else:
                self.ring_kv = data["kv"]
        self.ring_seg = data.get("seg")
        self.bundle = data.get("bundle")


def _sel(pv, a, b):
    """Predicate-select two pytrees of arrays/scalars (None passes
    through)."""
    return jax.tree.map(lambda x, y: jnp.where(pv, x, y), a, b)


def _q_side(ctx: _Ctx, ref: Ref, extras):
    """(q, seg, off[, extras...]) for a q-side ref.  ``extras`` names the
    bundle-resident statistics the backward needs (do, lse, delta), pulled
    from the local arrays or the traveling bundle to match the ref."""
    if ref.src == "local":
        vals = [ctx._cut(ctx.q, ref.chunk)]
        vals += [ctx._cut(x, ref.chunk) for x in extras]
    else:
        assert ref.src == "bundle"
        vals = [ctx._cut(ctx.bundle[0], ref.chunk)]
        vals += [ctx._cut(x, ref.chunk) for x in ctx.bundle[1:]]
    return tuple(vals) + (ctx.seg_for(ref), ctx.offset(ref))


def _kv_side(ctx: _Ctx, ref: Ref):
    kk, vv = (ctx.k, ctx.v) if ref.src == "local" else ctx.ring_kv
    return (ctx._cut(kk, ref.chunk), ctx._cut(vv, ref.chunk),
            ctx.seg_for(ref), ctx.offset(ref))


def _resolve(ctx, op: Operand, side_fn):
    a = side_fn(op.ref)
    if op.alt is None:
        return a
    b = side_fn(op.alt)
    pv = _pred_val(op.pred, ctx.p)
    return tuple(None if x is None else _sel(pv, x, y)
                 for x, y in zip(a, b))


def _mask_kw(ctx, w: Work, q_seg, kv_seg, q_off, kv_off):
    kw = dict(ctx.tune)
    if w.mask.document and q_seg is not None:
        kw.update(q_segments=q_seg, kv_segments=kv_seg)
    if w.dyn_offsets:
        kw.update(q_offset=q_off, kv_offset=kv_off)
    return kw


def _wval(ctx, preds):
    """f32 product weight of a predicate list (None = 1)."""
    w = None
    for pr in preds:
        v = _pred_val(pr, ctx.p)
        if v is None:
            continue
        v = v.astype(jnp.float32)
        w = v if w is None else w * v
    return w


def _grad_branches(op: Operand, route_pred):
    """Resolve which operand branch(es) a route's gradient flows to,
    with the predicate weight(s): [(preds, ref), ...]."""
    if op.alt is None or op.pred == ALWAYS:
        return [([route_pred], op.ref)]
    if op.pred == route_pred:
        return [([route_pred], op.ref)]
    if op.pred == _neg(route_pred):
        return [([route_pred], op.alt)]
    return [([route_pred, op.pred], op.ref),
            ([route_pred, _neg(op.pred)], op.alt)]


# ---------------------------------------------------------------------------
# Forward executor
# ---------------------------------------------------------------------------

def execute_fwd(plan: SchedulePlan, q, k, v, seg=None, *, axis, tune,
                latent=None):
    """Run any SchedulePlan forward.  Local (per-shard) code for
    ``shard_map``; returns (o, lse).  ``latent=(payload, w_up, expand)``
    ships the payload on the KV ring and expands it locally on every
    device (the MLA latent ring's recompute-over-communicate trade)."""
    ctx = _Ctx(plan, axis, tune, q, k, v, seg, latent)
    acc = [None] * plan.n_chunks

    def run(step):
        for w in step.work:
            qc, q_seg, q_off = _resolve(ctx, w.q, lambda r: _q_side(ctx, r, ()))
            kc, vc, kv_seg, kv_off = _resolve(ctx, w.kv,
                                              lambda r: _kv_side(ctx, r))
            o_t, s_t = chunk_attn(qc, kc, vc, mask=w.mask,
                                  **_mask_kw(ctx, w, q_seg, kv_seg,
                                             q_off, kv_off))
            for r in w.routes:
                o_r, s_r = o_t, s_t
                pred = r.pred
                if r.ship:
                    o_r, s_r = _shift((o_t, s_t), axis, r.ship, plan.P)
                    pred = r.recv_pred
                pv = _pred_val(pred, ctx.p)
                if pv is not None:
                    o_r, s_r = mask_partial(pv, o_r, s_r)
                acc[r.chunk] = (o_r, s_r) if acc[r.chunk] is None \
                    else merge(*acc[r.chunk], o_r, s_r)

    run(plan.steps[0])
    rest = plan.steps[1:]
    if rest:
        data = ctx.data_containers()
        data = _shift(data, axis, rest[0].shift, plan.P)   # prefetch step 1
        ctx.d = rest[0].shift
        ctx.install(data)
        for i, step in enumerate(rest):
            nxt = _shift(data, axis, rest[i + 1].shift, plan.P) \
                if i + 1 < len(rest) else None               # prefetch (overlap)
            run(step)
            if nxt is not None:
                data = nxt
                ctx.d += rest[i + 1].shift
                ctx.install(data)
    outs = [a if a is not None
            else empty_partial(ctx._cut(q, i))
            for i, a in enumerate(acc)]
    if plan.n_chunks == 1:
        return outs[0]
    return (jnp.concatenate([o for o, _ in outs], axis=1),
            jnp.concatenate([s for _, s in outs], axis=1))


# ---------------------------------------------------------------------------
# Backward executor
# ---------------------------------------------------------------------------

def execute_bwd(plan: SchedulePlan, q, k, v, o, lse, do, seg=None, *,
                axis, tune):
    """Run any SchedulePlan backward from the saved (o, lse) — FA2
    backward per Work item, gradients routed by operand source, traveling
    accumulators returned home with one final multi-hop ppermute.
    Returns (dq, dk, dv)."""
    f32 = jnp.float32
    delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)     # (B,T,H)
    ctx = _Ctx(plan, axis, tune, q, k, v, seg)
    dq = jnp.zeros(q.shape, f32)
    dk_home = jnp.zeros(k.shape, f32)
    dv_home = jnp.zeros(v.shape, f32)
    dkv = (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)) \
        if plan.uses_ring else None
    dqb = jnp.zeros(q.shape, f32) if plan.ship_q else None

    def sl(i):
        return slice(i * ctx.c, (i + 1) * ctx.c)

    def add(base, i, val, wgt):
        val = val.astype(f32) if wgt is None else val.astype(f32) * wgt
        return base.at[:, sl(i)].add(val)

    def run(step):
        nonlocal dq, dk_home, dv_home, dkv, dqb
        for w in step.work:
            qc, do_c, lse_c, dlt_c, q_seg, q_off = _resolve(
                ctx, w.q, lambda r: _q_side(ctx, r, (do, lse, delta)))
            kc, vc, kv_seg, kv_off = _resolve(ctx, w.kv,
                                              lambda r: _kv_side(ctx, r))
            dq_t, dk_t, dv_t = chunk_attn_bwd(
                qc, kc, vc, jnp.zeros_like(qc), lse_c, do_c, mask=w.mask,
                delta=dlt_c,
                **_mask_kw(ctx, w, q_seg, kv_seg, q_off, kv_off))
            for r in w.routes:
                for preds, ref in _grad_branches(w.q, r.pred):
                    wgt = _wval(ctx, preds)
                    if ref.src == "local":
                        dq = add(dq, ref.chunk, dq_t, wgt)
                    else:
                        dqb = add(dqb, ref.chunk, dq_t, wgt)
                for preds, ref in _grad_branches(w.kv, r.pred):
                    wgt = _wval(ctx, preds)
                    if ref.src == "local":
                        dk_home = add(dk_home, ref.chunk, dk_t, wgt)
                        dv_home = add(dv_home, ref.chunk, dv_t, wgt)
                    else:
                        dkv = (add(dkv[0], ref.chunk, dk_t, wgt),
                               add(dkv[1], ref.chunk, dv_t, wgt))

    run(plan.steps[0])
    rest = plan.steps[1:]
    if rest:
        data = ctx.data_containers(bwd_bundle=(do, lse, delta))
        data = _shift(data, axis, rest[0].shift, plan.P)
        ctx.d = rest[0].shift
        ctx.install(data)
        for i, step in enumerate(rest):
            nxt = _shift(data, axis, rest[i + 1].shift, plan.P) \
                if i + 1 < len(rest) else None               # prefetch (overlap)
            run(step)
            if nxt is not None:
                data = nxt
                ctx.install(data)
                s = rest[i + 1].shift
                ctx.d += s
                if dkv is not None:                # accumulators move late
                    dkv = _shift(dkv, axis, s, plan.P)
                if dqb is not None:
                    dqb = _shift(dqb, axis, s, plan.P)
        D = ctx.d                                  # route accumulators home
        if dkv is not None:
            dkv = _shift(dkv, axis, -D, plan.P)
        if dqb is not None:
            dqb = _shift(dqb, axis, -D, plan.P)
    if dkv is not None:
        dk_home = dk_home + dkv[0]
        dv_home = dv_home + dkv[1]
    if dqb is not None:
        dq = dq + dqb
    return dq.astype(q.dtype), dk_home.astype(k.dtype), \
        dv_home.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pure-python plan simulator (property tests: exactly-once coverage)
# ---------------------------------------------------------------------------

def _sim_allow(w: Work, plan: SchedulePlan, qg, kg, c, segments):
    """Boolean (c, c) attend matrix exactly as the kernel would compute it
    for this work item: static mask offsets, plus true global offsets when
    ``dyn_offsets``, plus segment IDs (given or boundary-derived)."""
    m = w.mask
    q_pos = m.q_offset + (qg * c if w.dyn_offsets else 0) + np.arange(c)
    k_pos = m.kv_offset + (kg * c if w.dyn_offsets else 0) + np.arange(c)
    qs = ks = None
    if m.document:
        if segments is not None:
            qs = np.asarray(segments)[qg * c:(qg + 1) * c][:, None]
            ks = np.asarray(segments)[kg * c:(kg + 1) * c][None, :]
        elif plan.mask.boundaries is not None:
            gb = plan.mask
            qs = np.array([gb.segment_index(qg * c + i)
                           for i in range(c)])[:, None]
            ks = np.array([gb.segment_index(kg * c + j)
                           for j in range(c)])[None, :]
    allow = m.allow(q_pos[:, None], k_pos[None, :], qs, ks)
    if allow is None:
        return np.ones((c, c), bool)
    return np.asarray(allow)


def plan_coverage(plan: SchedulePlan, c: Optional[int] = None,
                  segments=None) -> np.ndarray:
    """(T, T) count of how many times each *global* (q, kv) token pair is
    computed-and-merged by the plan — a pure-python walk of the executor's
    routing.  ``c`` overrides tokens per sub-chunk (default: the plan's);
    ``segments`` is an optional (T,) global segment-ID array for dynamic
    document masks.  The exactly-once property: counts equal 1 on the
    global mask's allowed pairs and 0 elsewhere (see
    :func:`global_allow`)."""
    P, nc = plan.P, plan.n_chunks
    c = plan.chunk_len if c is None else c
    T = P * nc * c
    counts = np.zeros((T, T), np.int64)
    for p in range(P):
        d = 0
        for step in plan.steps:
            d += step.shift
            for w in step.work:
                qref = w.q.ref if _pred_int(w.q.pred, p) else w.q.alt
                kref = w.kv.ref if _pred_int(w.kv.pred, p) else w.kv.alt
                q_owner = p if qref.src == "local" else (p - d) % P
                k_owner = p if kref.src == "local" else (p - d) % P
                qg = _gchunk(plan.layout, P, q_owner, qref.chunk)
                kg = _gchunk(plan.layout, P, k_owner, kref.chunk)
                for r in w.routes:
                    if r.ship:
                        recv = (p + r.ship) % P
                        active = (_pred_int(r.pred, p)
                                  and _pred_int(r.recv_pred, recv))
                    else:
                        active = _pred_int(r.pred, p)
                    if not active:
                        continue
                    allow = _sim_allow(w, plan, qg, kg, c, segments)
                    counts[qg * c:(qg + 1) * c,
                           kg * c:(kg + 1) * c] += allow
    return counts


def global_allow(mask: MaskSpec, T: int, segments=None) -> np.ndarray:
    """(T, T) ground-truth attend matrix of the *global* mask at absolute
    positions — what the distributed schedules must jointly reproduce."""
    pos = np.arange(T)
    qs = ks = None
    if mask.document:
        if segments is not None:
            qs = np.asarray(segments)[:, None]
            ks = np.asarray(segments)[None, :]
        elif mask.boundaries is not None:
            seg = np.array([mask.segment_index(i) for i in range(T)])
            qs, ks = seg[:, None], seg[None, :]
        else:
            raise ValueError("document mask needs segments or boundaries")
    allow = mask.allow(pos[:, None], pos[None, :], qs, ks)
    if allow is None:
        return np.ones((T, T), bool)
    return np.asarray(allow)


# ---------------------------------------------------------------------------
# Static comm/compute cost model (drives schedule="auto")
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanCost:
    """Per-device static cost summary of one plan (or the ulysses
    baseline).  ``comm_bytes_*`` are hop-weighted ring-link bytes;
    ``flops_*`` count kernel matmul FLOPs after static mask pruning
    (dynamic-offset items count dense — their kernels can't prune)."""
    schedule: str
    exec_steps: int
    total_steps: int
    kernel_calls: int
    flops_fwd: float
    flops_bwd: float
    comm_bytes_fwd: float
    comm_bytes_bwd: float

    def time_estimate(self, include_bwd: bool = True) -> dict:
        """Two-term (compute, collective) roofline seconds via
        analysis.roofline constants — no HBM term (schedule-invariant)."""
        from repro.analysis.roofline import schedule_cost_terms
        fl = self.flops_fwd + (self.flops_bwd if include_bwd else 0.0)
        by = self.comm_bytes_fwd + (self.comm_bytes_bwd if include_bwd
                                    else 0.0)
        return schedule_cost_terms(flops=fl, comm_bytes=by)


def _band_pairs(mask: MaskSpec, cq: int, ck: int) -> float:
    """Unmasked (q, kv) pair count of a *static* work mask over a (cq, ck)
    chunk pair (document refinement is dynamic and ignored — an upper
    bound)."""
    if not (mask.causal or (mask.window and mask.window > 0)):
        return float(cq * ck)
    qpos = mask.q_offset - mask.kv_offset + np.arange(cq)
    hi = np.minimum(qpos, ck - 1) if mask.causal \
        else np.full(cq, ck - 1)
    lo = np.maximum(qpos - mask.window + 1, 0) if mask.window \
        else np.zeros(cq)
    return float(np.maximum(hi - lo + 1, 0).sum())


def plan_cost(plan: SchedulePlan, *, B: int = 1, Hq: int = 8,
              Hkv: Optional[int] = None, Dqk: int = 64,
              Dv: Optional[int] = None, bpe: int = 2,
              dynamic_seg: bool = False) -> PlanCost:
    """Static per-device cost of a plan: kernel FLOPs per Work item
    (after static mask pruning) and hop-weighted ring traffic per
    executed shift, fwd and bwd."""
    Hkv = Hq if Hkv is None else Hkv
    Dv = Dqk if Dv is None else Dv
    c = plan.chunk_len
    f_fwd = f_bwd = 0.0
    for s in plan.steps:
        for w in s.work:
            pairs = float(c * c) if w.dyn_offsets \
                else _band_pairs(w.mask, c, c)
            f_fwd += 2.0 * B * Hq * pairs * (Dqk + Dv)
            f_bwd += 2.0 * B * Hq * pairs * (3 * Dqk + 2 * Dv)
    kv_bytes = B * plan.Tl * Hkv * (Dqk + Dv) * bpe if plan.uses_ring \
        else 0.0
    seg_bytes = B * plan.Tl * 4 if (plan.mask.document and dynamic_seg
                                    and (plan.uses_ring or plan.ship_q)) \
        else 0.0
    q_bytes = B * plan.Tl * Hq * Dqk * bpe if plan.ship_q else 0.0
    do_bytes = B * plan.Tl * Hq * Dv * bpe if plan.ship_q else 0.0
    stat_bytes = 2 * B * plan.Tl * Hq * 4 if plan.ship_q else 0.0
    dkv_bytes = B * plan.Tl * Hkv * (Dqk + Dv) * 4 if plan.uses_ring \
        else 0.0
    dqb_bytes = B * plan.Tl * Hq * Dqk * 4 if plan.ship_q else 0.0
    shifts = [s.shift for s in plan.steps[1:]]
    D = sum(shifts)
    c_fwd = (kv_bytes + seg_bytes + q_bytes) * D
    for s in plan.steps:
        for w in s.work:
            for r in w.routes:
                if r.ship:
                    c_fwd += (B * c * Hq * Dv * bpe
                              + B * c * Hq * 4) * abs(r.ship)
    # bwd: data containers travel D hops; traveling accumulators move on
    # every transition after the first executed step (D − s1 hops) and
    # return home with one D-hop shift
    acc_hops = (D - shifts[0] if shifts else 0) + (D if shifts else 0)
    c_bwd = (kv_bytes + seg_bytes + q_bytes + do_bytes + stat_bytes) * D \
        + (dkv_bytes + dqb_bytes) * acc_hops
    return PlanCost(schedule=plan.name, exec_steps=plan.exec_steps,
                    total_steps=plan.total_steps,
                    kernel_calls=plan.kernel_calls,
                    flops_fwd=f_fwd, flops_bwd=f_bwd,
                    comm_bytes_fwd=c_fwd, comm_bytes_bwd=c_bwd)


def ulysses_cost(mask: MaskSpec, P: int, *, Tl: int, B: int = 1,
                 Hq: int = 8, Hkv: Optional[int] = None, Dqk: int = 64,
                 Dv: Optional[int] = None, bpe: int = 2) -> PlanCost:
    """Analytic per-device cost of the DeepSpeed-Ulysses baseline:
    all-to-all q/k/v + o, full-sequence attention over Hq/P heads."""
    Hkv = Hq if Hkv is None else Hkv
    Dv = Dqk if Dv is None else Dv
    Tg = P * Tl
    pairs = _band_pairs(mask, Tg, Tg)
    f_fwd = 2.0 * B * (Hq / P) * pairs * (Dqk + Dv)
    f_bwd = 2.0 * B * (Hq / P) * pairs * (3 * Dqk + 2 * Dv)
    a2a = (P - 1) / P
    io_fwd = B * Tl * (Hq * Dqk + Hkv * (Dqk + Dv) + Hq * Dv) * bpe \
        + B * Tl * Hq * 4                     # q,k,v in; o, lse back
    c_fwd = io_fwd * a2a
    c_bwd = 2.0 * c_fwd                       # dq,dk,dv + do round trips
    return PlanCost(schedule="ulysses", exec_steps=1, total_steps=1,
                    kernel_calls=1, flops_fwd=f_fwd, flops_bwd=f_bwd,
                    comm_bytes_fwd=c_fwd, comm_bytes_bwd=c_bwd)


def plan_capable(schedule: str, mask: MaskSpec) -> bool:
    """Can this plan schedule serve the mask?  (prefix_lm needs absolute
    kv positions on every chunk — ulysses/rsa territory; balanced/zigzag
    additionally need a causal-kind mask for their strictly-causal pair
    placement.  A *non-causal* sliding window needs future-direction band
    steps the ring's strictly-past step masks can't express — ulysses
    only.)"""
    if mask.prefix_len:
        return False
    if mask.window and not mask.causal:
        return False
    if schedule in ("balanced", "zigzag"):
        return bool(mask.causal)
    return schedule == "ring"


def ulysses_capable(mask: MaskSpec, P: int, Hq: int, Hkv: int, *,
                    include_bwd: bool = True) -> bool:
    """Can the bespoke ulysses baseline serve this call *without raising at
    execution time*?  Forward needs both head counts divisible by P
    (``_fwd_ulysses`` raises otherwise); a backward additionally rules out
    prefix_lm and non-causal sliding windows, because the baselines reuse
    the ring backward, whose per-shard chunks cannot see absolute
    positions / future-direction bands (``_bwd_local`` raises).  The
    trace-time filter must mirror those runtime checks exactly —
    ``schedule="auto"`` may never resolve to a name that then raises."""
    if Hq % P or Hkv % P:
        return False
    if include_bwd and mask.prefix_len:
        return False
    if include_bwd and mask.window and not mask.causal:
        return False
    return True


# ---------------------------------------------------------------------------
# 2D sequence×head (ring×ulysses) factored plans
# ---------------------------------------------------------------------------
#
# BurstAttention-style mesh factorization: the P sequence-parallel workers
# are split into a (seq = r) × (head = u) grid, P = r·u.  The global
# sequence is sharded over the *pair* of axes (seq major, head minor), so a
# tiled all-to-all over the head sub-axis — DeepSpeed-Ulysses' head scatter
# — leaves each device with a contiguous T/r sequence shard and Hq/u query
# heads; any ring-family SchedulePlan then runs unchanged on the seq
# sub-axis (windowed/document step pruning intact), and the results travel
# back through the inverse all-to-all.  GQA-aware: query heads always
# scatter; KV heads scatter when ``Hkv % u == 0`` and are otherwise
# all-gathered over the head sub-axis with a per-device head *selection*
# (each device keeps exactly the KV heads its query heads map to, so the
# inner plan is locally MHA).

PLAN2D_SCHEDULES = PLAN_SCHEDULES


@dataclasses.dataclass(frozen=True)
class Plan2D:
    """A factored 2D schedule: head scatter over ``u`` devices wrapping the
    ``inner`` ring-family plan over ``r`` devices (``inner.P == r``,
    ``inner.Tl == u · Tl_dev``).  ``Hq``/``Hkv`` are the *global* head
    counts — the head routing is static."""
    inner: SchedulePlan
    r: int
    u: int
    Hq: int
    Hkv: int
    kv_mode: str                   # "scatter" | "replicate"

    @property
    def name(self) -> str:
        return f"{self.inner.name}@r{self.r}u{self.u}"

    @property
    def P(self) -> int:
        return self.r * self.u

    def cost(self, **kw) -> "PlanCost":
        return plan2d_cost(self, **kw)


def plan2d_capable(schedule: str, mask: MaskSpec, *, r: int, u: int,
                   Hq: int, Hkv: int) -> bool:
    """Can the (schedule, r, u) factorization serve this mask × head
    shape?  Query heads must split evenly over the head sub-axis and the
    GQA group structure must be uniform; the inner schedule follows the 1D
    plan capability rules — except at r == 1, where the 'ring' degenerates
    to one local full-sequence kernel after the head scatter, which can
    express *any* mask kind (absolute positions exist), prefix_lm and
    non-causal windows included."""
    if schedule not in PLAN2D_SCHEDULES:
        return False
    if Hq % u or Hq % Hkv:
        return False
    if r == 1:
        return schedule == "ring"
    return plan_capable(schedule, mask)


def build_plan2d(schedule: str, mask: MaskSpec, r: int, u: int,
                 Tl_dev: int, *, Hq: int, Hkv: int) -> Plan2D:
    """Build the 2D plan for one factorization: the inner seq-axis plan at
    P = r over the post-scatter shard length u·Tl_dev, plus the static KV
    head-routing mode.  Pure python over static ints — trace time."""
    if not plan2d_capable(schedule, mask, r=r, u=u, Hq=Hq, Hkv=Hkv):
        raise ValueError(
            f"2D factorization (schedule={schedule!r}, r={r}, u={u}) "
            f"cannot serve mask {mask.kind!r} with heads ({Hq}, {Hkv}) — "
            f"query heads must divide u and the inner schedule must be "
            f"plan-capable for the mask (any mask goes at r == 1)")
    inner = build_plan(schedule, mask, r, u * Tl_dev)
    kv_mode = "scatter" if Hkv % u == 0 else "replicate"
    return Plan2D(inner=inner, r=r, u=u, Hq=Hq, Hkv=Hkv, kv_mode=kv_mode)


def plan2d_head_map(p2: Plan2D, j: int):
    """Static head routing of head-device ``j`` (python ints — the test
    simulator's view): ``(q_ids, kv_ids)`` global head indices of the
    local slots after the scatter.  In scatter mode the KV slots are the
    device's a2a share; in replicate mode they are the selection
    ``(global q head) // g`` — locally MHA (one KV slot per query slot)."""
    Hql = p2.Hq // p2.u
    q_ids = np.arange(j * Hql, (j + 1) * Hql)
    if p2.kv_mode == "scatter":
        Hkvl = p2.Hkv // p2.u
        kv_ids = np.arange(j * Hkvl, (j + 1) * Hkvl)
    else:
        kv_ids = (j * Hql + np.arange(Hql)) // (p2.Hq // p2.Hkv)
    return q_ids, kv_ids


def _a2a_heads(x, axis):
    """Scatter heads, gather sequence (forward direction of the head
    all-to-all): (B, Tc, H, …) → (B, u·Tc, H/u, …).  Peer-order concat
    over the head sub-axis reassembles a contiguous sequence row because
    the global sequence is sharded (seq major, head minor)."""
    return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def _a2a_seq(x, axis):
    """Inverse direction: split sequence, gather heads."""
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


def _scatter_heads(p2: Plan2D, q, k, v, seg, head_axis):
    """Head-scatter the per-device shards into the inner plan's layout.
    Returns (qh, kh, vh, segh, kv_ids); ``kv_ids`` is the traced global-KV
    selection (replicate mode only) the backward scatters gradients back
    through."""
    qh = _a2a_heads(q, head_axis)
    kv_ids = None
    if p2.kv_mode == "scatter":
        kh, vh = _a2a_heads(k, head_axis), _a2a_heads(v, head_axis)
    else:
        j = lax.axis_index(head_axis)
        Hql = p2.Hq // p2.u
        g = p2.Hq // p2.Hkv
        kv_ids = (j * Hql + jnp.arange(Hql)) // g
        kg = lax.all_gather(k, head_axis, axis=1, tiled=True)
        vg = lax.all_gather(v, head_axis, axis=1, tiled=True)
        kh = jnp.take(kg, kv_ids, axis=2)
        vh = jnp.take(vg, kv_ids, axis=2)
    segh = None if seg is None \
        else lax.all_gather(seg, head_axis, axis=1, tiled=True)
    return qh, kh, vh, segh, kv_ids


def execute2d_fwd(p2: Plan2D, q, k, v, seg=None, *, seq_axis, head_axis,
                  tune):
    """Run a 2D plan forward: head scatter over ``head_axis``, the inner
    SchedulePlan over ``seq_axis``, inverse scatter home.  Local
    (per-shard) code for shard_map over the (seq, head) axis pair; returns
    (o, lse) in the caller's (seq-major, head-minor) sharding."""
    qh, kh, vh, segh, _ = _scatter_heads(p2, q, k, v, seg, head_axis)
    o_h, s_h = execute_fwd(p2.inner, qh, kh, vh, segh, axis=seq_axis,
                           tune=tune)
    return _a2a_seq(o_h, head_axis), _a2a_seq(s_h, head_axis)


def execute2d_bwd(p2: Plan2D, q, k, v, o, lse, do, seg=None, *, seq_axis,
                  head_axis, tune):
    """Run a 2D plan backward from saved (o, lse): forward-direction
    scatters for the operands, the inner plan backward on the seq
    sub-axis, then gradients home — all-to-all for dq (and dk/dv in
    scatter mode); in replicate mode the selected-head KV gradients
    scatter-add into the full head dim, psum over the head sub-axis, and
    each device keeps its own token chunk."""
    qh, kh, vh, segh, kv_ids = _scatter_heads(p2, q, k, v, seg, head_axis)
    oh, doh = _a2a_heads(o, head_axis), _a2a_heads(do, head_axis)
    lseh = _a2a_heads(lse, head_axis)
    dqh, dkh, dvh = execute_bwd(p2.inner, qh, kh, vh, oh, lseh, doh, segh,
                                axis=seq_axis, tune=tune)
    dq = _a2a_seq(dqh, head_axis)
    if p2.kv_mode == "scatter":
        return dq, _a2a_seq(dkh, head_axis), _a2a_seq(dvh, head_axis)
    B, Tc = k.shape[0], k.shape[1]
    j = lax.axis_index(head_axis)

    def home(dx, x):
        full = jnp.zeros((B, Tc * p2.u, p2.Hkv) + x.shape[3:], jnp.float32)
        full = full.at[:, :, kv_ids].add(dx.astype(jnp.float32))
        full = lax.psum(full, head_axis)
        return lax.dynamic_slice_in_dim(full, j * Tc, Tc,
                                        axis=1).astype(x.dtype)

    return dq, home(dkh, k), home(dvh, v)


def plan2d_cost(p2: Plan2D, *, B: int = 1, Dqk: int = 64,
                Dv: Optional[int] = None, bpe: int = 2,
                dynamic_seg: bool = False) -> PlanCost:
    """Static per-device cost of a 2D plan: the inner plan's cost at the
    factored shapes (Hq/u heads over T/r tokens) plus the head-axis
    collective traffic (all-to-all factor (u−1)/u, all-gather factor u−1 —
    analysis/roofline constants)."""
    from repro.analysis.roofline import a2a_bytes, allgather_bytes
    Dv = Dqk if Dv is None else Dv
    u = p2.u
    Hql = p2.Hq // u
    Hkv_in = Hql if p2.kv_mode == "replicate" else p2.Hkv // u
    inner = plan_cost(p2.inner, B=B, Hq=Hql, Hkv=Hkv_in, Dqk=Dqk, Dv=Dv,
                      bpe=bpe, dynamic_seg=dynamic_seg)
    Tc = p2.inner.Tl // u                       # per-device tokens
    q_b = B * Tc * p2.Hq * Dqk * bpe
    o_b = B * Tc * p2.Hq * Dv * bpe
    lse_b = B * Tc * p2.Hq * 4
    kv_b = B * Tc * p2.Hkv * (Dqk + Dv) * bpe
    seg_b = B * Tc * 4 if dynamic_seg else 0.0
    if p2.kv_mode == "scatter":
        kv_in = a2a_bytes(kv_b, u)
        kv_grad_home = a2a_bytes(kv_b, u)
    else:
        kv_in = allgather_bytes(kv_b, u)
        # ring-allreduce of the full-row f32 KV grads over the head axis
        kv_grad_home = 2.0 * a2a_bytes(
            B * (Tc * u) * p2.Hkv * (Dqk + Dv) * 4, u)
    c_fwd = inner.comm_bytes_fwd + a2a_bytes(q_b + o_b + lse_b, u) \
        + kv_in + allgather_bytes(seg_b, u)
    c_bwd = inner.comm_bytes_bwd \
        + a2a_bytes(2 * q_b + 2 * o_b + lse_b, u) \
        + kv_in + kv_grad_home + allgather_bytes(seg_b, u)
    return PlanCost(schedule=p2.name, exec_steps=inner.exec_steps,
                    total_steps=inner.total_steps,
                    kernel_calls=inner.kernel_calls,
                    flops_fwd=inner.flops_fwd, flops_bwd=inner.flops_bwd,
                    comm_bytes_fwd=c_fwd, comm_bytes_bwd=c_bwd)


def factorizations(P: int):
    """All (r, u) with r·u == P — the 2D search space of
    ``choose_schedule(..., factorize=True)``."""
    return [(r, P // r) for r in range(1, P + 1) if P % r == 0]


def choose_inner_schedule(mask: MaskSpec, r: int, u: int, *, Tl_dev: int,
                          B: int = 1, Hq: int = 8,
                          Hkv: Optional[int] = None, Dqk: int = 64,
                          Dv: Optional[int] = None, bpe: int = 2,
                          dynamic_seg: bool = False,
                          include_bwd: bool = True) -> str:
    """``schedule="auto"`` for a FIXED (r, u) factorization (the mesh is
    already built, so only the inner seq-axis schedule is free): cheapest
    capable ring-family plan by the analytic 2D cost.  zigzag is excluded
    — its global-layout permutation stays a caller contract."""
    Hkv = Hq if Hkv is None else Hkv
    if r == 1:
        return "ring"
    scored = []
    for i, name in enumerate(("balanced", "ring")):
        if not plan2d_capable(name, mask, r=r, u=u, Hq=Hq, Hkv=Hkv):
            continue
        p2 = build_plan2d(name, mask, r, u, Tl_dev, Hq=Hq, Hkv=Hkv)
        t = plan2d_cost(p2, B=B, Dqk=Dqk, Dv=Dv, bpe=bpe,
                        dynamic_seg=dynamic_seg) \
            .time_estimate(include_bwd)["step_s_lower_bound"]
        scored.append((t, i, name))
    if not scored:
        raise ValueError(
            f"schedule='auto': no capable inner schedule for mask "
            f"{mask.kind!r} on a 2D (r={r}, u={u}) mesh with heads "
            f"({Hq}, {Hkv}) — prefix_lm and non-causal sliding windows "
            f"need r == 1 (head-only scatter) or a single-shard axis")
    return min(scored)[2]


def choose_schedule(mask: MaskSpec, P: int, *, Tl: int, B: int = 1,
                    Hq: int = 8, Hkv: Optional[int] = None, Dqk: int = 64,
                    Dv: Optional[int] = None, bpe: int = 2,
                    dynamic_seg: bool = False, include_bwd: bool = True,
                    factorize: bool = False):
    """``schedule="auto"``: pick the cheapest capable schedule for this
    (mask, P, shapes).  Candidates are the plan schedules (zigzag
    excluded — it requires the caller to pre-permute the global layout,
    so it stays an explicit opt-in) plus the ulysses baseline when the
    head counts divide P.

    Ranking consults the active tuning table (repro.tune) first: a
    measured row at the nearest (mask kind, P, seq) bucket decides
    outright; otherwise the table's calibrated cost-model coefficients
    rank the candidates; only with no table at all does the uncalibrated
    analytic roofline decide.  Deterministic: ties break toward
    balanced > ring > ulysses.

    ``include_bwd`` is both the cost-ranking horizon *and* a capability
    constraint: with it set, candidates that would raise in the
    distributed backward (ulysses under prefix_lm / non-causal windows —
    the baselines reuse the ring backward) are filtered out here, at
    trace time, so the resolved name never raises at execution time.

    ``factorize=True`` widens the search to the 2D (seq=r, head=u)
    factorization space and returns a ``(name, r, u)`` triple instead of
    a name — ranked purely by the analytic cost model (the tuning table's
    measured rows are 1D walls and would be incommensurable)."""
    Hkv = Hq if Hkv is None else Hkv
    if factorize:
        return _choose_factorized(mask, P, Tl=Tl, B=B, Hq=Hq, Hkv=Hkv,
                                  Dqk=Dqk, Dv=Dv, bpe=bpe,
                                  dynamic_seg=dynamic_seg,
                                  include_bwd=include_bwd)
    if P <= 1:
        return "ring"
    names = [n for n in ("balanced", "ring") if plan_capable(n, mask)]
    if ulysses_capable(mask, P, Hq, Hkv, include_bwd=include_bwd):
        names.append("ulysses")
    if not names:
        raise ValueError(
            f"schedule='auto': no capable schedule for mask {mask.kind!r} "
            f"with P={P}, heads=({Hq}, {Hkv}) — prefix_lm and non-causal "
            f"sliding windows need absolute positions (ulysses, which "
            f"needs head counts divisible by P) or a single-shard axis")
    if len(names) == 1:
        return names[0]

    from repro.tune.table import active_table
    tab = active_table()
    if tab is not None:
        hit = tab.best_schedule(mask_kind=mask.kind, P=P, seq=P * Tl,
                                candidates=names)
        if hit is not None:
            return hit
    coeffs = tab.coeffs() if tab is not None else None

    scored = []
    order = {"balanced": 0, "ring": 1, "ulysses": 2}
    for name in names:
        if coeffs is not None:
            from repro.tune.calibrate import (predict_s,
                                              schedule_features)
            feats = schedule_features(
                name, mask_kind=mask.kind, P=P, seq=P * Tl, B=B, Hq=Hq,
                Hkv=Hkv, Dqk=Dqk, bpe=bpe, window=mask.window or None,
                dynamic_seg=dynamic_seg, include_bwd=include_bwd)
            if feats is None:
                continue
            t = predict_s(feats, coeffs)
        elif name == "ulysses":
            cost = ulysses_cost(mask, P, Tl=Tl, B=B, Hq=Hq, Hkv=Hkv,
                                Dqk=Dqk, Dv=Dv, bpe=bpe)
            t = cost.time_estimate(include_bwd)["step_s_lower_bound"]
        else:
            cost = plan_cost(build_plan(name, mask, P, Tl), B=B, Hq=Hq,
                             Hkv=Hkv, Dqk=Dqk, Dv=Dv, bpe=bpe,
                             dynamic_seg=dynamic_seg)
            t = cost.time_estimate(include_bwd)["step_s_lower_bound"]
        scored.append((t, order[name], name))
    return min(scored)[2]


def _choose_factorized(mask: MaskSpec, P: int, *, Tl: int, B: int,
                       Hq: int, Hkv: int, Dqk: int, Dv: Optional[int],
                       bpe: int, dynamic_seg: bool, include_bwd: bool):
    """The 2D branch of ``choose_schedule``: rank every capable
    (schedule, r, u) with r·u == P by the analytic cost model and return
    the cheapest triple.  (r = P, u = 1) entries are today's 1D plans;
    (r = 1, u = P) is pure head parallelism through the plan path — the
    ulysses-equivalent, GQA-capable via KV replication, and backward-
    capable for *any* mask kind because the post-scatter kernel sees the
    whole sequence.  zigzag is excluded (caller-permutation contract);
    ties break toward smaller u (fewer head-axis collectives), then
    balanced > ring."""
    if P <= 1:
        return ("ring", 1, 1)
    order = {"balanced": 0, "ring": 1}
    scored = []
    for r, u in factorizations(P):
        for name in ("balanced", "ring"):
            if u == 1:
                if not plan_capable(name, mask):
                    continue
                cost = plan_cost(build_plan(name, mask, P, Tl), B=B,
                                 Hq=Hq, Hkv=Hkv, Dqk=Dqk, Dv=Dv, bpe=bpe,
                                 dynamic_seg=dynamic_seg)
            else:
                if name == "balanced" and r == 1:
                    continue          # degenerate — identical to ring
                if not plan2d_capable(name, mask, r=r, u=u, Hq=Hq,
                                      Hkv=Hkv):
                    continue
                p2 = build_plan2d(name, mask, r, u, Tl, Hq=Hq, Hkv=Hkv)
                cost = plan2d_cost(p2, B=B, Dqk=Dqk, Dv=Dv, bpe=bpe,
                                   dynamic_seg=dynamic_seg)
            t = cost.time_estimate(include_bwd)["step_s_lower_bound"]
            scored.append((t, u, order[name], (name, r, u)))
    if not scored:
        raise ValueError(
            f"schedule='auto': no capable (schedule, r, u) factorization "
            f"of P={P} for mask {mask.kind!r} with heads ({Hq}, {Hkv}) — "
            f"head-parallel factorizations need Hq % u == 0 and a uniform "
            f"GQA group structure")
    return min(scored)[3]
