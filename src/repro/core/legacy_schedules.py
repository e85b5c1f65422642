"""Frozen seed implementations of the hand-written DISTFLASHATTN
schedules (pre-SchedulePlan-IR), kept verbatim SOLELY as differential-test
references for the plan executors (tests/test_schedule_plan.py).

Not used by the library: core/dist_attention.py now builds SchedulePlans
(core/schedule.py) and runs them through the shared step engine.  Do not
extend these — new schedule capabilities go into the plan builders.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import mask as mk
from repro.core.attention import chunk_attn, chunk_attn_bwd, mask_partial, merge


def _tune(spec):
    return dict(scale=spec.scale, impl=spec.impl)


def _seg_kw(mask, q_seg, kv_seg):
    if not mask.document or q_seg is None:
        return {}
    return dict(q_segments=q_seg, kv_segments=kv_seg)


def _shift(x, axis, shift, size):
    """ppermute by a fixed shift: device p receives from (p − shift) mod P."""
    perm = [(i, (i + shift) % size) for i in range(size)]
    return jax.tree.map(lambda a: lax.ppermute(a, axis, perm), x)

def _ring_steps(spec: DistAttnSpec, chunk_len: int) -> int:
    """Number of ring steps; truncated by the sliding window (Appendix F)."""
    P_ = spec.axis_size
    n = P_ - 1
    w = spec.mask.window
    if w and w > 0:
        # step t covers query-key distances [(t-1)*Tc+1, (t+1)*Tc-1];
        # it contributes only if the smallest distance is inside the window.
        n = min(n, max(0, -(-(w - 1) // chunk_len)))
    return n

def _fwd_ring(spec, q, k, v, seg=None):
    """Vanilla ring (Alg. 1) — causal, bidirectional, windowed, document."""
    p = lax.axis_index(spec.axis)
    P_, Tc = spec.axis_size, q.shape[1]
    m = spec.mask
    o, s = chunk_attn(q, k, v, mask=m, **_seg_kw(m, seg, seg), **_tune(spec))
    n = _ring_steps(spec, Tc)
    if n == 0:
        return o, s
    kv = _shift((k, v), spec.axis, 1, P_)            # prefetch step 1
    seg_r = _shift(seg, spec.axis, 1, P_) if seg is not None else None
    for t in range(1, n + 1):
        if t < n:                                     # prefetch (overlap)
            kv_next = _shift(kv, spec.axis, 1, P_)
            seg_next = _shift(seg_r, spec.axis, 1, P_) \
                if seg_r is not None else None
        m_t = mk.ring_step(m, t * Tc)
        o_t, s_t = chunk_attn(q, kv[0], kv[1], mask=m_t,
                              **_seg_kw(m_t, seg, seg_r), **_tune(spec))
        if m.causal:
            o_t, s_t = mask_partial(p >= t, o_t, s_t)
        o, s = merge(o, s, o_t, s_t)
        if t < n:
            kv, seg_r = kv_next, seg_next
    return o, s

def _fwd_balanced(spec, q, k, v, seg=None):
    """Load-balanced schedule (Alg. 2). Causal-kind masks, full window."""
    p = lax.axis_index(spec.axis)
    P_, Tc = spec.axis_size, q.shape[1]
    m = spec.mask
    m_x = mk.strict_causal_pair(m)     # off-diagonal pairs: document only
    o, s = chunk_attn(q, k, v, mask=m, **_seg_kw(m, seg, seg), **_tune(spec))
    if P_ == 1:
        return o, s
    T = P_ // 2
    kv = _shift((k, v), spec.axis, 1, P_)            # prefetch step 1
    qb = _shift(q, spec.axis, 1, P_)
    # one traveling segment chunk serves both sides: the helper's q chunk
    # and the worker's kv chunk are the same remote device's tokens
    seg_r = _shift(seg, spec.axis, 1, P_) if seg is not None else None
    for t in range(1, T + 1):
        helpers = (t != T) or (P_ % 2 == 1)
        if t < T:                                     # prefetch step t+1
            kv_next = _shift(kv, spec.axis, 1, P_)
            qb_next = _shift(qb, spec.axis, 1, P_)
            seg_next = _shift(seg_r, spec.axis, 1, P_) \
                if seg_r is not None else None
        is_worker = p >= t
        # one attn kernel per device per step: workers use (q_p, kv_{p−t}),
        # helpers use (q_{(p−t) mod P}, kv_p). No positional mask — strictly
        # causal pairs; document segments still apply.
        q_sel = jnp.where(is_worker, q, qb)
        k_sel = jnp.where(is_worker, kv[0], k)
        v_sel = jnp.where(is_worker, kv[1], v)
        skw = {}
        if seg_r is not None and m.document:
            skw = dict(q_segments=jnp.where(is_worker, seg, seg_r),
                       kv_segments=jnp.where(is_worker, seg_r, seg))
        o_t, s_t = chunk_attn(q_sel, k_sel, v_sel, mask=m_x, **skw,
                              **_tune(spec))
        o_w, s_w = mask_partial(is_worker, o_t, s_t)
        o, s = merge(o, s, o_w, s_w)
        if helpers:
            # helper h computed for worker w=(h−t) mod P: route (o,lse) back
            o_r, s_r = _shift((o_t, s_t), spec.axis, -t, P_)
            o_r, s_r = mask_partial(p >= P_ - t, o_r, s_r)
            o, s = merge(o, s, o_r, s_r)
        if t < T:
            kv, qb = kv_next, qb_next
            seg_r = seg_next if seg_r is not None else None
    return o, s

def _bwd_ring(spec, q, k, v, o, s, do, seg=None):
    p = lax.axis_index(spec.axis)
    P_, Tc = spec.axis_size, q.shape[1]
    m = spec.mask
    f32 = jnp.float32
    delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)  # (B,T,H)
    dq_l, dk_l, dv_l = chunk_attn_bwd(
        q, k, v, o, s, do, mask=m, **_seg_kw(m, seg, seg), **_tune(spec))
    dq = dq_l.astype(f32)
    dkv_home = (dk_l.astype(f32), dv_l.astype(f32))
    n = _ring_steps(spec, Tc)
    if n == 0:
        return dq.astype(q.dtype), dkv_home[0].astype(k.dtype), \
            dkv_home[1].astype(v.dtype)
    # containers: (k, v) data + (dk, dv) accumulators travel together
    kv = _shift((k, v), spec.axis, 1, P_)
    seg_r = _shift(seg, spec.axis, 1, P_) if seg is not None else None
    dkv = jax.tree.map(lambda a: jnp.zeros(a.shape, f32), kv)
    for t in range(1, n + 1):
        if t < n:                                     # prefetch data (overlap)
            kv_nxt = _shift(kv, spec.axis, 1, P_)
            seg_nxt = _shift(seg_r, spec.axis, 1, P_) \
                if seg_r is not None else None
        m_t = mk.ring_step(m, t * Tc)
        dq_t, dk_t, dv_t = chunk_attn_bwd(
            q, kv[0], kv[1], o, s, do, mask=m_t,
            **_seg_kw(m_t, seg, seg_r), **_tune(spec), delta=delta)
        valid = (p >= t) if m.causal else jnp.bool_(True)
        w = valid.astype(f32)
        dq = dq + dq_t.astype(f32) * w
        dkv = (dkv[0] + dk_t.astype(f32) * w, dkv[1] + dv_t.astype(f32) * w)
        if t < n:                                     # accumulators move late
            kv, seg_r = kv_nxt, (seg_nxt if seg_r is not None else None)
            dkv = _shift(dkv, spec.axis, 1, P_)
    # route accumulated dkv home: container at p holds chunk (p−n) mod P
    dkv = _shift(dkv, spec.axis, -n, P_)
    dk = dkv_home[0] + dkv[0]
    dv = dkv_home[1] + dkv[1]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

def _bwd_balanced(spec, q, k, v, o, s, do, seg=None):
    p = lax.axis_index(spec.axis)
    P_, Tc = spec.axis_size, q.shape[1]
    m = spec.mask
    m_x = mk.strict_causal_pair(m)
    f32 = jnp.float32
    dq_l, dk_l, dv_l = chunk_attn_bwd(q, k, v, o, s, do, mask=m,
                                      **_seg_kw(m, seg, seg), **_tune(spec))
    dq = dq_l.astype(f32)
    dk_home = dk_l.astype(f32)
    dv_home = dv_l.astype(f32)
    if P_ == 1:
        return dq.astype(q.dtype), dk_home.astype(k.dtype), \
            dv_home.astype(v.dtype)
    T = P_ // 2
    delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)
    # traveling containers (ring +1): kv side and q-bundle side
    kv = _shift((k, v), spec.axis, 1, P_)
    dkv = (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32))
    qb = _shift((q, do, s, delta), spec.axis, 1, P_)
    seg_r = _shift(seg, spec.axis, 1, P_) if seg is not None else None
    dqb = jnp.zeros(q.shape, f32)
    for t in range(1, T + 1):
        helpers = (t != T) or (P_ % 2 == 1)
        if t < T:                                     # prefetch data (overlap)
            kv_nxt = _shift(kv, spec.axis, 1, P_)
            qb_nxt = _shift(qb, spec.axis, 1, P_)
            seg_nxt = _shift(seg_r, spec.axis, 1, P_) \
                if seg_r is not None else None
        is_worker = p >= t
        q_sel = jnp.where(is_worker, q, qb[0])
        do_sel = jnp.where(is_worker, do, qb[1])
        s_sel = jnp.where(is_worker, s, qb[2])
        k_sel = jnp.where(is_worker, kv[0], k)
        v_sel = jnp.where(is_worker, kv[1], v)
        o_unused = jnp.zeros_like(q_sel)  # delta passed explicitly
        d_sel = jnp.where(is_worker, delta, qb[3])
        skw = {}
        if seg_r is not None and m.document:
            skw = dict(q_segments=jnp.where(is_worker, seg, seg_r),
                       kv_segments=jnp.where(is_worker, seg_r, seg))
        dq_t, dk_t, dv_t = chunk_attn_bwd(
            q_sel, k_sel, v_sel, o_unused, s_sel, do_sel, mask=m_x, **skw,
            **_tune(spec), delta=d_sel)
        w_w = is_worker.astype(f32)
        dq = dq + dq_t.astype(f32) * w_w                 # worker: local dq
        dkv = (dkv[0] + dk_t.astype(f32) * w_w,          # worker: traveling dkv
               dkv[1] + dv_t.astype(f32) * w_w)
        if helpers:
            w_h = (p < t).astype(f32)
            dqb = dqb + dq_t.astype(f32) * w_h           # helper: traveling dq
            dk_home = dk_home + dk_t.astype(f32) * w_h   # helper: local dkv
            dv_home = dv_home + dv_t.astype(f32) * w_h
        if t < T:                                     # accumulators move late
            kv, qb = kv_nxt, qb_nxt
            seg_r = seg_nxt if seg_r is not None else None
            dkv = _shift(dkv, spec.axis, 1, P_)
            dqb = _shift(dqb, spec.axis, 1, P_)
    # route containers home (container at p holds chunk (p−T) mod P)
    dkv = _shift(dkv, spec.axis, -T, P_)
    dqb = _shift(dqb, spec.axis, -T, P_)
    dq = dq + dqb
    dk = dk_home + dkv[0]
    dv = dv_home + dkv[1]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

def _fwd_zigzag(spec, q, k, v, seg=None):
    p = lax.axis_index(spec.axis)
    P_ = spec.axis_size
    Tl = q.shape[1]
    c = Tl // 2
    m = spec.mask
    m_x = mk.strict_causal_pair(m)
    doc = seg is not None and m.document

    def sk(qs, ks):
        return dict(q_segments=qs, kv_segments=ks) if doc else {}

    q_a, q_b = q[:, :c], q[:, c:]
    k_a, k_b = k[:, :c], k[:, c:]
    v_a, v_b = v[:, :c], v[:, c:]
    s_a_, s_b_ = (seg[:, :c], seg[:, c:]) if seg is not None else (None, None)
    # local step: a×a causal; b̄×a full; b̄×b̄ causal
    o_a, s_a = chunk_attn(q_a, k_a, v_a, mask=m, **sk(s_a_, s_a_),
                          **_tune(spec))
    o_b1, s_b1 = chunk_attn(q_b, k_a, v_a, mask=m_x, **sk(s_b_, s_a_),
                            **_tune(spec))
    o_b2, s_b2 = chunk_attn(q_b, k_b, v_b, mask=m, **sk(s_b_, s_b_),
                            **_tune(spec))
    o_b, s_b = merge(o_b1, s_b1, o_b2, s_b2)
    if P_ == 1:
        return jnp.concatenate([o_a, o_b], 1), jnp.concatenate([s_a, s_b], 1)
    kv = _shift((k, v), spec.axis, 1, P_)
    seg_r = _shift(seg, spec.axis, 1, P_) if seg is not None else None
    for t in range(1, P_):
        if t < P_ - 1:
            kv_next = _shift(kv, spec.axis, 1, P_)
            seg_next = _shift(seg_r, spec.axis, 1, P_) \
                if seg_r is not None else None
        ka_r, kb_r = kv[0][:, :c], kv[0][:, c:]
        va_r, vb_r = kv[1][:, :c], kv[1][:, c:]
        sa_r, sb_r = (seg_r[:, :c], seg_r[:, c:]) if seg_r is not None \
            else (None, None)
        w = p >= t
        # pair 1 -> (q_a if worker else q_b) × kv_a
        q1 = jnp.where(w, q_a, q_b)
        s1q = jnp.where(w, s_a_, s_b_) if doc else None
        o1, s1 = chunk_attn(q1, ka_r, va_r, mask=m_x, **sk(s1q, sa_r),
                            **_tune(spec))
        o1a, s1a = mask_partial(w, o1, s1)
        o_a, s_a = merge(o_a, s_a, o1a, s1a)
        o1b, s1b = mask_partial(~w, o1, s1)
        o_b, s_b = merge(o_b, s_b, o1b, s1b)
        # pair 2 -> q_b × (kv_a if worker else kv_b̄)
        k2 = jnp.where(w, ka_r, kb_r)
        v2 = jnp.where(w, va_r, vb_r)
        s2k = jnp.where(w, sa_r, sb_r) if doc else None
        o2, s2 = chunk_attn(q_b, k2, v2, mask=m_x, **sk(s_b_, s2k),
                            **_tune(spec))
        o_b, s_b = merge(o_b, s_b, o2, s2)
        if t < P_ - 1:
            kv, seg_r = kv_next, (seg_next if seg_r is not None else None)
    return jnp.concatenate([o_a, o_b], 1), jnp.concatenate([s_a, s_b], 1)

def _bwd_zigzag(spec, q, k, v, o, s, do, seg=None):
    p = lax.axis_index(spec.axis)
    P_ = spec.axis_size
    f32 = jnp.float32
    Tl = q.shape[1]
    c = Tl // 2
    sl_a, sl_b = slice(0, c), slice(c, None)
    m = spec.mask
    m_x = mk.strict_causal_pair(m)
    doc = seg is not None and m.document
    delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)

    def cb(qs, ks, vs, ss, dos, ds, mask, qseg=None, kseg=None):
        skw = dict(q_segments=qseg, kv_segments=kseg) if doc else {}
        return chunk_attn_bwd(qs, ks, vs, jnp.zeros_like(qs), ss, dos,
                              mask=mask, **skw, **_tune(spec), delta=ds)

    # local pairs
    dq = jnp.zeros(q.shape, f32)
    dk_h = jnp.zeros(k.shape, f32)
    dv_h = jnp.zeros(v.shape, f32)
    for (qs, ks, mask) in ((sl_a, sl_a, m), (sl_b, sl_a, m_x),
                           (sl_b, sl_b, m)):
        dq_t, dk_t, dv_t = cb(q[:, qs], k[:, ks], v[:, ks], s[:, qs],
                              do[:, qs], delta[:, qs], mask,
                              seg[:, qs] if doc else None,
                              seg[:, ks] if doc else None)
        dq = dq.at[:, qs].add(dq_t.astype(f32))
        dk_h = dk_h.at[:, ks].add(dk_t.astype(f32))
        dv_h = dv_h.at[:, ks].add(dv_t.astype(f32))
    if P_ == 1:
        return dq.astype(q.dtype), dk_h.astype(k.dtype), dv_h.astype(v.dtype)

    q_a, q_b = q[:, sl_a], q[:, sl_b]
    s_a, s_b = s[:, sl_a], s[:, sl_b]
    do_a, do_b = do[:, sl_a], do[:, sl_b]
    de_a, de_b = delta[:, sl_a], delta[:, sl_b]
    sg_a, sg_b = (seg[:, sl_a], seg[:, sl_b]) if doc else (None, None)
    kv = _shift((k, v), spec.axis, 1, P_)
    seg_r = _shift(seg, spec.axis, 1, P_) if seg is not None else None
    dkv = (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32))
    for t in range(1, P_):
        if t < P_ - 1:
            kv_nxt = _shift(kv, spec.axis, 1, P_)
            seg_nxt = _shift(seg_r, spec.axis, 1, P_) \
                if seg_r is not None else None
        ka_r, kb_r = kv[0][:, :c], kv[0][:, c:]
        va_r, vb_r = kv[1][:, :c], kv[1][:, c:]
        sa_r, sb_r = (seg_r[:, :c], seg_r[:, c:]) if seg_r is not None \
            else (None, None)
        w = p >= t
        wf = w.astype(f32)
        # pair 1
        q1 = jnp.where(w, q_a, q_b)
        s1 = jnp.where(w, s_a, s_b)
        do1 = jnp.where(w, do_a, do_b)
        de1 = jnp.where(w, de_a, de_b)
        sg1 = jnp.where(w, sg_a, sg_b) if doc else None
        dq1, dk1, dv1 = cb(q1, ka_r, va_r, s1, do1, de1, m_x, sg1, sa_r)
        dq = dq.at[:, sl_a].add(dq1.astype(f32) * wf)
        dq = dq.at[:, sl_b].add(dq1.astype(f32) * (1 - wf))
        dkv = (dkv[0].at[:, sl_a].add(dk1.astype(f32)),
               dkv[1].at[:, sl_a].add(dv1.astype(f32)))
        # pair 2
        k2 = jnp.where(w, ka_r, kb_r)
        v2 = jnp.where(w, va_r, vb_r)
        sg2 = jnp.where(w, sa_r, sb_r) if doc else None
        dq2, dk2, dv2 = cb(q_b, k2, v2, s_b, do_b, de_b, m_x, sg_b, sg2)
        dq = dq.at[:, sl_b].add(dq2.astype(f32))
        dkv = (dkv[0].at[:, sl_a].add(dk2.astype(f32) * wf),
               dkv[1].at[:, sl_a].add(dv2.astype(f32) * wf))
        dkv = (dkv[0].at[:, sl_b].add(dk2.astype(f32) * (1 - wf)),
               dkv[1].at[:, sl_b].add(dv2.astype(f32) * (1 - wf)))
        if t < P_ - 1:
            kv, seg_r = kv_nxt, (seg_nxt if seg_r is not None else None)
            dkv = _shift(dkv, spec.axis, 1, P_)
    # containers at p hold chunk of (p − (P−1)) mod P = (p+1) mod P
    dkv = _shift(dkv, spec.axis, -(P_ - 1), P_)
    dk = dk_h + dkv[0]
    dv = dv_h + dkv[1]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
