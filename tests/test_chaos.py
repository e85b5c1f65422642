"""Chaos property suite: the serving engine under deterministic fault
injection (serve/faults.py).

Property tests (hypothesis-driven; the conftest shim supplies seeded
example generation when the real package is absent) assert the three
acceptance properties under seeded fault storms:

  1. **definite termination** — every submitted request reaches exactly one
     terminal state (finished / rejected / expired / failed) with a
     structured ``finish_reason``;
  2. **no block leaked or double-freed** — allocator conservation holds at
     exit (and, with ``audit=True``, after *every* step), and every
     non-cache block is back on the free list once the engine drains;
  3. **fault-isolation / batch invariance** — a chaos run's token streams
     agree with the zero-fault run of the same trace on their common
     prefix, and requests that finish under chaos finish with the
     *identical* stream: faults perturb scheduling, never a surviving
     request's numerics.

Engineered-scenario tests then pin down each fault kind's contract: NaN
quarantine hits exactly the poisoned row, corrupted blocks are scrubbed
before re-entering the free list, dropped steps retry with capped backoff
and exhaust into FAILED, preemption storms trip the forward-progress
watchdog into serial admission, squeezes never break conservation, and
the auditor raises structured :class:`AuditFailure`\\ s for seeded
corruption of the bookkeeping itself.
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.config import ShapeSpec, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine
from repro.serve.faults import (FAULT_OWNER, KINDS, AuditFailure, FaultEvent,
                                FaultInjector)
from repro.serve.scheduler import TERMINAL_STATES
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def served():
    """One smoke model for the whole module (build+init dominates)."""
    cfg = smoke_config(get_config("smollm-360m"))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("chaos", 24, 4, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
    return model, params, prompts


def _engine(model, params, *, faults=None, n_blocks=28, max_batch=3,
            chunk=8, audit=True, **kw):
    return Engine(model, params, max_batch=max_batch, block_size=8,
                  n_blocks=n_blocks, prefill_chunk_tokens=chunk,
                  audit=audit, faults=faults, **kw)


def _trace(eng, prompts, trace_seed, *, n_reqs=5):
    """Submit a deterministic mixed trace: varied prompt lengths, token
    budgets, temperatures, and sprinkled deadlines. Returns rids."""
    rng = np.random.default_rng(trace_seed)
    rids = []
    for i in range(n_reqs):
        p = prompts[i % len(prompts)]
        plen = int(rng.integers(3, len(p)))
        deadline = int(rng.integers(25, 120)) if rng.random() < 0.3 else None
        rids.append(eng.submit(
            p[:plen], max_new_tokens=int(rng.integers(3, 8)),
            temperature=float(rng.choice([0.0, 0.8])), seed=i,
            deadline_steps=deadline))
    return rids


def _storm_run(model, params, prompts, fault_seed, trace_seed, *,
               faulty=True):
    inj = FaultInjector.seeded(fault_seed, n_steps=20,
                               rate=0.5) if faulty else None
    eng = _engine(model, params, faults=inj, max_retries=4,
                  watchdog_window=4, watchdog_threshold=3)
    rids = _trace(eng, prompts, trace_seed)
    out = eng.run(max_steps=3000)
    return eng, rids, out


def _assert_clean_exit(eng):
    """No block leaked or double-freed, nothing left running."""
    eng.cache.allocator.check_conservation()
    assert eng.sched.idle
    a = eng.cache.allocator
    assert a.n_free + eng.cache.n_cache_blocks == a.n_usable, \
        "blocks still held after drain (leak)"


# ==========================================================================
# acceptance properties under seeded storms
# ==========================================================================

@settings(max_examples=6, deadline=None)
@given(fault_seed=st.integers(0, 10_000), trace_seed=st.integers(0, 10_000))
def test_storm_every_request_terminates_and_pool_conserves(
        served, fault_seed, trace_seed):
    """Properties 1+2: any seeded fault schedule → definite terminal
    status for every request, conservation at exit (audit=True also
    checks it after every single step)."""
    model, params, prompts = served
    eng, rids, out = _storm_run(model, params, prompts, fault_seed,
                                trace_seed)
    for rid in rids:
        req = eng.requests[rid]
        assert req.state in TERMINAL_STATES, \
            f"rid {rid} ended in non-terminal state {req.state!r}"
        assert req.finish_reason is not None
    _assert_clean_exit(eng)


@settings(max_examples=4, deadline=None)
@given(fault_seed=st.integers(0, 10_000), trace_seed=st.integers(0, 10_000))
def test_storm_streams_match_zero_fault_run(served, fault_seed, trace_seed):
    """Property 3: chaos streams agree with the zero-fault run of the same
    trace on their common prefix, and chaos-FINISHED requests are
    token-identical — faults never touch a surviving request's numerics."""
    model, params, prompts = served
    chaos, rids, out_c = _storm_run(model, params, prompts, fault_seed,
                                    trace_seed)
    calm, rids2, out_0 = _storm_run(model, params, prompts, fault_seed,
                                    trace_seed, faulty=False)
    assert rids == rids2                       # same trace, same rids
    for rid in rids:
        m = min(out_c[rid].size, out_0[rid].size)
        np.testing.assert_array_equal(out_c[rid][:m], out_0[rid][:m])
        if chaos.requests[rid].state == "finished":
            assert calm.requests[rid].state == "finished"
            np.testing.assert_array_equal(out_c[rid], out_0[rid])


@settings(max_examples=3, deadline=None)
@given(fault_seed=st.integers(0, 10_000), trace_seed=st.integers(0, 10_000))
def test_storm_replays_byte_for_byte(served, fault_seed, trace_seed):
    """Same seed → same storm: the injector fire log, every terminal
    (state, reason), every emitted stream, and the counters replay
    exactly."""
    model, params, prompts = served
    a, rids_a, out_a = _storm_run(model, params, prompts, fault_seed,
                                  trace_seed)
    b, rids_b, out_b = _storm_run(model, params, prompts, fault_seed,
                                  trace_seed)
    assert a.injector.log == b.injector.log
    assert a.injector.counts == b.injector.counts
    for rid in rids_a:
        ra, rb = a.requests[rid], b.requests[rid]
        assert (ra.state, ra.finish_reason) == (rb.state, rb.finish_reason)
        np.testing.assert_array_equal(out_a[rid], out_b[rid])
    sa, sb = a.stats(), b.stats()
    assert sa == sb


# ==========================================================================
# engineered scenarios: one fault kind at a time
# ==========================================================================

def _solo(model, params, prompt, n, *, seed=0):
    eng = _engine(model, params, n_blocks=40, chunk=0, audit=False)
    rid = eng.submit(prompt, max_new_tokens=n, seed=seed)
    return eng.run()[rid]


def test_nan_quarantine_hits_only_the_poisoned_row(served):
    """A NaN-logit fault on one decode row fails exactly that request
    (reason nan_logits, clean partial stream kept); its batchmate streams
    on token-identical to its solo run; blocks are freed, refcounts
    intact."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=4, kind="nan_logits", target=0)])
    eng = _engine(model, params, faults=inj, chunk=0)
    r0 = eng.submit(prompts[0][:10], max_new_tokens=8, seed=0)
    r1 = eng.submit(prompts[1][:10], max_new_tokens=8, seed=1)
    out = eng.run()
    states = sorted(eng.requests[r].state for r in (r0, r1))
    assert states == ["failed", "finished"]
    failed = r0 if eng.requests[r0].state == "failed" else r1
    ok = r1 if failed == r0 else r0
    assert eng.requests[failed].finish_reason == "nan_logits"
    assert eng.stats()["quarantined"] == 1
    # the poisoned sample was discarded: the kept partial stream is a
    # clean prefix of the victim's solo stream
    solo_f = _solo(model, params, eng.requests[failed].prompt, 8,
                   seed=0 if failed == r0 else 1)
    np.testing.assert_array_equal(out[failed],
                                  solo_f[:out[failed].size])
    assert out[failed].size < 8
    # the survivor is untouched
    solo_ok = _solo(model, params, eng.requests[ok].prompt, 8,
                    seed=0 if ok == r0 else 1)
    np.testing.assert_array_equal(out[ok], solo_ok)
    _assert_clean_exit(eng)


def test_corrupt_block_poisons_exactly_one_request_and_is_scrubbed(served):
    """A corrupted pool block surfaces as NaN logits in the owning request
    → quarantined; the block is zero-scrubbed before returning to the free
    list (no NaN survives for the next tenant)."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=5, kind="corrupt_block",
                                    target=0)])
    eng = _engine(model, params, faults=inj, chunk=0)
    rid = eng.submit(prompts[0][:12], max_new_tokens=10)
    out = eng.run()
    req = eng.requests[rid]
    assert req.state == "failed" and req.finish_reason == "nan_logits"
    fired = [d for s, k, d in inj.log if k == "corrupt_block"]
    assert fired and fired[0].startswith(f"rid={rid} block=")
    block = int(fired[0].split("block=")[1])
    for pk, pool in eng.cache.pools.items():
        assert np.isfinite(np.asarray(pool[:, block])).all(), \
            f"NaN survived the scrub in {pk}"
    _assert_clean_exit(eng)


def test_drop_step_retries_without_perturbing_the_stream(served):
    """A transient dropped decode step advances nobody; the engine backs
    off and retries, and the final stream is token-identical to the
    fault-free stream (nothing lost, nothing re-sampled)."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=3, kind="drop_step"),
                         FaultEvent(step=6, kind="drop_step")])
    eng = _engine(model, params, faults=inj, chunk=0)
    rid = eng.submit(prompts[0][:10], max_new_tokens=8)
    out = eng.run()
    assert eng.requests[rid].state == "finished"
    np.testing.assert_array_equal(
        out[rid], _solo(model, params, prompts[0][:10], 8))
    s = eng.stats()
    assert s["retried"] >= 2 and s["faults"]["drop_step"] == 2
    # the first drop opens a backoff window past the fault itself: at
    # least one later (fault-free) step was skipped waiting it out
    assert s["backoff_steps"] > 0
    _assert_clean_exit(eng)


def test_consecutive_drops_exhaust_retries_into_failed(served):
    """Endless transient faults must not spin forever: after max_retries
    dropped attempts a request terminates FAILED(retries_exhausted)."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=s, kind="drop_step")
                         for s in range(40)])
    eng = _engine(model, params, faults=inj, chunk=0, max_retries=3)
    rid = eng.submit(prompts[0][:8], max_new_tokens=6)
    eng.run()
    req = eng.requests[rid]
    assert req.state == "failed"
    assert req.finish_reason == "retries_exhausted"
    assert req.retries > 3
    # it failed long before the 40-step storm ended: bounded, not a spin
    assert eng.stats()["steps"] < 20
    _assert_clean_exit(eng)


def test_preempt_storm_trips_watchdog_into_serial_admission(served):
    """Livelock pressure: a storm preempting every step with no tokens
    emitted trips the forward-progress watchdog (serial admission); once
    the storm passes, the request completes with an unperturbed stream."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=s, kind="preempt_storm",
                                    magnitude=2) for s in range(10)])
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=28,
                 prefill_chunk_tokens=4, prefix_cache=False, audit=True,
                 faults=inj, watchdog_window=3, watchdog_threshold=2)
    rid = eng.submit(prompts[0][:20], max_new_tokens=5)
    out = eng.run()
    s = eng.stats()
    assert s["watchdog_trips"] >= 1
    assert s["storm_preempts"] > 0
    assert eng.requests[rid].state == "finished"
    np.testing.assert_array_equal(
        out[rid], _solo(model, params, prompts[0][:20], 5))
    _assert_clean_exit(eng)


def test_squeeze_holds_conservation_and_releases(served):
    """Pool squeezes park blocks under FAULT_OWNER — conservation holds
    mid-squeeze (audited every step) and every squeezed block is back on
    the free list once the engine drains."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=1, kind="squeeze", magnitude=12,
                                    duration=6),
                         FaultEvent(step=3, kind="squeeze", magnitude=8,
                                    duration=2)])
    eng = _engine(model, params, faults=inj, n_blocks=24, chunk=4)
    rids = [eng.submit(prompts[i][:12], max_new_tokens=5) for i in range(3)]
    eng.run()
    for rid in rids:
        assert eng.requests[rid].state in TERMINAL_STATES
    assert not eng.cache.allocator.owned(FAULT_OWNER)
    assert eng.stats()["faults"]["squeeze"] == 2
    _assert_clean_exit(eng)


def test_slow_steps_expire_deadlines_deterministically(served):
    """slow_step burns virtual clock ticks: a request whose TTL would
    comfortably fit in real steps expires under slow faults — EXPIRED,
    partial stream kept."""
    model, params, prompts = served
    inj = FaultInjector([FaultEvent(step=s, kind="slow_step", magnitude=5)
                         for s in range(2, 12)])
    eng = _engine(model, params, faults=inj, chunk=0)
    rid = eng.submit(prompts[0][:10], max_new_tokens=30, deadline_steps=25)
    out = eng.run()
    req = eng.requests[rid]
    assert req.state == "expired" and req.finish_reason == "deadline"
    assert 0 < out[rid].size < 30
    np.testing.assert_array_equal(
        out[rid],
        _solo(model, params, prompts[0][:10], 30)[:out[rid].size])
    _assert_clean_exit(eng)


# ==========================================================================
# the injector itself
# ==========================================================================

def test_seeded_schedule_is_deterministic_and_validated():
    a = FaultInjector.seeded(7, n_steps=50, rate=0.4)
    b = FaultInjector.seeded(7, n_steps=50, rate=0.4)
    assert a.events == b.events and len(a.events) > 0
    assert FaultInjector.seeded(8, n_steps=50, rate=0.4).events != a.events
    assert a.horizon >= max(e.step for e in a.events)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(step=0, kind="gamma_ray")
    with pytest.raises(ValueError, match="malformed"):
        FaultEvent(step=-1, kind="squeeze")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultInjector.seeded(0, kinds=("squeeze", "nope"))


def test_pick_is_stable_modulo_candidates():
    e = FaultEvent(step=0, kind="nan_logits", target=5)
    assert FaultInjector().pick(e, ["a", "b", "c"]) == "c"
    assert FaultInjector().pick(e, []) is None


# ==========================================================================
# the auditor
# ==========================================================================

def test_audit_failure_is_structured(served):
    """Seeded bookkeeping corruption: the auditor names the violated
    invariant in a structured AuditFailure."""
    model, params, prompts = served
    eng = _engine(model, params, chunk=0, audit=True)
    eng.submit(prompts[0][:10], max_new_tokens=4)
    eng.step()
    # corrupt the bookkeeping behind the allocator's back: orphan a block
    # out of the free list
    eng.cache.allocator._free.remove(eng.cache.allocator._free[0])
    with pytest.raises(AuditFailure) as ei:
        eng.step()
    assert ei.value.invariant == "allocator_conservation"
    assert "lost blocks" in ei.value.detail


def test_audit_catches_table_ownership_violation(served):
    model, params, prompts = served
    eng = _engine(model, params, chunk=0, audit=True)
    rid = eng.submit(prompts[0][:10], max_new_tokens=6)
    eng.step()
    slot = eng.requests[rid].slot
    # scribble a block id the request does not own into its table
    eng.cache.table[slot, 0] = eng.cache.allocator._free[0]
    with pytest.raises(AuditFailure) as ei:
        eng.step()
    assert ei.value.invariant == "table_ownership"


def test_audit_passes_are_counted(served):
    model, params, prompts = served
    eng = _engine(model, params, chunk=0, audit=True)
    eng.submit(prompts[0][:8], max_new_tokens=4)
    eng.run()
    s = eng.stats()
    assert s["audit_passes"] == s["steps"] > 0
