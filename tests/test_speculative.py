"""Speculative-decoding suite: the accept/reject determinism contract.

Covers the acceptance criteria of the speculative subsystem:

  * **degenerate-tree equivalence** — ``depth=0`` (single-node tree) runs
    the verify path yet emits exactly the vanilla engine's streams;
  * **stream identity** — speculative streams (greedy AND seeded
    sampling, n-gram self-speculation AND a paired draft model) are
    token-identical to the non-speculative engine across the ``ref``,
    ``chunked-lax``, and ``pallas-interpret`` backends;
  * **rollback conservation** — rejected branches leak no blocks: target
    and draft allocators conserve through rollbacks, including under a
    seeded chaos storm;
  * unit tests for the tree-mask helpers (core/mask.tree_spec) and the
    prompt-lookup matcher (NGramDraft);
  * adaptive depth — the controller is a pure function of a request's
    own acceptance history, and an adaptive engine emits token-identical
    streams to the vanilla engine.
"""
import types

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mask as mk
from repro.core.config import ShapeSpec, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine
from repro.serve.speculative import (ModelDraft, NGramDraft, NullDraft,
                                     SpecConfig, make_draft)
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def served():
    """One smoke model for the whole module (build+init dominates)."""
    cfg = smoke_config(get_config("smollm-360m"))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("spec", 32, 4, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
    return cfg, model, params, prompts


def _drive(model, params, specs, *, spec=None, draft=None, n_blocks=32,
           max_batch=4, stagger=0, **ekw):
    """Run a list of (prompt, n, temperature, seed) to completion; returns
    (streams list, engine)."""
    eng = Engine(model, params, max_batch=max_batch, block_size=8,
                 n_blocks=n_blocks, spec=spec, draft=draft, **ekw)
    rids = []
    for prompt, n, temp, seed in specs:
        rids.append(eng.submit(prompt, max_new_tokens=n, temperature=temp,
                               seed=seed))
        for _ in range(stagger):
            eng.step()
    out = eng.run()
    return [np.asarray(out[r]) for r in rids], eng


# ==========================================================================
# unit: SpecConfig / draft sources
# ==========================================================================

def test_spec_config_validation():
    with pytest.raises(ValueError, match="depth"):
        SpecConfig(depth=-1)
    with pytest.raises(ValueError, match="mode"):
        SpecConfig(mode="telepathy")
    with pytest.raises(ValueError, match="ngram"):
        SpecConfig(ngram=0)
    assert isinstance(make_draft(SpecConfig(mode="ngram")), NGramDraft)
    assert isinstance(make_draft(SpecConfig(mode="none")), NullDraft)
    with pytest.raises(ValueError, match="ModelDraft"):
        make_draft(SpecConfig(mode="model"))


def test_ngram_draft_prompt_lookup():
    d = NGramDraft(ngram=3)

    def req(*ctx):
        return types.SimpleNamespace(context=np.asarray(ctx, np.int32))

    # trailing [1,2,3] recurs at index 1 -> propose its continuation
    assert d.propose(req(5, 1, 2, 3, 7, 8, 1, 2, 3), 2) == [7, 8]
    # continuation truncated to k
    assert d.propose(req(5, 1, 2, 3, 7, 8, 1, 2, 3), 1) == [7]
    # rightmost (freshest) earlier occurrence wins
    assert d.propose(req(1, 2, 9, 1, 2, 4, 1, 2), 1) == [4]
    # falls back to shorter n-grams before giving up
    assert d.propose(req(3, 7, 5, 3), 1) == [7]
    # no earlier occurrence of any suffix -> nothing
    assert d.propose(req(1, 2, 3, 4), 3) == []
    assert d.propose(req(1, 2, 3, 1), 0) == []


# ==========================================================================
# unit: tree masks (core/mask)
# ==========================================================================

def test_chain_parents_and_chain_spec():
    assert mk.chain_parents(4) == (-1, 0, 1, 2)
    # a chain (and the single node) degenerates to plain causal
    assert mk.tree_spec(mk.chain_parents(1)) == mk.MaskSpec(causal=True)
    assert mk.tree_spec(mk.chain_parents(5), window=7) == \
        mk.MaskSpec(causal=True, window=7)


@pytest.mark.parametrize("parents", [
    (-1,),                      # single node
    (-1, 0, 1, 2),              # chain
    (-1, 0, -1, 2),             # two branches of 2
    (-1, -1, -1),               # three singleton branches
    (-1, 0, 1, -1, 3),          # branches of 3 and 2
])
def test_tree_spec_matches_ancestor_mask(parents):
    """The MaskSpec's allow() over the verify chunk's absolute positions
    must reproduce the ground-truth ancestor matrix, with the committed
    context attendable by every node."""
    P = 6                                        # committed-context length
    K = len(parents)
    spec = mk.tree_spec(parents, prefix_len=P)
    pos = np.arange(P + K)
    m = np.asarray(spec.allow(pos[:, None], pos[None, :]))
    want = np.zeros((P + K, P + K), bool)
    want[:P, :P] = np.tril(np.ones((P, P), bool))     # context: causal
    want[P:, :P] = True                               # nodes see context
    want[P:, P:] = mk.tree_ancestor_mask(parents)
    np.testing.assert_array_equal(m[P:], want[P:])
    # context rows must never attend draft nodes
    assert not m[:P, P:].any()


def test_tree_spec_rejects_rebranching():
    with pytest.raises(ValueError, match="chains and stars"):
        mk.tree_spec((-1, 0, 0))            # node 2 re-branches off node 0
    with pytest.raises(ValueError, match="empty"):
        mk.tree_spec(())


# ==========================================================================
# degenerate-tree equivalence + stream identity
# ==========================================================================

def _specs(prompts):
    return [(prompts[0][:24], 6, 0.0, 0),       # greedy
            (prompts[1][:17], 5, 0.8, 123),     # seeded sampling
            (prompts[2][:9], 6, 0.8, 7)]


def test_degenerate_tree_equals_vanilla(served):
    """depth=0: the verify path runs (single-node tree) but must emit
    exactly the vanilla engine's streams — the bitwise anchor for the
    whole acceptance scheme."""
    cfg, model, params, prompts = served
    vanilla, _ = _drive(model, params, _specs(prompts))
    degen, eng = _drive(model, params, _specs(prompts),
                        spec=SpecConfig(depth=0, mode="none"))
    for a, b in zip(vanilla, degen):
        np.testing.assert_array_equal(a, b)
    s = eng.stats()
    assert s["spec_proposed"] == 0 and s["spec_rollbacks"] == 0


def test_ngram_speculative_stream_identity(served):
    """Self-speculation at depth 3: token-identical streams (greedy and
    seeded sampling), counters consistent, no allocator damage."""
    cfg, model, params, prompts = served
    vanilla, _ = _drive(model, params, _specs(prompts))
    spec, eng = _drive(model, params, _specs(prompts),
                       spec=SpecConfig(depth=3, mode="ngram"))
    for a, b in zip(vanilla, spec):
        np.testing.assert_array_equal(a, b)
    s = eng.stats()
    assert s["spec_accepted"] + s["spec_rejected"] == s["spec_proposed"]
    assert 0.0 <= s["spec_acceptance"] <= 1.0
    eng.cache.allocator.check_conservation()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable


def test_model_draft_acceptance_and_identity(served):
    """A ModelDraft sharing the target's params (the ceiling regime) must
    actually accept proposals (> 0), emit identical streams, finish in
    fewer engine steps than vanilla, and conserve BOTH allocators —
    including the draft's own pool after its per-request state is
    dropped."""
    cfg, model, params, prompts = served
    specs = [(prompts[0][:24], 8, 0.0, 0), (prompts[1][:17], 8, 0.0, 1)]
    vanilla, veng = _drive(model, params, specs)
    draft = ModelDraft(model, params, block_size=8, n_blocks=32,
                       max_batch=4)
    spec, eng = _drive(model, params, specs,
                       spec=SpecConfig(depth=3, mode="model"), draft=draft)
    for a, b in zip(vanilla, spec):
        np.testing.assert_array_equal(a, b)
    s = eng.stats()
    assert s["spec_accepted"] > 0, "target-params draft must accept"
    assert s["spec_acceptance"] > 0.0
    assert s["steps"] < veng.stats()["steps"], \
        "accepted proposals must reduce engine steps"
    eng.cache.allocator.check_conservation()
    draft.cache.allocator.check_conservation()
    assert not draft._slots, "terminal requests must release draft state"
    assert draft.cache.allocator.n_free + draft.cache.n_cache_blocks \
        == draft.cache.allocator.n_usable


@pytest.mark.parametrize("impl", ["ref", "chunked-lax", "pallas-interpret"])
def test_backend_stream_identity(served, impl):
    """The speculative streams are backend-invariant: each kernel backend
    reproduces the ref backend's vanilla streams exactly (greedy + seeded
    sampling) with speculation on."""
    cfg, model, params, prompts = served
    vanilla, _ = _drive(model, params, _specs(prompts))
    m2 = model if impl == "ref" else build_model(
        cfg, Runtime(mesh=model.rt.mesh, par=model.rt.par, impl=impl))
    spec, _ = _drive(m2, params, _specs(prompts),
                     spec=SpecConfig(depth=3, mode="ngram"))
    for a, b in zip(vanilla, spec):
        np.testing.assert_array_equal(a, b)


# ==========================================================================
# rollbacks under chaos: conservation + stream isolation
# ==========================================================================

@settings(max_examples=3, deadline=None)
@given(chaos_seed=st.integers(0, 10_000))
def test_rollbacks_under_chaos_conserve_and_isolate(served, chaos_seed):
    """A seeded fault storm over a speculating engine: every request
    reaches a terminal state, the allocator conserves through rejected-
    branch rollbacks AND fault recovery, and every request that finishes
    does so with its exact solo non-speculative stream."""
    from repro.serve.faults import FaultInjector
    from repro.serve.scheduler import TERMINAL_STATES
    cfg, model, params, prompts = served
    specs = [(prompts[i % 4][:(9 + 5 * i) % 24 + 4], 4 + i % 3,
              [0.0, 0.8][i % 2], i) for i in range(4)]
    solo = [_drive(model, params, [sp])[0][0] for sp in specs]
    eng = Engine(model, params, max_batch=3, block_size=8, n_blocks=24,
                 prefill_chunk_tokens=8, audit=True, max_retries=6,
                 spec=SpecConfig(depth=2, mode="ngram"),
                 faults=FaultInjector.seeded(chaos_seed, n_steps=16,
                                             rate=0.5))
    rids = [eng.submit(p, max_new_tokens=n, temperature=t, seed=s)
            for p, n, t, s in specs]
    out = eng.run()
    eng.release_faults()
    eng.cache.allocator.check_conservation()
    for rid, sol in zip(rids, solo):
        req = eng.requests[rid]
        assert req.state in TERMINAL_STATES
        got = np.asarray(out[rid])
        # chaos may truncate (expire/quarantine) but never corrupt: any
        # emitted prefix is a prefix of the solo stream
        np.testing.assert_array_equal(got, sol[:len(got)])
        if req.state == "finished" and req.finish_reason == "length":
            assert len(got) == len(sol)


def test_stats_merges_spec_and_robustness_counters(served):
    """Engine.stats() carries the PR-7 robustness counters and the
    speculative counters side by side."""
    cfg, model, params, prompts = served
    _, eng = _drive(model, params, [(prompts[0][:9], 3, 0.0, 0)],
                    spec=SpecConfig(depth=2, mode="ngram"))
    s = eng.stats()
    for k in ("spec_proposed", "spec_accepted", "spec_rejected",
              "spec_rollbacks", "spec_acceptance", "shed", "retried",
              "quarantined", "expired", "failed", "watchdog_trips"):
        assert k in s, k


# ==========================================================================
# adaptive speculative depth
# ==========================================================================

def _adaptive_spec(**over):
    from repro.serve.speculative import SpecConfig
    kw = dict(depth=4, mode="ngram", adaptive=True, adapt_window=4,
              adapt_floor=0.25, min_depth=1)
    kw.update(over)
    return SpecConfig(**kw)


def test_spec_config_adaptive_validation():
    from repro.serve.speculative import SpecConfig
    with pytest.raises(ValueError, match="adapt_window"):
        SpecConfig(depth=4, adaptive=True, adapt_window=0)
    with pytest.raises(ValueError, match="adapt_floor"):
        SpecConfig(depth=4, adaptive=True, adapt_floor=1.5)
    with pytest.raises(ValueError, match="min_depth"):
        SpecConfig(depth=4, adaptive=True, min_depth=9)


def test_adaptive_depth_is_pure_function_of_own_history():
    from repro.serve.speculative import AdaptiveDepth
    ad = AdaptiveDepth(_adaptive_spec())
    # optimistic start: no history -> full cap
    assert ad.depth_for(1) == 4
    # full acceptance keeps the cap
    for _ in range(4):
        ad.observe(1, 4, 4)
    assert ad.depth_for(1) == 4
    # zero acceptance floors at min_depth
    for _ in range(4):
        ad.observe(1, 0, 4)
    assert ad.depth_for(1) == 1
    # a == 0.5 -> d* = log(.25)/log(.5) = 2
    ad2 = AdaptiveDepth(_adaptive_spec())
    for _ in range(4):
        ad2.observe(2, 2, 4)
    assert ad2.depth_for(2) == 2
    # other requests' history never leaks: rid 3 untouched -> cap
    assert ad2.depth_for(3) == 4
    # release forgets
    ad2.release(2)
    assert ad2.depth_for(2) == 4
    # zero-proposal steps carry no signal
    ad3 = AdaptiveDepth(_adaptive_spec())
    ad3.observe(5, 0, 0)
    assert ad3.depth_for(5) == 4


def test_adaptive_depth_window_slides():
    from repro.serve.speculative import AdaptiveDepth
    ad = AdaptiveDepth(_adaptive_spec(adapt_window=2))
    for _ in range(10):
        ad.observe(1, 0, 4)
    assert ad.depth_for(1) == 1
    # two perfect steps push the zeros out of the window -> cap again
    ad.observe(1, 4, 4)
    ad.observe(1, 4, 4)
    assert ad.depth_for(1) == 4


def _streams(model, params, prompts, spec):
    from repro.serve.engine import Engine
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 spec=spec)
    rids = [eng.submit(prompts[i][:24 + 4 * i], max_new_tokens=8,
                       temperature=0.0) for i in range(2)]
    out = eng.run()
    return [np.asarray(out[r]) for r in rids], eng


def test_adaptive_engine_streams_token_identical(served):
    _, model, params, prompts = served
    base, _ = _streams(model, params, prompts, None)
    adapt, eng = _streams(model, params, prompts, _adaptive_spec())
    for b, a in zip(base, adapt):
        np.testing.assert_array_equal(b, a)
    hist = eng.stats()["spec_depth_hist"]
    assert hist and sum(hist.values()) > 0
    assert all(0 <= k <= 4 for k in hist)
    # determinism of the whole adaptive engine: replay is identical
    adapt2, eng2 = _streams(model, params, prompts, _adaptive_spec())
    for a, a2 in zip(adapt, adapt2):
        np.testing.assert_array_equal(a, a2)
    assert eng2.stats()["spec_depth_hist"] == hist
