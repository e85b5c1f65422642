"""Continuous-batching engine tests: fixed-slot vs paged ``generate``
equivalence, per-request sampling determinism, stop-token early exit, and
the batch-invariance property suite (staggered arrivals, mixed prompt
lengths, pool-pressure preemption ⇒ every request's greedy stream equals
its solo run — with speculation on, every stream plus its acceptance
history must match the solo NON-speculative run), plus
scheduler/allocator bookkeeping invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.config import ShapeSpec, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine, FixedSlotEngine
from repro.launch.mesh import make_mesh


def _setup(arch, window=0, prompt_len=24, batch=3):
    import dataclasses
    cfg = smoke_config(get_config(arch))
    if window:
        cfg = cfg.replace(attn=dataclasses.replace(cfg.attn, window=window))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("srv", prompt_len, batch, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    batch_d = SyntheticTokens(cfg, shape, par, mesh).batch(0)
    return cfg, model, params, batch_d


def _prompts(batch_d):
    return np.asarray(batch_d["tokens"])


def _solo_stream(model, params, prompt, *, n, temperature=0.0, seed=0,
                 max_batch=4, block_size=8):
    """The request run alone — whole-prompt prefill, no prefix cache, ample
    pool: the canonical baseline every batched/chunked/cached stream must
    reproduce exactly."""
    eng = Engine(model, params, max_batch=max_batch, block_size=block_size,
                 n_blocks=4 * (len(prompt) + n) // block_size + 8,
                 prefill_chunk_tokens=0, prefix_cache=False)
    rid = eng.submit(prompt, max_new_tokens=n, temperature=temperature,
                     seed=seed)
    return eng.run()[rid]


# ==========================================================================
# engine smoke: old fixed-slot API vs the paged engine
# ==========================================================================

@pytest.mark.parametrize("arch,window",
                         [("llama-gqa", 0), ("llama-gqa", 16),
                          pytest.param("deepseek-v2-lite-16b", 0,
                                       marks=pytest.mark.slow)])
def test_generate_equivalence_fixed_slot_vs_paged(arch, window):
    """Greedy streams of the dense fixed-slot oracle and the paged
    continuous-batching engine must agree (GQA; windowed; MLA+MoE is the
    slow param)."""
    cfg, model, params, batch_d = _setup(arch, window=window)
    n = 6
    toks_fixed, _ = FixedSlotEngine(model, params).generate(batch_d, n)
    eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=32)
    toks_paged = eng.generate(batch_d, n)
    np.testing.assert_array_equal(np.asarray(toks_fixed),
                                  np.asarray(toks_paged))


def test_temperature_sampling_determinism():
    """Same (seed, prompt) ⇒ identical sampled stream — across engine
    instances AND across different batch compositions; different seeds
    diverge."""
    cfg, model, params, batch_d = _setup("llama-gqa")
    prompts = _prompts(batch_d)
    kw = dict(max_new_tokens=6, temperature=0.9)

    def run(extra_load):
        eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=64)
        if extra_load:                       # different batch composition
            eng.submit(prompts[1], max_new_tokens=4, temperature=0.5,
                       seed=7)
        rid = eng.submit(prompts[0], seed=123, **kw)
        return eng.run()[rid]

    a, b, c = run(False), run(False), run(True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)

    eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=64)
    rid = eng.submit(prompts[0], seed=124, **kw)
    assert not np.array_equal(a, eng.run()[rid])


def test_stop_token_early_exit():
    cfg, model, params, batch_d = _setup("llama-gqa")
    prompt = _prompts(batch_d)[0]
    full = _solo_stream(model, params, prompt, n=8)
    stop = int(full[3])
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32)
    rid = eng.submit(prompt, max_new_tokens=8, stop_tokens=(stop,))
    out = eng.run()[rid]
    req = eng.requests[rid]
    assert req.finish_reason == "stop"
    k = int(np.nonzero(full == stop)[0][0])
    np.testing.assert_array_equal(out, full[:k + 1])
    assert len(out) < 8


# ==========================================================================
# batch-invariance property suite
# ==========================================================================

@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_batch_invariance_under_staggered_arrivals(seed):
    """Hypothesis-driven: random staggered arrivals, mixed prompt lengths
    and budgets, a pool small enough to preempt — every request's greedy
    stream equals its solo (batch-of-one) run, and the allocator conserves
    its blocks."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=4)
    prompts = _prompts(batch_d)
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(3, 6))
    specs = []
    for i in range(n_req):
        plen = int(rng.choice([9, 17, 25, 32]))
        specs.append(dict(prompt=prompts[i % len(prompts)][:plen],
                          n=int(rng.integers(3, 8)),
                          arrive=int(rng.integers(0, 4))))
    # pool sized to hold ~2 requests: forces queueing and/or preemption
    eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=14)
    rids = {}
    step = 0
    order = sorted(range(n_req), key=lambda i: (specs[i]["arrive"], i))
    for i in order:
        while step < specs[i]["arrive"]:
            eng.step()
            step += 1
        rids[i] = eng.submit(specs[i]["prompt"],
                             max_new_tokens=specs[i]["n"])
    out = eng.run()
    eng.cache.allocator.check_conservation()
    # after draining, every block is either free or pinned by the prefix
    # cache (retained for future shared-prefix arrivals)
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    for i, spec in enumerate(specs):
        got = out[rids[i]]
        assert len(got) <= spec["n"]
        solo = _solo_stream(model, params, spec["prompt"], n=spec["n"])
        np.testing.assert_array_equal(got, solo[:len(got)], err_msg=str(i))
        assert len(got) == len(solo)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000), chunk=st.sampled_from([0, 8, 16]),
       warm=st.booleans())
def test_batch_invariance_across_chunk_size_and_cache_state(seed, chunk,
                                                            warm):
    """A request's stream is unchanged whether its prefix hit or missed
    the cache and whether its prefill ran whole or chunked: random
    overlapping-prefix arrivals under every chunking regime, optionally
    against a pre-warmed cache, all match the cold whole-prefill solo
    baseline."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=4)
    prompts = _prompts(batch_d)
    rng = np.random.default_rng(seed)
    eng = Engine(model, params, max_batch=3, block_size=8, n_blocks=32,
                 prefill_chunk_tokens=chunk)
    if warm:                                  # populate the prefix cache
        w = eng.submit(prompts[0], max_new_tokens=2)
        eng.run()
        del eng.requests[w]
    # overlapping prompts (prefixes of the same rows) force a mix of full,
    # partial-tail, and missed lookups
    specs = [dict(prompt=prompts[int(rng.integers(0, 2))]
                  [:int(rng.choice([9, 17, 25, 32]))],
                  n=int(rng.integers(3, 7)),
                  arrive=int(rng.integers(0, 4)))
             for _ in range(int(rng.integers(3, 6)))]
    rids = {}
    step = 0
    for i in sorted(range(len(specs)),
                    key=lambda i: (specs[i]["arrive"], i)):
        while step < specs[i]["arrive"]:
            eng.step()
            step += 1
        rids[i] = eng.submit(specs[i]["prompt"],
                             max_new_tokens=specs[i]["n"])
    out = eng.run()
    eng.cache.allocator.check_conservation()
    eng.cache.prefix.check_integrity()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    for i, spec in enumerate(specs):
        solo = _solo_stream(model, params, spec["prompt"], n=spec["n"],
                            max_batch=3)
        np.testing.assert_array_equal(out[rids[i]], solo, err_msg=str(i))


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000), depth=st.sampled_from([1, 2, 3]))
def test_spec_batch_invariance_under_staggered_arrivals(seed, depth):
    """Speculative streams are batch- and preemption-invariant: random
    staggered arrivals (mixed lengths, budgets, temperatures) into a
    speculating engine with a pool small enough to preempt — every
    stream equals its solo NON-speculative run, the per-request
    acceptance history is internally consistent, and the allocator
    conserves through every rejected-branch rollback."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=4)
    from repro.serve.speculative import SpecConfig
    prompts = _prompts(batch_d)
    rng = np.random.default_rng(seed)
    specs = [dict(prompt=prompts[i % len(prompts)]
                  [:int(rng.choice([9, 17, 25, 32]))],
                  n=int(rng.integers(3, 8)),
                  temp=float(rng.choice([0.0, 0.8])),
                  arrive=int(rng.integers(0, 4)))
             for i in range(int(rng.integers(3, 6)))]
    # pool sized to hold ~2 requests incl. lookahead: forces queueing
    # and/or preemption through the speculative path
    eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=16,
                 spec=SpecConfig(depth=depth, mode="ngram"))
    rids = {}
    step = 0
    for i in sorted(range(len(specs)),
                    key=lambda i: (specs[i]["arrive"], i)):
        while step < specs[i]["arrive"]:
            eng.step()
            step += 1
        rids[i] = eng.submit(specs[i]["prompt"],
                             max_new_tokens=specs[i]["n"],
                             temperature=specs[i]["temp"], seed=i)
    out = eng.run()
    eng.cache.allocator.check_conservation()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    s = eng.stats()
    assert s["spec_accepted"] + s["spec_rejected"] == s["spec_proposed"]
    for i, spec in enumerate(specs):
        solo = _solo_stream(model, params, spec["prompt"], n=spec["n"],
                            temperature=spec["temp"], seed=i)
        np.testing.assert_array_equal(out[rids[i]], solo, err_msg=str(i))


def test_preemption_of_shared_prefix_request_conserves_blocks():
    """Preempting a request whose blocks are shared (with another live
    request and with the prefix cache) must drop only the victim's refs:
    the survivor keeps streaming correctly, the pool stays conserved, and
    the victim completes after re-admission with its exact solo stream."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=3)
    prompts = _prompts(batch_d)
    shared = prompts[0][:16]                   # 2 full blocks of prefix
    a = np.concatenate([shared, prompts[1][:9]])
    b = np.concatenate([shared, prompts[2][:9]])
    # 7 usable blocks: each request peaks at 5 (25 prompt + 12 new), so
    # the pair only fits while the prefix is shared — growth must preempt
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=8,
                 prefill_chunk_tokens=8)
    r0 = eng.submit(a, max_new_tokens=12)
    r1 = eng.submit(b, max_new_tokens=12)
    out = eng.run()
    assert eng.sched.n_preemptions > 0, \
        "pool was sized so decode growth must preempt the younger request"
    assert eng.stats()["hit_blocks"] > 0 or eng.stats()["dedup_swaps"] > 0, \
        "the common prefix must actually be shared"
    eng.cache.allocator.check_conservation()
    eng.cache.prefix.check_integrity()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    for rid, prompt in ((r0, a), (r1, b)):
        assert len(out[rid]) == 12
        solo = _solo_stream(model, params, prompt, n=12, max_batch=2)
        np.testing.assert_array_equal(out[rid], solo)


def test_preemption_requeue_completes_and_matches_solo():
    """Engineered pool pressure: three long-budget requests into a pool
    that holds barely two — preemptions must occur, every request must
    still finish with its full budget, and streams match solo runs."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=24,
                                         batch=3)
    prompts = _prompts(batch_d)
    eng = Engine(model, params, max_batch=3, block_size=8, n_blocks=10)
    rids = [eng.submit(prompts[i], max_new_tokens=10) for i in range(3)]
    out = eng.run()
    assert eng.sched.n_preemptions > 0, "pool was sized to force preemption"
    eng.cache.allocator.check_conservation()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    for i, rid in enumerate(rids):
        assert len(out[rid]) == 10
        solo = _solo_stream(model, params, prompts[i], n=10, max_batch=3)
        np.testing.assert_array_equal(out[rid], solo)


def test_submit_rejects_never_fitting_request():
    """Shedding is a structured status, not an exception: a request that
    could never fit in the pool comes back terminal REJECTED with a
    reason, and the pool is untouched."""
    cfg, model, params, batch_d = _setup("smollm-360m")
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=4)
    free_before = eng.cache.allocator.n_free
    rid = eng.submit(_prompts(batch_d)[0], max_new_tokens=32)
    state, reason = eng.status(rid)
    assert state == "rejected" and reason == "never_fits"
    assert eng.cache.allocator.n_free == free_before
    assert eng.stats()["shed"] == 1
    assert eng.sched.idle                 # never entered the queue
    assert eng.run()[rid].size == 0       # drains trivially, empty stream


def test_rejected_at_admission_never_touches_pool():
    """Queue-depth shedding: the shed request is terminal REJECTED at
    submit time and the block pool is bit-identical before and after —
    allocation only ever happens at admission, which it never reaches."""
    cfg, model, params, batch_d = _setup("smollm-360m")
    prompts = _prompts(batch_d)
    eng = Engine(model, params, max_batch=1, block_size=8, n_blocks=24,
                 max_queue=1)
    eng.cache.allocator.check_conservation()
    free_before = eng.cache.allocator.n_free
    keep = eng.submit(prompts[0][:10], max_new_tokens=4)
    shed = eng.submit(prompts[1][:10], max_new_tokens=4)
    assert eng.status(shed) == ("rejected", "queue_full")
    assert eng.cache.allocator.n_free == free_before
    eng.cache.allocator.check_conservation()
    out = eng.run()
    assert eng.status(keep)[0] == "finished" and out[shed].size == 0


def test_deadline_expiry_mid_prefill_returns_partial_stream():
    """A TTL elapsing while the request is still chunk-prefilling ends it
    EXPIRED with its (empty) partial stream, blocks released; an expiry
    landing mid-decode keeps the partial stream, a prefix of the solo
    run."""
    cfg, model, params, batch_d = _setup("smollm-360m")
    prompts = _prompts(batch_d)
    # chunk=2: a 20-token prompt needs ~10 prefill steps; TTL of 3 ticks
    # expires mid-prefill
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 prefill_chunk_tokens=2)
    rid = eng.submit(prompts[0][:20], max_new_tokens=8, deadline_steps=3)
    out = eng.run()
    req = eng.requests[rid]
    assert (req.state, req.finish_reason) == ("expired", "deadline")
    assert out[rid].size == 0            # never reached decode
    eng.cache.allocator.check_conservation()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    # mid-decode expiry: enough ticks to emit a few tokens, not all
    eng2 = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                  prefill_chunk_tokens=0)
    rid2 = eng2.submit(prompts[0][:10], max_new_tokens=50,
                       deadline_steps=6)
    out2 = eng2.run()
    req2 = eng2.requests[rid2]
    assert (req2.state, req2.finish_reason) == ("expired", "deadline")
    assert 0 < out2[rid2].size < 50
    solo = _solo_stream(model, params, prompts[0][:10], n=50)
    np.testing.assert_array_equal(out2[rid2], solo[:out2[rid2].size])


@pytest.mark.slow
def test_long_arrival_trace_drains_and_is_invariant():
    """Longer seeded trace (the CI serving bench's shape): a dozen mixed
    requests with Poisson-ish arrivals; drains, conserves blocks, and every
    greedy stream matches solo."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=4)
    prompts = _prompts(batch_d)
    rng = np.random.default_rng(42)
    eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=24)
    pending = [(int(rng.integers(0, 20)),
                prompts[i % 4][:int(rng.choice([8, 16, 24, 32]))],
                int(rng.integers(2, 9))) for i in range(12)]
    pending.sort(key=lambda t: t[0])
    rids, meta = [], []
    step = 0
    while pending or not eng.sched.idle:
        while pending and pending[0][0] <= step:
            _, pr, n = pending.pop(0)
            rids.append(eng.submit(pr, max_new_tokens=n))
            meta.append((pr, n))
        eng.step()
        step += 1
        assert step < 10_000
    out = {r: np.asarray(eng.requests[r].emitted) for r in rids}
    eng.cache.allocator.check_conservation()
    for rid, (pr, n) in zip(rids, meta):
        solo = _solo_stream(model, params, pr, n=n)
        np.testing.assert_array_equal(out[rid], solo)


# ==========================================================================
# 8-device mesh: engine-level invariance with a sharded pool
# ==========================================================================

def test_engine_8dev_batch_invariance(subproc):
    """Two staggered requests on a (1, 8) sequence-sharded mesh (pool
    block-sharded by GSPMD) produce the same greedy streams as their solo
    runs on the same mesh."""
    out = subproc("""
import numpy as np, jax
from repro.launch.mesh import make_mesh
from repro.core.config import ShapeSpec, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine
cfg = smoke_config(get_config("qwen3-8b"))
mesh = make_mesh((1, 8), ("data", "model"))
shape = ShapeSpec("srv", 32, 2, "prefill")
par = make_parallel_config(mesh, shape)
model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
params = model.init(jax.random.PRNGKey(0))
prompts = np.asarray(SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
def solo(p, n):
    e = Engine(model, params, max_batch=2, block_size=8, n_blocks=32)
    r = e.submit(p, max_new_tokens=n)
    return e.run()[r]
eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32)
r0 = eng.submit(prompts[0], max_new_tokens=4)
eng.step(); eng.step()
r1 = eng.submit(prompts[1], max_new_tokens=4)
out = eng.run()
a0, a1 = solo(prompts[0], 4), solo(prompts[1], 4)
assert np.array_equal(out[r0], a0), (out[r0], a0)
assert np.array_equal(out[r1], a1), (out[r1], a1)
print("OK 8dev engine invariance", list(map(int, out[r0])))
""")
    assert "OK 8dev engine invariance" in out


def test_decode_scalar_pos_shim_warns():
    """model.decode with the legacy scalar position broadcasts with a
    one-shot DeprecationWarning."""
    import warnings
    from repro.core import mask as mkm
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=16,
                                         batch=2)
    _, cache = jax.jit(model.prefill)(params, batch_d)
    site = 'decode(batch={"pos": <scalar>})'
    mkm._DEPRECATION_WARNED.discard(site)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        model.decode(params, cache,
                     {"token": jnp.zeros((2, 1), jnp.int32),
                      "pos": jnp.int32(16)})
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)
           and site in str(x.message)]
    assert len(dep) == 1
