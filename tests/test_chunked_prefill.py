"""Oracle differentials for chunked prefill and the shared-prefix cache:
streams from the paged engine — whole or chunked prefill, cold or warm
prefix cache, copy-on-write forks, windowed reclamation — must be
token-identical to the dense fixed-slot oracle and to cold-cache solo
runs, across mha/gqa/mla and causal/sliding-window masking.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.config import ShapeSpec, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine, FixedSlotEngine
from repro.launch.mesh import make_mesh


def _setup(arch, window=0, prompt_len=24, batch=3):
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


def _solo_cold(model, params, prompt, *, n):
    """The canonical baseline: whole-prompt prefill, no prefix cache,
    request run alone."""
    eng = Engine(model, params, max_batch=4, block_size=8,
                 n_blocks=4 * (len(prompt) + n) // 8 + 8,
                 prefill_chunk_tokens=0, prefix_cache=False)
    rid = eng.submit(prompt, max_new_tokens=n)
    return eng.run()[rid]


def _drained_conservation(eng):
    eng.cache.allocator.check_conservation()
    assert eng.cache.allocator.n_free + eng.cache.n_cache_blocks \
        == eng.cache.allocator.n_usable
    if eng.cache.prefix is not None:
        eng.cache.prefix.check_integrity()


# ==========================================================================
# fixed-slot oracle differential: chunked × cold/warm × arch × mask
# ==========================================================================

@pytest.mark.parametrize("chunk", [0, 7, 16])
@pytest.mark.parametrize("arch,window",
                         [("smollm-360m", 0), ("llama-gqa", 0),
                          ("llama-gqa", 16),
                          pytest.param("deepseek-v2-lite-16b", 0,
                                       marks=pytest.mark.slow)])
def test_chunked_prefill_matches_fixed_slot_oracle(arch, window, chunk):
    """Cold pass: chunked prefill streams equal the dense oracle's.  Warm
    pass (same prompts resubmitted): the prefix cache must actually hit,
    and the streams must not change."""
    cfg, model, params, batch_d = _setup(arch, window=window)
    n = 6
    toks_fixed, _ = FixedSlotEngine(model, params).generate(batch_d, n)
    eng = Engine(model, params, max_batch=4, block_size=8, n_blocks=48,
                 prefill_chunk_tokens=chunk, prefix_cache=True)
    toks_cold = eng.generate(batch_d, n)
    np.testing.assert_array_equal(np.asarray(toks_fixed),
                                  np.asarray(toks_cold))
    hits_before = eng.stats()["hit_tokens"]
    toks_warm = eng.generate(batch_d, n)
    assert eng.stats()["hit_tokens"] > hits_before, \
        "warm pass should be served (partly) from the prefix cache"
    np.testing.assert_array_equal(np.asarray(toks_cold),
                                  np.asarray(toks_warm))
    _drained_conservation(eng)


# ==========================================================================
# windowed reclamation
# ==========================================================================

def test_windowed_reclamation_frees_blocks_and_matches_oracle():
    """Sliding-window serving reclaims blocks wholly below the window
    (freed storage, not masked storage) without perturbing the stream."""
    cfg, model, params, batch_d = _setup("llama-gqa", window=16,
                                         prompt_len=32, batch=2)
    n = 12
    toks_fixed, _ = FixedSlotEngine(model, params).generate(batch_d, n)
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 prefill_chunk_tokens=8)
    toks_paged = eng.generate(batch_d, n)
    np.testing.assert_array_equal(np.asarray(toks_fixed),
                                  np.asarray(toks_paged))
    assert eng.stats()["reclaimed"] > 0, \
        "context grew past the window; blocks below it must be reclaimed"
    _drained_conservation(eng)


# ==========================================================================
# copy-on-write forks (engineered divergence)
# ==========================================================================

def test_partial_tail_hit_forks_before_chunk_write():
    """A request sharing a prefix that ends *inside* a cached block must
    fork that block before its chunk writes into it — and stream exactly
    as if it ran cold and alone."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=2)
    prompts = _prompts(batch_d)
    donor = prompts[0][:25]                    # prefill 24 = 3 full blocks
    div = donor.copy()
    div[20:] = (div[20:] + 1) % cfg.vocab     # diverges mid-block-2
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 prefill_chunk_tokens=8)
    eng.submit(donor, max_new_tokens=4)
    eng.run()
    assert eng.cache.n_cache_blocks >= 3
    r1 = eng.submit(div, max_new_tokens=4)
    out = eng.run()
    req = eng.requests[r1]
    assert req.n_hit == 20, "expected a partial-tail hit (2.5 blocks)"
    assert eng.stats()["forks"] >= 1, \
        "writing past the shared partial tail must fork the block"
    np.testing.assert_array_equal(out[r1],
                                  _solo_cold(model, params, div, n=4))
    # the donor's cached prefix must be untouched by the fork: a third
    # request with the donor's exact prompt still streams identically
    r2 = eng.submit(donor, max_new_tokens=4)
    out2 = eng.run()
    np.testing.assert_array_equal(out2[r2],
                                  _solo_cold(model, params, donor, n=4))
    _drained_conservation(eng)


def test_full_prefix_hit_forks_on_first_decode_write():
    """A request whose *entire* prefill is cached (prefix + partial tail)
    skips prefill chunks entirely; its first decode write lands inside a
    shared block and must fork it."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=32,
                                         batch=2)
    prompts = _prompts(batch_d)
    donor = prompts[0][:27]                    # prefill 26 = 3 full blocks
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 prefill_chunk_tokens=8)
    eng.submit(donor, max_new_tokens=4)
    eng.run()
    short = donor[:23]                         # prefill 22: fully cached
    r1 = eng.submit(short, max_new_tokens=4)
    out = eng.run()
    req = eng.requests[r1]
    assert req.n_hit == 22 and req.n_hit == len(short) - 1, \
        "whole prefill should be served from the cache"
    assert eng.stats()["forks"] >= 1, \
        "decode writes into the shared tail block must fork it"
    np.testing.assert_array_equal(out[r1],
                                  _solo_cold(model, params, short, n=4))
    _drained_conservation(eng)


# ==========================================================================
# content-hash dedupe
# ==========================================================================

def test_same_step_duplicate_prompts_dedupe_onto_one_copy():
    """Two identical prompts admitted in the same step both miss the
    lookup and prefill privately; registration dedupes the second onto
    the first's canonical blocks (content addressing, not just prefix
    lookup), and both streams agree with the cold solo run."""
    cfg, model, params, batch_d = _setup("smollm-360m", prompt_len=24,
                                         batch=2)
    p = _prompts(batch_d)[0]
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 prefill_chunk_tokens=8)
    r0 = eng.submit(p, max_new_tokens=4)
    r1 = eng.submit(p, max_new_tokens=4)
    out = eng.run()
    assert eng.stats()["dedup_swaps"] > 0, \
        "the duplicate's full blocks must be swapped onto the canonical copy"
    np.testing.assert_array_equal(out[r0], out[r1])
    np.testing.assert_array_equal(out[r0], _solo_cold(model, params, p, n=4))
    _drained_conservation(eng)
