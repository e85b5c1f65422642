"""Autotuner suite: table resolution, precedence, degradation, and the
acceptance-aware speculative depth controller (satellites of the
measurement-backed autotuner PR).

Covers the acceptance criteria of the tuning subsystem:

  * **precedence** — explicit kwargs > env overrides > table hit >
    built-in heuristics, at every consumer (kernel tiles via
    ``block_tuning_kw``, ``schedule="auto"``, paged ``block_size``);
  * **nearest-bucket** — an unseen seq resolves to the closest measured
    bucket in log space, never to nothing;
  * **degradation** — a schema-version mismatch or corrupt JSON degrades
    to heuristics with one logged warning per process per path, and
    never raises out of a resolve;
  * **adaptive depth** — the controller is a pure function of a
    request's own acceptance history, and an adaptive engine emits
    token-identical streams to the vanilla engine.
"""
import json
import logging
import math

import jax
import numpy as np
import pytest

from repro.core import mask as mk
from repro.core.schedule import choose_schedule
from repro.kernels.registry import block_tuning_kw
from repro.tune import table as tt
from repro.tune.calibrate import (fit_nonneg, mask_for_kind,
                                  schedule_features, spearman)
from repro.launch.mesh import make_mesh

# --------------------------------------------------------------- fixtures


@pytest.fixture(autouse=True)
def _clean_tuning_state(monkeypatch):
    """Each test starts from env/bundled resolution with no cache and no
    tuning env vars; restores afterwards."""
    for var in ("REPRO_TUNE", "REPRO_TUNE_TABLE", "REPRO_TUNE_BLOCK_Q",
                "REPRO_TUNE_BLOCK_KV", "REPRO_TUNE_BLOCK_SIZE"):
        monkeypatch.delenv(var, raising=False)
    tt.reset()
    yield
    tt.reset()


def sample_table(**over):
    """A minimal valid table: kernel rows at two seq buckets, one schedule
    row, one paged row, calibrated coeffs."""
    data = dict(
        schema_version=tt.SCHEMA_VERSION,
        generated_by="tests",
        host=dict(platform="cpu"),
        kernel=[
            dict(backend="chunked-lax", platform="cpu", mask_kind="causal",
                 head_dim=64, seq=256, op="fwd", block_q=256, block_kv=32,
                 wall_us=10.0),
            dict(backend="chunked-lax", platform="cpu", mask_kind="causal",
                 head_dim=64, seq=1024, op="fwd", block_q=1024, block_kv=128,
                 wall_us=40.0),
        ],
        schedule=[
            dict(mask_kind="causal", P=8, seq=2048, Hq=8, Hkv=8, Dqk=64,
                 B=1, bpe=4, best="balanced",
                 wall_us=dict(zigzag=90.0, balanced=100.0, ring=200.0,
                              ulysses=300.0)),
        ],
        paged=[
            dict(layout="mha", sharding="none", block_size=32,
                 tokens_per_s=100.0),
        ],
        calibration=dict(
            coeffs=dict(s_per_flop=0.0, s_per_byte=0.0, s_per_hop=3e-2,
                        s_per_elem=2e-7, base_s=0.0),
            fit=dict(n_points=15, spearman=0.97, spearman_roofline=-0.07),
        ),
    )
    data.update(over)
    return data


# ==========================================================================
# unit: schema validation + nearest-bucket lookup
# ==========================================================================

def test_valid_table_roundtrip(tmp_path):
    p = tmp_path / "t.json"
    tab = tt.TuningTable(sample_table())
    tab.save(str(p))
    back = tt.TuningTable.load(str(p))
    assert back.data == tab.data
    assert back.path == str(p)


def test_validate_rejects_bad_shapes():
    assert tt.TuningTable.validate([1, 2]) != []
    assert tt.TuningTable.validate(sample_table(schema_version=99)) != []
    bad = sample_table(kernel=[dict(backend="chunked-lax")])
    assert any("missing" in e for e in tt.TuningTable.validate(bad))
    bad = sample_table(calibration=dict(coeffs=dict(s_per_flop="x")))
    assert any("coeffs" in e for e in tt.TuningTable.validate(bad))
    with pytest.raises(tt.TableError, match="schema_version"):
        tt.TuningTable(sample_table(schema_version=99))


def test_nearest_bucket_kernel_lookup():
    tab = tt.TuningTable(sample_table())
    # exact hits
    assert tab.best_blocks(backend="chunked-lax", platform="cpu",
                           mask_kind="causal", head_dim=64,
                           seq=256) == (256, 32)
    # 384 is nearer 256 in log2 space; 768 is nearer 1024
    assert tab.best_blocks(backend="chunked-lax", platform="cpu",
                           mask_kind="causal", head_dim=64,
                           seq=384) == (256, 32)
    assert tab.best_blocks(backend="chunked-lax", platform="cpu",
                           mask_kind="causal", head_dim=64,
                           seq=768) == (1024, 128)
    # categorical keys are exact: unknown backend/mask/op -> None
    assert tab.best_blocks(backend="pallas", platform="cpu",
                           mask_kind="causal", head_dim=64, seq=256) is None
    assert tab.best_blocks(backend="chunked-lax", platform="cpu",
                           mask_kind="sliding_window", head_dim=64,
                           seq=256) is None
    assert tab.best_blocks(backend="chunked-lax", platform="cpu",
                           mask_kind="causal", head_dim=64, seq=256,
                           op="bwd") is None


def test_best_schedule_candidate_restriction():
    tab = tt.TuningTable(sample_table())
    # global winner is zigzag, but restricted to the capable set the
    # fastest candidate wins
    assert tab.best_schedule(mask_kind="causal", P=8, seq=2048) == "zigzag"
    assert tab.best_schedule(mask_kind="causal", P=8, seq=2048,
                             candidates=("balanced", "ring",
                                         "ulysses")) == "balanced"
    # nearest seq bucket serves unseen lengths; P is exact
    assert tab.best_schedule(mask_kind="causal", P=8, seq=4096,
                             candidates=("ring",)) == "ring"
    assert tab.best_schedule(mask_kind="causal", P=4, seq=2048) is None
    assert tab.best_schedule(mask_kind="document", P=8, seq=2048) is None


def test_best_block_size_sharding_fallback():
    tab = tt.TuningTable(sample_table())
    assert tab.best_block_size(layout="mha", sharding="none") == 32
    # unswept sharding falls back to the same layout
    assert tab.best_block_size(layout="mha", sharding="pool") == 32
    assert tab.best_block_size(layout="mla") is None


# ==========================================================================
# unit: degradation — corrupt/mismatched tables never crash a resolve
# ==========================================================================

def test_schema_mismatch_degrades_with_one_warning(tmp_path, caplog,
                                                   monkeypatch):
    p = tmp_path / "future.json"
    p.write_text(json.dumps(sample_table(schema_version=99)))
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(p))
    with caplog.at_level(logging.WARNING, logger="repro.tune.table"):
        assert tt.active_table() is None
        tt.reset()
        assert tt.active_table() is None   # second resolve: no new warning
    warned = [r for r in caplog.records if str(p) in r.getMessage()]
    assert len(warned) == 1
    assert "schema_version" in warned[0].getMessage()


def test_corrupt_json_never_crashes_consumers(tmp_path, monkeypatch):
    p = tmp_path / "corrupt.json"
    p.write_text("{this is not json")
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(p))
    assert tt.active_table() is None
    # every consumer degrades to its built-in heuristic, no raise
    assert block_tuning_kw(None, None, backend="chunked-lax",
                           mask_kind="causal", head_dim=64, seq=256) == {}
    from repro.core.config import get_config
    from repro.serve.cache import PagedKVCache
    assert PagedKVCache.default_block_size(
        get_config("smollm-360m").attn) == 16
    assert choose_schedule(mk.causal(), 8, Tl=32, Hq=8) in (
        "balanced", "ring", "ulysses")
    # explicit set_table with a corrupt path also degrades to None
    tt.set_table(str(p))
    assert tt.active_table() is None


def test_off_switch_disables_table(monkeypatch):
    tt.set_table(tt.TuningTable(sample_table()))
    monkeypatch.setenv("REPRO_TUNE", "off")
    assert tt.active_table() is None
    monkeypatch.delenv("REPRO_TUNE")
    assert tt.active_table() is not None


# ==========================================================================
# precedence: explicit kwarg > env > table > heuristic
# ==========================================================================

def test_kernel_tile_precedence(monkeypatch):
    ctx = dict(backend="chunked-lax", platform="cpu", mask_kind="causal",
               head_dim=64, seq=256)
    tt.set_table(tt.TuningTable(sample_table()))
    # table hit
    assert block_tuning_kw(None, None, **ctx) == dict(block_q=256,
                                                      block_kv=32)
    # env beats table
    monkeypatch.setenv("REPRO_TUNE_BLOCK_KV", "48")
    assert block_tuning_kw(None, None, **ctx) == dict(block_kv=48)
    # explicit kwargs beat both, wholesale (no table fill-in of the other)
    assert block_tuning_kw(16, None, **ctx) == dict(block_q=16)
    assert block_tuning_kw(16, 64, **ctx) == dict(block_q=16, block_kv=64)
    # garbage env is ignored (warn-once), falls through to the table
    monkeypatch.setenv("REPRO_TUNE_BLOCK_KV", "banana")
    assert block_tuning_kw(None, None, **ctx) == dict(block_q=256,
                                                      block_kv=32)
    monkeypatch.delenv("REPRO_TUNE_BLOCK_KV")
    # no table -> heuristics (empty kwargs, kernels keep their defaults)
    tt.set_table(None)
    assert block_tuning_kw(None, None, **ctx) == {}
    # bare two-arg form (inside backend closures) never consults the table
    tt.set_table(tt.TuningTable(sample_table()))
    assert block_tuning_kw(None, None) == {}


def test_paged_block_size_precedence(monkeypatch):
    from repro.core.config import get_config
    from repro.serve.cache import PagedKVCache
    a = get_config("smollm-360m").attn     # mha layout
    tt.set_table(tt.TuningTable(sample_table()))
    assert PagedKVCache.default_block_size(a) == 32
    monkeypatch.setenv("REPRO_TUNE_BLOCK_SIZE", "8")
    assert PagedKVCache.default_block_size(a) == 8
    monkeypatch.delenv("REPRO_TUNE_BLOCK_SIZE")
    tt.set_table(None)
    assert PagedKVCache.default_block_size(a) == 16


def test_paged_create_uses_table_default():
    from repro.core.config import get_config, smoke_config
    from repro.serve.cache import PagedKVCache
    cfg = smoke_config(get_config("smollm-360m"))
    tt.set_table(tt.TuningTable(sample_table()))
    cache = PagedKVCache.create(cfg, n_blocks=4, max_reqs=1)
    assert cache.block_size == 32
    explicit = PagedKVCache.create(cfg, block_size=8, n_blocks=4,
                                   max_reqs=1)
    assert explicit.block_size == 8


# ==========================================================================
# schedule="auto": table hit > calibrated coeffs > roofline
# ==========================================================================

def test_choose_schedule_table_hit():
    tt.set_table(tt.TuningTable(sample_table()))
    # measured row says balanced is the fastest capable schedule (zigzag
    # is excluded from auto's candidate set)
    assert choose_schedule(mk.causal(), 8, Tl=256, Hq=8) == "balanced"
    # head counts not divisible by P: ulysses drops out, table still wins
    assert choose_schedule(mk.causal(), 8, Tl=256, Hq=12,
                           Hkv=12) == "balanced"


def test_choose_schedule_coeffs_fallback_at_unseen_regime():
    tt.set_table(tt.TuningTable(sample_table()))
    # P=4 has no measured row -> calibrated coefficients rank candidates;
    # must return a capable name, deterministically
    picks = {choose_schedule(mk.causal(), 4, Tl=256, Hq=8)
             for _ in range(3)}
    assert len(picks) == 1 and picks.pop() in ("balanced", "ring",
                                               "ulysses")
    # document mask at unseen P likewise
    assert choose_schedule(mk.document(), 4, Tl=256, Hq=8) in (
        "balanced", "ring", "ulysses")


def test_choose_schedule_roofline_without_table():
    tt.set_table(None)
    assert choose_schedule(mk.causal(), 1, Tl=64) == "ring"
    assert choose_schedule(mk.causal(), 8, Tl=256, Hq=8) in (
        "balanced", "ring", "ulysses")


# ==========================================================================
# calibration: feature extraction + nonneg least squares + spearman
# ==========================================================================

def test_mask_for_kind_matches_kinds():
    for kind in ("causal", "full", "sliding_window", "document",
                 "prefix_lm"):
        assert mask_for_kind(kind, T=256).kind == kind


def test_schedule_features_shapes():
    for sched in ("balanced", "ring", "ulysses"):
        f = schedule_features(sched, mask_kind="causal", P=8, seq=2048)
        assert f is not None
        assert set(f) >= {"flops", "comm_bytes", "hops", "score_elems"}
        assert all(v >= 0 for v in f.values())
    # rsa has no sliding-window path
    assert schedule_features("rsa", mask_kind="sliding_window", P=8,
                             seq=2048) is None


def test_fit_nonneg_recovers_synthetic_coeffs():
    rng = np.random.default_rng(0)
    X = np.hstack([rng.uniform(0.1, 1.0, size=(40, 3)),
                   np.ones((40, 1))])            # last column = base term
    y = X @ np.array([2.0, 0.0, 5.0, 0.3])
    w = fit_nonneg(X, y)
    assert np.all(w >= 0)
    assert float(np.max(np.abs(X @ w - y))) < 1e-6
    # a feature anti-correlated with y gets clamped to zero, not negative
    X2 = np.hstack([np.linspace(1, 2, 20)[:, None], np.ones((20, 1))])
    y2 = -3.0 * X2[:, 0] + 10.0
    w2 = fit_nonneg(X2, y2)
    assert np.all(w2 >= 0)


def test_spearman_rank_correlation():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [40, 30, 20, 10]) == pytest.approx(-1.0)
    assert abs(spearman([1, 2, 3, 4], [1, 2, 4, 3])) < 1.0


# ==========================================================================
# adaptive speculative depth (satellite of this PR)
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


@pytest.fixture(scope="module")
def served():
    from repro.core.config import ShapeSpec, get_config, smoke_config
    from repro.data.pipeline import SyntheticTokens
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    cfg = smoke_config(get_config("smollm-360m"))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("tune", 32, 4, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
    return model, params, prompts


def _streams(model, params, prompts, spec):
    from repro.serve.engine import Engine
    eng = Engine(model, params, max_batch=2, block_size=8, n_blocks=32,
                 spec=spec)
    rids = [eng.submit(prompts[i][:24 + 4 * i], max_new_tokens=8,
                       temperature=0.0) for i in range(2)]
    out = eng.run()
    return [np.asarray(out[r]) for r in rids], eng


def test_adaptive_engine_streams_token_identical(served):
    model, params, prompts = served
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
