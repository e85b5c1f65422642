"""Attention backend registry: differential validation of every registered
exact backend against the pure-jnp oracle across mask regimes, rescale-math
property tests, and resolve()/fallback behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mask as mk
from repro.core.attention import (chunk_attn, chunk_attn_bwd, empty_partial,
                                  merge)
from repro.core.mask import MaskSpec
from repro.kernels import registry
from repro.kernels.ref import chunk_attn_bwd_ref, chunk_attn_ref

EXACT_BACKENDS = [n for n in registry.names() if registry.get(n).exact]

# one MaskSpec per declarative kind (plus offsets): every registered exact
# backend must serve the full kind set
MASK_CASES = {
    "causal":      mk.causal(),
    "non-causal":  mk.full(),
    "rel-offset":  mk.causal(rel_offset=96),
    "window":      mk.sliding_window(40, rel_offset=96),
    "prefix-lm":   mk.prefix_lm(24),
    "document":    mk.document(boundaries=(0, 40, 100, 180)),
    "doc-window":  mk.document(boundaries=(0, 40, 100, 180), window=64),
}


# Tk > chunked.DEFAULT_BLOCK_KV so the chunked-lax legs exercise the real
# blocked-scan path (nb > 1), not its single-block early return
def _mk(seed=0, B=1, Tq=64, Tk=256, Hq=4, Hkv=2, D=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Tq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Tk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Tk, Hkv, D), dtype)
    do = jax.random.normal(ks[3], (B, Tq, Hq, D), dtype)
    return q, k, v, do


@pytest.mark.parametrize("mask", MASK_CASES, ids=list(MASK_CASES))
@pytest.mark.parametrize("backend", EXACT_BACKENDS)
def test_backend_matches_ref(backend, mask):
    """Every registered exact backend × every MaskSpec kind agrees with the
    oracle within fp32 tolerance, forward and backward. ``pallas`` resolves
    through its CPU fallback chain here — that path must stay exact too."""
    kw = dict(mask=MASK_CASES[mask])
    q, k, v, do = _mk()
    o_r, l_r = chunk_attn_ref(q, k, v, **kw)
    o_b, l_b = chunk_attn(q, k, v, impl=backend, **kw)
    np.testing.assert_allclose(np.asarray(o_b), np.asarray(o_r), atol=1e-5)
    m = (l_r > -1e29) | (l_b > -1e29)
    np.testing.assert_allclose(np.asarray(jnp.where(m, l_b, 0)),
                               np.asarray(jnp.where(m, l_r, 0)), atol=1e-4)
    g_r = chunk_attn_bwd_ref(q, k, v, o_r, l_r, do, **kw)
    g_b = chunk_attn_bwd(q, k, v, o_b, l_b, do, impl=backend, **kw)
    for a, b in zip(g_b, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("backend",
                         [n for n in EXACT_BACKENDS if n != "ref"])
def test_backend_gqa_and_asymmetric_dv(backend):
    """GQA grouping and MLA-style Dk != Dv shapes survive every backend."""
    q, k, _, _ = _mk(seed=3, Hq=4, Hkv=2, D=48)
    v = jax.random.normal(jax.random.PRNGKey(9), (1, 256, 2, 24))
    o_r, l_r = chunk_attn_ref(q, k, v, mask=mk.causal(), scale=0.2)
    o_b, l_b = chunk_attn(q, k, v, mask=mk.causal(), scale=0.2,
                          impl=backend)
    np.testing.assert_allclose(np.asarray(o_b), np.asarray(o_r), atol=1e-5)


def test_chunked_lax_block_picking_and_odd_lengths():
    """Block selection avoids the degenerate near-token-level scan for
    prime-ish KV lengths (falls back to single-block), and the backend
    stays exact at a non-power-of-two length."""
    from repro.kernels.chunked import _pick_block
    assert _pick_block(256, 128) == 128      # clean blocking
    assert _pick_block(96, 128) == 96        # Tk smaller than target
    assert _pick_block(257, 128) == 257      # prime: single block, no bc=1
    assert _pick_block(262, 128) == 262      # 2×131: single block, no bc=2
    q, _, _, do = _mk(seed=5)
    k = jax.random.normal(jax.random.PRNGKey(6), (1, 257, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(7), (1, 257, 2, 32))
    o_r, l_r = chunk_attn_ref(q, k, v, mask=mk.causal(200))
    o_b, l_b = chunk_attn(q, k, v, mask=mk.causal(200),
                          impl="chunked-lax")
    np.testing.assert_allclose(np.asarray(o_b), np.asarray(o_r), atol=1e-5)


# ------------------------------------------------------------ rescale math

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.sampled_from([2, 3, 4, 5]))
def test_merge_associative_and_order_independent(seed, n):
    """Any merge order/association of the per-chunk partials is identical —
    the invariant that lets the balanced schedule fold helper results in as
    they arrive."""
    B, T, H, D = 1, 8, 2, 4
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, T, H, D))
    parts = []
    for i in range(n):
        k = jax.random.normal(jax.random.fold_in(rng, 2 * i + 1),
                              (B, T, H, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2 * i + 2),
                              (B, T, H, D))
        parts.append(chunk_attn_ref(q, k, v))
    # left fold in order
    o1, l1 = parts[0]
    for o, l in parts[1:]:
        o1, l1 = merge(o1, l1, o, l)
    # fold in a seed-dependent permuted order with different association
    order = list(np.random.RandomState(seed).permutation(n))
    o2, l2 = empty_partial(q)
    for i in order:
        o2, l2 = merge(*parts[i], o2, l2)       # also flips argument order
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_mask_partial_is_merge_identity(seed):
    """mask_partial(False, ·) produces the identity element of merge."""
    from repro.core.attention import mask_partial
    B, T, H, D = 1, 8, 2, 4
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, T, H, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, H, D))
    o, lse = chunk_attn_ref(q, k, k)
    om, lm = mask_partial(jnp.bool_(False), o, lse)
    e_o, e_l = empty_partial(q)
    np.testing.assert_allclose(np.asarray(om), np.asarray(e_o))
    np.testing.assert_allclose(np.asarray(lm), np.asarray(e_l))
    o2, l2 = merge(om, lm, o, lse)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l2), np.asarray(lse), atol=1e-6)


# ------------------------------------------------------- resolve / fallback

def test_resolve_pallas_on_cpu_downgrades_not_crashes():
    be = registry.resolve("pallas", platform="cpu")
    assert be.name in ("pallas-interpret", "chunked-lax", "ref")
    assert be.unsupported_reason(platform="cpu") is None
    # the downgrade is recorded (logged once per triple)
    assert ("pallas", be.name, "cpu") in registry._WARNED


def test_resolve_on_tpu_keeps_pallas():
    assert registry.resolve("pallas", platform="tpu").name == "pallas"


def test_resolve_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown attention backend"):
        registry.resolve("cudnn-flash")


def test_resolve_name_normalization():
    """Pre-registry spelling (underscores) still resolves."""
    assert registry.resolve("pallas_interpret", platform="cpu").name == \
        "pallas-interpret"


def test_resolve_default_roundtrip():
    """impl=None resolves to the platform's default backend."""
    assert registry.resolve(None).name == registry.default_name()
    for platform in ("cpu", "gpu", "tpu"):
        assert (registry.resolve(None, platform=platform).name
                == registry.default_name(platform))


def test_default_backend_follows_platform():
    assert registry.resolve(None, platform="tpu").name == "pallas"
    assert registry.resolve(None, platform="cpu").name == "ref"
    assert registry.default_name("cpu") == "ref"
    assert registry.resolve(None, platform="tpu", mask=mk.document(),
                            paged=False).name == "pallas"
    assert registry.resolve(None, platform="tpu", mask=mk.causal(),
                            paged=True).name == "pallas"


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_pallas_never_falls_back_to_interpreter(platform):
    """pallas-interpret runs only when named; no chain reaches it."""
    for name in registry.names():
        assert "pallas-interpret" not in registry.get(name).fallback, name
    for kw in ({}, {"paged": True, "mask": mk.causal()},
               {"dynamic_offsets": True}):
        assert registry.resolve("pallas", platform=platform,
                                **kw).name != "pallas-interpret"
    assert registry.resolve("pallas-interpret",
                            platform=platform).name == "pallas-interpret"


def test_tpu_fallback_to_exact_compiled_backend_only():
    # traced offsets: pallas cannot serve them, chunked-lax (compiled) can
    assert registry.resolve("pallas", platform="tpu",
                            dynamic_offsets=True).name == "chunked-lax"
    only_ref = registry.BackendSpec(
        name="tpu-only-ref-fallback-test", fwd=lambda *a, **k: None,
        bwd=lambda *a, **k: None, platforms=("tpu",),
        mask_kinds=frozenset({"causal"}), fallback=("ref",))
    registry.register(only_ref, overwrite=True)
    try:
        with pytest.raises(ValueError, match="no runnable attention backend"):
            registry.resolve("tpu-only-ref-fallback-test", platform="tpu",
                             mask=mk.document())
        # off the TPU the same chain may end on the oracle
        assert registry.resolve("tpu-only-ref-fallback-test", platform="cpu",
                                mask=mk.causal()).name == "ref"
    finally:
        registry._REGISTRY.pop("tpu-only-ref-fallback-test", None)
    # naming ref on TPU is a caller's choice, not a fallback
    assert registry.resolve("ref", platform="tpu").name == "ref"


def test_null_backend_is_marked_inexact_and_never_a_fallback():
    assert not registry.get("null").exact
    for name in registry.names():
        assert "null" not in registry.get(name).fallback, name


def test_capability_flags_reported():
    spec = registry.get("chunked-lax")
    assert spec.mask_kinds == frozenset(
        {"causal", "sliding_window", "prefix_lm", "document"})
    assert "cpu" in spec.platforms and "tpu" in spec.platforms
    assert registry.get("pallas").platforms == ("tpu",)


def test_resolve_matches_on_mask_kinds():
    """resolve() falls back when a backend lacks a required mask kind."""
    limited = registry.BackendSpec(
        name="no-docs-test", fwd=lambda *a, **k: None,
        bwd=lambda *a, **k: None,
        mask_kinds=frozenset({"causal", "sliding_window"}),
        fallback=("ref",))
    registry.register(limited, overwrite=True)
    try:
        got = registry.resolve("no-docs-test", platform="cpu",
                               mask=mk.document())
        assert got.name == "ref"
        assert registry.resolve("no-docs-test", platform="cpu",
                                mask=mk.causal()).name == "no-docs-test"
        reason = limited.unsupported_reason(platform="cpu",
                                            mask=mk.prefix_lm(8))
        assert "prefix_lm" in reason
    finally:
        registry._REGISTRY.pop("no-docs-test", None)


def test_legacy_kwargs_removed():
    """The pre-MaskSpec kwarg triple is gone from chunk_attn: passing any
    of causal/rel_offset/window — alone or alongside mask= — raises
    ``TypeError`` with the migration hint, and ``mask=None`` keeps its
    full-attention default."""
    q, k, v, _ = _mk(seed=8)
    for kw in (dict(causal=True), dict(window=40), dict(rel_offset=96),
               dict(causal=True, rel_offset=96, window=40),
               dict(mask=mk.causal(), causal=True)):
        with pytest.raises(TypeError, match="was removed.*mask="):
            chunk_attn(q, k, v, impl="ref", **kw)
    o_none, _ = chunk_attn(q, k, v, impl="ref")
    o_full, _ = chunk_attn(q, k, v, mask=mk.full(), impl="ref")
    np.testing.assert_allclose(np.asarray(o_none), np.asarray(o_full))
