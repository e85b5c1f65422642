"""Per-kernel validation: Pallas flash-attention (interpret mode) vs the
pure-jnp oracle, swept over shapes / dtypes / masks / GQA groupings."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.ref import chunk_attn_ref, chunk_attn_bwd_ref

CASES = [
    # (B, Tq, Tk, Hq, Hkv, D, causal, rel, window, dtype)
    (1, 128, 128, 2, 2, 64, True, 0, 0, jnp.float32),
    (2, 128, 128, 4, 2, 64, False, 256, 0, jnp.float32),
    (1, 256, 128, 2, 1, 32, False, 512, 300, jnp.float32),
    (1, 64, 64, 2, 2, 16, True, 0, 0, jnp.float32),
    (1, 128, 256, 8, 8, 128, False, 512, 0, jnp.float32),
    (2, 128, 128, 2, 2, 64, True, 0, 100, jnp.float32),
    (1, 128, 128, 2, 2, 64, True, 0, 0, jnp.bfloat16),
    (1, 256, 256, 3, 1, 64, True, 0, 0, jnp.float32),  # odd heads (33H case)
]


def _mk(B, Tq, Tk, Hq, Hkv, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Tq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Tk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Tk, Hkv, D), dtype)
    do = jax.random.normal(ks[3], (B, Tq, Hq, D), dtype)
    return q, k, v, do


@pytest.mark.parametrize("case", CASES)
def test_flash_fwd_matches_ref(case):
    B, Tq, Tk, Hq, Hkv, D, causal, rel, window, dtype = case
    q, k, v, _ = _mk(B, Tq, Tk, Hq, Hkv, D, dtype)
    o_r, lse_r = chunk_attn_ref(q, k, v, causal=causal, q_offset=rel,
                                window=window)
    o_p, lse_p = ops.flash_fwd(q, k, v, causal=causal, rel_offset=rel,
                               window=window, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert jnp.allclose(o_r.astype(jnp.float32), o_p.astype(jnp.float32),
                        atol=tol, rtol=tol)
    m = (lse_r > -1e29) | (lse_p > -1e29)
    assert jnp.allclose(jnp.where(m, lse_r, 0), jnp.where(m, lse_p, 0),
                        atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", CASES)
def test_flash_bwd_matches_ref(case):
    B, Tq, Tk, Hq, Hkv, D, causal, rel, window, dtype = case
    q, k, v, do = _mk(B, Tq, Tk, Hq, Hkv, D, dtype)
    o, lse = chunk_attn_ref(q, k, v, causal=causal, q_offset=rel,
                            window=window)
    ref = chunk_attn_bwd_ref(q, k, v, o, lse, do, causal=causal,
                             q_offset=rel, window=window)
    pal = ops.flash_bwd(q, k, v, o, lse, do, causal=causal, rel_offset=rel,
                        window=window, interpret=True)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    for r, p_ in zip(ref, pal):
        assert jnp.allclose(r.astype(jnp.float32), p_.astype(jnp.float32),
                            atol=tol, rtol=tol)


def test_kernel_mla_asymmetric_dims():
    """MLA head shapes: Dk=192-like != Dv (here 48/24), custom scale."""
    q, k, _, _ = _mk(1, 128, 128, 4, 4, 48, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 4, 24))
    o_r, l_r = chunk_attn_ref(q, k, v, causal=True, scale=0.2)
    o_p, l_p = ops.flash_fwd(q, k, v, causal=True, scale=0.2, interpret=True)
    assert jnp.allclose(o_r, o_p, atol=1e-5)
    do = jax.random.normal(jax.random.PRNGKey(8), o_r.shape)
    r = chunk_attn_bwd_ref(q, k, v, o_r, l_r, do, causal=True, scale=0.2)
    p_ = ops.flash_bwd(q, k, v, o_p, l_p, do, causal=True, scale=0.2,
                       interpret=True)
    for a, b in zip(r, p_):
        assert jnp.allclose(a, b, atol=2e-4)


def test_kernel_block_sizes():
    """Non-default BlockSpec tilings agree with the oracle."""
    q, k, v, _ = _mk(1, 256, 256, 2, 2, 64, jnp.float32)
    o_r, _ = chunk_attn_ref(q, k, v, causal=True)
    for bq, bk in [(64, 128), (128, 64), (256, 256), (64, 64)]:
        o_p, _ = ops.flash_fwd(q, k, v, causal=True, block_q=bq, block_kv=bk,
                               interpret=True)
        assert jnp.allclose(o_r, o_p, atol=1e-5), (bq, bk)


# --------------------------------------------------- block-sparse pruning

# causal × window × rel_offset sweep for the pruned grids, including odd
# nq/nk, Tq != Tk, GQA g > 1, and the all-masked / all-unmasked range edges
PRUNE_CASES = [
    # (B, Tq, Tk, Hq, Hkv, D, causal, rel, window, bq, bk)
    (1, 192, 320, 4, 2, 32, True, 0, 0, 64, 64),     # odd nq/nk trapezoid
    (1, 192, 320, 4, 2, 32, True, 128, 48, 64, 64),  # causal + rel + window
    (1, 128, 256, 2, 1, 32, False, 256, 96, 64, 64),  # windowed ring step
    (1, 128, 128, 2, 2, 32, True, -128, 0, 64, 64),  # all blocks masked
    (1, 128, 128, 2, 2, 32, False, 0, 0, 64, 64),    # no mask: prune = noop
    (1, 128, 128, 2, 2, 32, True, -64, 0, 64, 64),   # leading rows masked
    (1, 64, 256, 2, 2, 32, True, 192, 64, 64, 64),   # single-q-block band
    (1, 128, 192, 3, 3, 16, True, 32, 80, 32, 64),   # br != bc, odd heads
]


def _prune_ids(c):
    B, Tq, Tk, Hq, Hkv, D, causal, rel, window, bq, bk = c
    return (f"Tq{Tq}-Tk{Tk}-g{Hq // Hkv}-c{int(causal)}-r{rel}-w{window}"
            f"-b{bq}x{bk}")


@pytest.mark.parametrize("case", PRUNE_CASES, ids=_prune_ids)
def test_pruned_flash_fwd_matches_ref_and_dense(case):
    """Pruned Pallas grids are exact vs the oracle AND bit-consistent with
    the dense (prune=False) sweep of the same kernel."""
    B, Tq, Tk, Hq, Hkv, D, causal, rel, window, bq, bk = case
    q, k, v, _ = _mk(B, Tq, Tk, Hq, Hkv, D, jnp.float32)
    o_r, lse_r = chunk_attn_ref(q, k, v, causal=causal, q_offset=rel,
                                window=window)
    kw = dict(causal=causal, rel_offset=rel, window=window, block_q=bq,
              block_kv=bk, interpret=True)
    o_p, lse_p = ops.flash_fwd(q, k, v, **kw)
    o_d, lse_d = ops.flash_fwd(q, k, v, prune=False, **kw)
    assert jnp.allclose(o_r, o_p, atol=1e-5, rtol=1e-5)
    m = (lse_r > -1e29) | (lse_p > -1e29)
    assert jnp.allclose(jnp.where(m, lse_r, 0), jnp.where(m, lse_p, 0),
                        atol=1e-4, rtol=1e-4)
    assert jnp.allclose(o_p, o_d, atol=1e-6), "prune changed the result"
    assert jnp.allclose(lse_p, lse_d, atol=1e-6)


@pytest.mark.parametrize("case", PRUNE_CASES, ids=_prune_ids)
def test_pruned_flash_bwd_matches_ref_and_dense(case):
    B, Tq, Tk, Hq, Hkv, D, causal, rel, window, bq, bk = case
    q, k, v, do = _mk(B, Tq, Tk, Hq, Hkv, D, jnp.float32)
    o, lse = chunk_attn_ref(q, k, v, causal=causal, q_offset=rel,
                            window=window)
    ref = chunk_attn_bwd_ref(q, k, v, o, lse, do, causal=causal,
                             q_offset=rel, window=window)
    kw = dict(causal=causal, rel_offset=rel, window=window, block_q=bq,
              block_kv=bk, interpret=True)
    pal = ops.flash_bwd(q, k, v, o, lse, do, **kw)
    den = ops.flash_bwd(q, k, v, o, lse, do, prune=False, **kw)
    for r, p_, d_ in zip(ref, pal, den):
        assert jnp.allclose(r, p_, atol=2e-4, rtol=2e-4)
        assert jnp.allclose(p_, d_, atol=1e-6), "prune changed the result"


@pytest.mark.parametrize("case", PRUNE_CASES, ids=_prune_ids)
def test_pruned_chunked_lax_matches_ref(case):
    """The chunked-lax backend prunes its KV scan with the identical
    block-range logic — exact vs the oracle on the same sweep."""
    from repro.kernels.chunked import chunked_bwd, chunked_fwd
    B, Tq, Tk, Hq, Hkv, D, causal, rel, window, bq, bk = case
    q, k, v, do = _mk(B, Tq, Tk, Hq, Hkv, D, jnp.float32)
    o_r, lse_r = chunk_attn_ref(q, k, v, causal=causal, q_offset=rel,
                                window=window)
    kw = dict(causal=causal, rel_offset=rel, window=window, block_kv=bk)
    o_c, lse_c = chunked_fwd(q, k, v, **kw)
    o_d, lse_d = chunked_fwd(q, k, v, prune=False, **kw)
    assert jnp.allclose(o_r, o_c, atol=1e-5, rtol=1e-5)
    assert jnp.allclose(o_c, o_d, atol=1e-6)
    m = (lse_r > -1e29) | (lse_c > -1e29)
    assert jnp.allclose(jnp.where(m, lse_r, 0), jnp.where(m, lse_c, 0),
                        atol=1e-4, rtol=1e-4)
    g_r = chunk_attn_bwd_ref(q, k, v, o_r, lse_r, do, causal=causal,
                             q_offset=rel, window=window)
    g_c = chunked_bwd(q, k, v, o_c, lse_c, do, **kw)
    g_d = chunked_bwd(q, k, v, o_c, lse_c, do, prune=False, **kw)
    for r, c_, d_ in zip(g_r, g_c, g_d):
        assert jnp.allclose(r, c_, atol=2e-4, rtol=2e-4)
        assert jnp.allclose(c_, d_, atol=1e-6)


def test_pruned_grid_is_smaller_where_mask_allows():
    """The windowed regimes actually shrink the sequential grid dimension
    (not just skip compute): seq_grid < nk."""
    from repro.core.mask import MaskSpec
    from repro.kernels.block_sparse import kv_profile
    p = kv_profile(nq=8, nk=8, br=128, bc=128,
                   mask=MaskSpec(window=512, q_offset=1024))
    assert 0 < p.seq_grid < 8
    assert p.executed_steps < p.launched_steps < p.full_steps


# --------------------------------------------- MaskSpec kinds in the kernels

@pytest.mark.parametrize("kind", ["document-boundaries", "document-segments",
                                  "document-window", "prefix-lm"])
def test_mask_kinds_flash_vs_ref(kind):
    """The new MaskSpec kinds (document / prefix_lm) are exact vs the
    oracle in the Pallas kernels (interpret), pruned AND dense, fwd + bwd,
    with GQA."""
    import numpy as np
    from repro.core import mask as mk
    B, Tq, Tk, Hq, Hkv, D = 2, 192, 192, 4, 2, 32
    q, k, v, do = _mk(B, Tq, Tk, Hq, Hkv, D, jnp.float32, seed=11)
    bnd = mk.doc_boundaries(Tk, 4)
    seg = jnp.asarray(np.tile(mk.segments_from_boundaries(Tk, bnd), (B, 1)))
    segs = {}
    if kind == "document-boundaries":
        mask = mk.document(boundaries=bnd)
    elif kind == "document-segments":
        mask = mk.document()
        segs = dict(q_segments=seg, kv_segments=seg)
    elif kind == "document-window":
        mask = mk.document(boundaries=bnd, window=48)
    else:
        mask = mk.prefix_lm(70)
    o_r, lse_r = chunk_attn_ref(q, k, v, mask=mask, **segs)
    kw = dict(mask=mask, block_q=64, block_kv=64, interpret=True, **segs)
    o_p, lse_p = ops.flash_fwd(q, k, v, **kw)
    o_d, lse_d = ops.flash_fwd(q, k, v, prune=False, **kw)
    assert jnp.allclose(o_r, o_p, atol=1e-5, rtol=1e-5), kind
    m = (lse_r > -1e29) | (lse_p > -1e29)
    assert jnp.allclose(jnp.where(m, lse_r, 0), jnp.where(m, lse_p, 0),
                        atol=1e-4, rtol=1e-4)
    assert jnp.allclose(o_p, o_d, atol=1e-6), "prune changed the result"
    ref = chunk_attn_bwd_ref(q, k, v, o_r, lse_r, do, mask=mask, **segs)
    pal = ops.flash_bwd(q, k, v, o_r, lse_r, do, **kw)
    den = ops.flash_bwd(q, k, v, o_r, lse_r, do, prune=False, **kw)
    for r, p_, d_ in zip(ref, pal, den):
        assert jnp.allclose(r, p_, atol=2e-4, rtol=2e-4), kind
        assert jnp.allclose(p_, d_, atol=1e-6), kind


@pytest.mark.parametrize("kind", ["document-boundaries", "document-segments",
                                  "document-window", "prefix-lm"])
def test_mask_kinds_chunked_vs_ref(kind):
    """Same MaskSpec-kind sweep through the chunked-lax scan."""
    import numpy as np
    from repro.core import mask as mk
    from repro.kernels.chunked import chunked_bwd, chunked_fwd
    B, Tq, Tk, Hq, Hkv, D = 2, 128, 256, 4, 2, 32
    q, k, v, do = _mk(B, Tq, Tk, Hq, Hkv, D, jnp.float32, seed=12)
    bnd = mk.doc_boundaries(Tk, 4)
    seg_k = jnp.asarray(np.tile(mk.segments_from_boundaries(Tk, bnd),
                                (B, 1)))
    seg_q = seg_k[:, :Tq]
    segs = {}
    if kind == "document-boundaries":
        mask = mk.document(boundaries=bnd)
    elif kind == "document-segments":
        mask = mk.document()
        segs = dict(q_segments=seg_q, kv_segments=seg_k)
    elif kind == "document-window":
        mask = mk.document(boundaries=bnd, window=48)
    else:
        mask = mk.prefix_lm(70)
    o_r, lse_r = chunk_attn_ref(q, k, v, mask=mask, **segs)
    o_c, lse_c = chunked_fwd(q, k, v, mask=mask, block_kv=64, **segs)
    o_d, _ = chunked_fwd(q, k, v, mask=mask, block_kv=64, prune=False,
                         **segs)
    assert jnp.allclose(o_r, o_c, atol=1e-5, rtol=1e-5), kind
    assert jnp.allclose(o_c, o_d, atol=1e-6), kind
    g_r = chunk_attn_bwd_ref(q, k, v, o_r, lse_r, do, mask=mask, **segs)
    g_c = chunked_bwd(q, k, v, o_c, lse_c, do, mask=mask, block_kv=64,
                      **segs)
    for r, c_ in zip(g_r, g_c):
        assert jnp.allclose(r, c_, atol=2e-4, rtol=2e-4), kind


# ------------------------------------------------------ block tuning surface

def test_chunk_attn_block_hints_reach_tunable_backends():
    """block_q/block_kv flow through chunk_attn to tunable backends and
    stay exact; non-tunable backends silently drop the hints."""
    from repro.core import mask as mkk
    from repro.core.attention import chunk_attn, chunk_attn_bwd
    q, k, v, do = _mk(1, 128, 256, 2, 2, 32, jnp.float32)
    m = mkk.causal(rel_offset=128)
    o_r, lse_r = chunk_attn_ref(q, k, v, mask=m)
    for impl in ("chunked-lax", "pallas-interpret", "ref"):
        # non-dividing hints (96 ∤ 128) must shrink to a divisor, not crash
        o_nd, _ = chunk_attn(q, k, v, mask=m, impl=impl, block_q=96,
                             block_kv=96)
        assert jnp.allclose(o_r, o_nd, atol=1e-5), impl
        o_b, lse_b = chunk_attn(q, k, v, mask=m, impl=impl, block_q=64,
                                block_kv=32)
        assert jnp.allclose(o_r, o_b, atol=1e-5), impl
        g_r = chunk_attn_bwd_ref(q, k, v, o_r, lse_r, do, mask=m)
        g_b = chunk_attn_bwd(q, k, v, o_b, lse_b, do, mask=m, impl=impl,
                             block_q=64, block_kv=32)
        for a, b in zip(g_r, g_b):
            assert jnp.allclose(a, b, atol=2e-4), impl


def test_registry_tunable_flag():
    from repro.kernels import registry
    assert registry.get("pallas").tunable_blocks
    assert registry.get("pallas-interpret").tunable_blocks
    assert registry.get("chunked-lax").tunable_blocks
    assert not registry.get("ref").tunable_blocks
    assert not registry.get("null").tunable_blocks


def test_kernel_ref_grad_consistency():
    """ref bwd == jax.grad through monolithic softmax attention."""
    from repro.kernels.ref import full_attn_ref
    q, k, v, _ = _mk(1, 64, 64, 2, 2, 32, jnp.float32)

    def loss(q, k, v):
        return jnp.sum(full_attn_ref(q, k, v, causal=True) ** 2)

    dq_a, dk_a, dv_a = jax.grad(loss, (0, 1, 2))(q, k, v)
    o, lse = chunk_attn_ref(q, k, v, causal=True)
    dq, dk, dv = chunk_attn_bwd_ref(q, k, v, o, lse, 2 * o, causal=True)
    assert jnp.allclose(dq, dq_a, atol=1e-4)
    assert jnp.allclose(dk, dk_a, atol=1e-4)
    assert jnp.allclose(dv, dv_a, atol=1e-4)
