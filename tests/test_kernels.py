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


# ------------------------------------------------------ explicit kernel tiles

def test_chunk_attn_block_hints_reach_tunable_backends():
    """Explicit tiles on the kernel entries (``ops.flash_fwd/flash_bwd``
    and chunked-lax's ``block_kv``) stay exact; a tile that does not
    divide T (96 ∤ 128) shrinks to a divisor instead of failing."""
    from functools import partial
    from repro.core import mask as mkk
    from repro.kernels import ops
    from repro.kernels.chunked import chunked_bwd, chunked_fwd
    q, k, v, do = _mk(1, 128, 256, 2, 2, 32, jnp.float32)
    m = mkk.causal(rel_offset=128)
    o_r, lse_r = chunk_attn_ref(q, k, v, mask=m)
    g_r = chunk_attn_bwd_ref(q, k, v, o_r, lse_r, do, mask=m)
    entries = {
        "chunked-lax": (lambda bq, bk: partial(chunked_fwd, block_kv=bk),
                        lambda bq, bk: partial(chunked_bwd, block_kv=bk)),
        "pallas-interpret": (
            lambda bq, bk: partial(ops.flash_fwd, block_q=bq, block_kv=bk,
                                   interpret=True),
            lambda bq, bk: partial(ops.flash_bwd, block_q=bq, block_kv=bk,
                                   interpret=True)),
    }
    for impl, (fwd, bwd) in entries.items():
        o_nd, _ = fwd(96, 96)(q, k, v, mask=m)
        assert jnp.allclose(o_r, o_nd, atol=1e-5), impl
        o_b, lse_b = fwd(64, 32)(q, k, v, mask=m)
        assert jnp.allclose(o_r, o_b, atol=1e-5), impl
        g_b = bwd(64, 32)(q, k, v, o_b, lse_b, do, mask=m)
        for a, b in zip(g_r, g_b):
            assert jnp.allclose(a, b, atol=2e-4), impl


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


# ------------------------------------------------------------- tile chooser

TILE_OPS = ("fwd", "dq", "dkv")


@pytest.mark.parametrize("op", TILE_OPS)
@pytest.mark.parametrize("D", [64, 128, 256])
@pytest.mark.parametrize("T", [8192, 1536, 640, 384, 96])
def test_flash_tiles_divide_t_align_and_fit_vmem(T, D, op):
    """The chosen tiles divide T, are multiples of 128 or all of T, and
    the step they make fits the VMEM budget."""
    from repro.core import mask as mk
    from repro.kernels import flash_attention as fa
    bq, bk = fa.flash_tiles(T, T, D, D, mk.causal(), op)
    for b in (bq, bk):
        assert T % b == 0 and (b % 128 == 0 or b == T), (b, T)
    assert fa._vmem_bytes(bq, bk, D, D, op) <= fa.VMEM_BUDGET


@pytest.mark.parametrize("op", TILE_OPS)
@pytest.mark.parametrize("T,causal_edge", [(8192, 1024), (4096, 1024),
                                           (2048, 512), (1024, 256),
                                           (256, 128), (96, 96)])
def test_flash_tiles_start_from_target_and_shrink_to_short_chunks(
        T, causal_edge, op):
    """At the head dims in use the tiles are the target or all of a
    shorter chunk; where the causal diagonal crosses the call, at most a
    quarter of it (at least 128). A causal chunk wholly after its keys has
    no diagonal in it."""
    from repro.core import mask as mk
    from repro.kernels import flash_attention as fa
    edge = min(fa.TILE_TARGET, T)
    for D in (64, 128):
        assert fa.flash_tiles(T, T, D, D, mk.full(), op) == (edge, edge)
        assert fa.flash_tiles(T, T, D, D, mk.causal(rel_offset=T),
                              op) == (edge, edge)
        assert fa.flash_tiles(T, T, D, D, mk.causal(),
                              op) == (causal_edge, causal_edge)


@pytest.mark.parametrize("window,cap", [(100, 128), (300, 256), (640, 512),
                                        (8192, 1024)])
def test_flash_tiles_follow_a_narrow_window(window, cap):
    """A sliding window narrower than the target cuts both edges to the
    window rounded down to 128 (at least 128); a wide one cuts nothing."""
    from repro.core import mask as mk
    from repro.kernels import flash_attention as fa
    for op in TILE_OPS:
        got = fa.flash_tiles(8192, 8192, 64, 64,
                             mk.sliding_window(window, causal=False), op)
        assert got == (min(cap, fa.TILE_TARGET),) * 2, op


@pytest.mark.parametrize("D", [256, 512, 1024])
def test_flash_tiles_shrink_for_wide_heads(D):
    """At head dims whose target tiles overflow the budget, the tiles
    shrink until they fit."""
    from repro.core import mask as mk
    from repro.kernels import flash_attention as fa
    for op in TILE_OPS:
        bq, bk = fa.flash_tiles(8192, 8192, D, D, mk.causal(), op)
        assert bq * bk < fa.TILE_TARGET ** 2, op
        assert fa._vmem_bytes(bq, bk, D, D, op) <= fa.VMEM_BUDGET, op


@pytest.mark.parametrize("op", TILE_OPS)
@pytest.mark.parametrize("D,kind", [(64, "causal"), (128, "causal"),
                                    (128, "unmasked")])
def test_flash_tiles_at_cell_shapes(D, kind, op):
    """The tiles of the benchmark cells' flash calls at T 8192 in bf16:
    smollm-360m's causal hd64 step, and the qwen3-8b sequence-parallel
    step's diagonal (causal) and off-diagonal (unmasked) hd128 chunks.
    Each kernel runs 1024 a side."""
    from repro.core import mask as mk
    from repro.kernels import flash_attention as fa
    mask = mk.causal() if kind == "causal" else mk.full()
    assert fa.flash_tiles(8192, 8192, D, D, mask, op, 2) == (1024, 1024)


@pytest.mark.parametrize("source", ["chooser", "hints"])
def test_flash_tile_precedence(monkeypatch, source):
    """Explicit tiles on ``ops.flash_fwd/flash_bwd`` win over the chooser;
    without them, as through chunk_attn, each kernel takes
    ``flash_tiles``'s. Each flash call records the tiles it ran with in
    its metadata."""
    from jax.experimental import pallas as pl
    from repro.core import mask as mk
    from repro.core.attention import chunk_attn, chunk_attn_bwd
    from repro.kernels import flash_attention as fa
    from repro.kernels import ops
    T, D = 1024, 64
    mask = mk.causal()
    want = {op: fa.flash_tiles(T, T, D, D, mask, op) for op in TILE_OPS}
    if source == "hints":
        want = dict.fromkeys(TILE_OPS, (256, 128))
    seen = {}
    real = pl.pallas_call

    def spy(*a, name=None, metadata=None, **kw):
        seen[name] = (metadata["block_q"], metadata["block_kv"])
        return real(*a, name=name, metadata=metadata, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    q, k, v, do = _mk(1, T, T, 2, 1, D, jnp.float32)

    def fwd_bwd(q, k, v, do):
        if source == "hints":
            kw = dict(mask=mask, interpret=True, block_q=256, block_kv=128)
            o, lse = ops.flash_fwd(q, k, v, **kw)
            return ops.flash_bwd(q, k, v, o, lse, do, **kw)
        kw = dict(mask=mask, impl="pallas-interpret")
        o, lse = chunk_attn(q, k, v, **kw)
        return chunk_attn_bwd(q, k, v, o, lse, do, **kw)

    jax.eval_shape(fwd_bwd, q, k, v, do)
    assert seen == {"flash_fwd": want["fwd"], "flash_bwd.dq": want["dq"],
                    "flash_bwd.dkv": want["dkv"]}


# (kind, dtype, head_dim): f32 at D 32 (scale 1/sqrt(32), applied to the
# logits), bf16 at hd64 (scale 1/8, a power of two: folded into the operand
# tile) and at hd128 (1/sqrt(128): on the logits)
MULTI_BLOCK_CASES = {
    "causal": ("causal", jnp.float32, 32),
    "document-boundaries": ("document-boundaries", jnp.float32, 32),
    "document-segments": ("document-segments", jnp.float32, 32),
    "causal-bf16-hd64": ("causal", jnp.bfloat16, 64),
    "document-boundaries-bf16-hd64": ("document-boundaries", jnp.bfloat16,
                                      64),
    "document-segments-bf16-hd64": ("document-segments", jnp.bfloat16, 64),
    "causal-bf16-hd128": ("causal", jnp.bfloat16, 128),
    "document-segments-bf16-hd128": ("document-segments", jnp.bfloat16,
                                     128),
}


@pytest.mark.parametrize("case", sorted(MULTI_BLOCK_CASES))
def test_default_tiles_multi_block_vs_ref(case):
    """The chooser's own tiles, several blocks a side (T = 3072: 4 × 4
    tiles of 768), against the oracle, fwd and bwd, GQA 4/2: the diagonal
    mask path at the tiles a TPU call runs with, in f32 and with bf16 MXU
    operands. With dynamic segments, one query row's document appears in
    no key of the chunk: its lse is NEG_INF, its output and dq are exactly
    0, and its q and dO leave dk and dv exactly as they are."""
    import numpy as np
    from repro.core import mask as mk
    from repro.kernels import flash_attention as fa
    kind, dtype, D = MULTI_BLOCK_CASES[case]
    B, T, Hq, Hkv = 1, 3072, 4, 2
    bnd = mk.doc_boundaries(T, 3)
    segs, row = {}, 1000
    if kind == "causal":
        mask = mk.causal()
    elif kind == "document-boundaries":
        mask = mk.document(boundaries=bnd)
    else:
        mask = mk.document()
        kv_seg = mk.segments_from_boundaries(T, bnd)[None]
        q_seg = kv_seg.copy()
        q_seg[0, row] = 7                  # a document no key belongs to
        segs = dict(q_segments=jnp.asarray(q_seg),
                    kv_segments=jnp.asarray(kv_seg))
    for op in TILE_OPS:
        bq, bk = fa.flash_tiles(T, T, D, D, mask, op, jnp.dtype(dtype).itemsize)
        assert T // bq > 1 and T // bk > 1, op
    q, k, v, do = _mk(B, T, T, Hq, Hkv, D, dtype, seed=5)
    f32 = dtype == jnp.float32
    tol_f, tol_b = (1e-5, 2e-4) if f32 else (2e-2, 5e-2)
    o_r, lse_r = chunk_attn_ref(q, k, v, mask=mask, **segs)
    o_p, lse_p = ops.flash_fwd(q, k, v, mask=mask, interpret=True, **segs)
    assert jnp.allclose(o_r.astype(jnp.float32), o_p.astype(jnp.float32),
                        atol=tol_f, rtol=tol_f), case
    assert jnp.allclose(lse_r, lse_p, atol=1e-4, rtol=1e-4), case
    ref = chunk_attn_bwd_ref(q, k, v, o_r, lse_r, do, mask=mask, **segs)
    pal = ops.flash_bwd(q, k, v, o_r.astype(dtype), lse_r, do, mask=mask,
                        interpret=True, **segs)
    for r, p_ in zip(ref, pal):
        assert jnp.allclose(r.astype(jnp.float32), p_.astype(jnp.float32),
                            atol=tol_b, rtol=tol_b), case
    if not segs:
        return
    assert bool(jnp.all(lse_p[:, row] <= fa.NEG_INF / 2))
    assert not np.any(np.asarray(o_p[:, row], np.float32))
    assert not np.any(np.asarray(pal[0][:, row], np.float32)), "dq row"
    other = ops.flash_bwd(q.at[:, row].multiply(-3), k, v, o_r.astype(dtype),
                          lse_r, do.at[:, row].multiply(5), mask=mask,
                          interpret=True, **segs)
    for a, b in zip(pal[1:], other[1:]):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), "dk/dv share"


def _branch_results(jaxpr):
    """The result shapes of every ``cond`` in ``jaxpr`` and the jaxprs
    nested in its equations, a tuple per cond (a kernel body's ``pl.when``
    is a cond with no results)."""
    from jax._src import core
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield tuple(v.aval.shape for v in eqn.outvars)
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _branch_results(sub)


@pytest.mark.parametrize("kind", ["causal", "window", "document-boundaries",
                                  "document-segments"])
def test_flash_bodies_pass_no_tile_through_a_branch(kind):
    """No flash kernel body carries a value through a branch: the mask is
    applied in line on every executed tile. A branch whose result is the
    (br, bc) score tile makes Mosaic store the tile and copy it through the
    branch on every grid step (PERF.md §6)."""
    from repro.core import mask as mk
    B, T, Hq, Hkv, D = 1, 512, 2, 1, 64
    q, k, v, do = _mk(B, T, T, Hq, Hkv, D, jnp.bfloat16)
    bnd = mk.doc_boundaries(T, 3)
    segs = {}
    if kind == "causal":
        mask = mk.causal()
    elif kind == "window":
        mask = mk.causal().replace(window=200)
    elif kind == "document-boundaries":
        mask = mk.document(boundaries=bnd)
    else:
        mask = mk.document()
        seg = jnp.asarray(mk.segments_from_boundaries(T, bnd)[None])
        segs = dict(q_segments=seg, kv_segments=seg)
    lse = jnp.zeros((B, Hq, T), jnp.float32)
    kw = dict(mask=mask, block_q=128, block_kv=128, interpret=True, **segs)

    def fwd_bwd(q, k, v, do):
        ops.flash_fwd(q, k, v, **kw)
        return ops.flash_bwd(q, k, v, q, lse, do, **kw)

    results = list(_branch_results(jax.make_jaxpr(fwd_bwd)(q, k, v,
                                                          do).jaxpr))
    assert results and all(r == () for r in results), results
