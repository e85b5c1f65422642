"""Oracle-differential tests for the paged KV cache + paged flash-decode.

Covers the ISSUE-5 acceptance surface:
  * allocator invariants (no double-alloc, owner-checked frees, free-list
    conservation, deterministic exhaustion);
  * paged-vs-dense decode differentials over randomly fragmented block
    tables (interleaved alloc/free, out-of-order blocks), for MHA / GQA /
    MLA-latent layouts and causal + sliding-window MaskSpecs, on every
    paged backend (ref / chunked-lax / pallas-interpret), to fp32
    tolerance;
  * the same differential on an 8-host-device mesh with a sharded pool;
  * per-request (B,) positions in the *dense* decode path (the satellite
    fix) + the scalar-broadcast shim's DeprecationWarning;
  * registry resolution of the ``paged`` capability flag.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mask as mk
from repro.core.attention import paged_decode_attn
from repro.kernels import registry
from repro.serve.cache import BlockAllocator, PagedKVCache, PoolExhausted
from repro.launch.mesh import make_mesh

TOL = 2e-5


# ==========================================================================
# allocator invariants
# ==========================================================================

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n_blocks=st.integers(2, 24))
def test_allocator_invariants(seed, n_blocks):
    rng = np.random.default_rng(seed)
    al = BlockAllocator(n_blocks)
    live = {}                                     # rid -> ids
    for step in range(40):
        op = rng.integers(0, 3)
        if op < 2:                                # alloc for a new rid
            rid = int(rng.integers(0, 1 << 30))
            n = int(rng.integers(1, 4))
            if al.n_free < n:
                with pytest.raises(PoolExhausted):
                    al.alloc(rid, n)
                continue
            ids = al.alloc(rid, n)
            assert len(set(ids)) == n             # no double-alloc inside
            for prev in live.values():
                assert not set(ids) & set(prev)   # ... or across requests
            live[rid] = ids
        elif live:                                # free one rid
            rid = sorted(live)[int(rng.integers(0, len(live)))]
            al.free(live.pop(rid), rid)
        al.check_conservation()
    # double free / foreign free raise
    if live:
        rid, ids = next(iter(live.items()))
        with pytest.raises(ValueError):
            al.free(ids, rid + 1)
        al.free(ids, rid)
        with pytest.raises(ValueError):
            al.free(ids, rid)


def test_allocator_exhaustion_is_atomic_and_deterministic():
    a1, a2 = BlockAllocator(8), BlockAllocator(8)
    assert a1.alloc(1, 3) == a2.alloc(1, 3)       # same sequence, same ids
    free_before = a1.n_free
    with pytest.raises(PoolExhausted):
        a1.alloc(2, free_before + 1)
    assert a1.n_free == free_before               # nothing leaked
    a1.check_conservation()


# ==========================================================================
# fragmented-table construction shared by the differentials
# ==========================================================================

def _fragmented_tables(rng, al, B, nb, lengths, bs):
    """Allocate each request's blocks with interleaved alloc/free churn so
    tables are out-of-order and non-contiguous in the pool."""
    table = np.zeros((B, nb), np.int32)
    # churn: grab and release scratch requests to scramble the free list
    for b in range(B):
        scratch = al.alloc(999_000 + b, int(rng.integers(1, 3)))
        n = -(-int(lengths[b]) // bs)
        ids = al.alloc(b, n)
        al.free(scratch, 999_000 + b)
        # the table's virtual→pool mapping is arbitrary: scramble it so the
        # differentials cover out-of-pool-order tables
        table[b, :n] = rng.permutation(ids)
    return table


def _dense_rowwise_oracle(q, k_pool, v_pool, table, lengths, mask, scale):
    """Per-row numpy softmax attention over the contiguous gather."""
    B, _, Hq, Dq = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / np.sqrt(Dq)
    out = np.zeros((B, 1, Hq, v_pool.shape[-1]), np.float32)
    for b in range(B):
        L = int(lengths[b])
        nb = -(-L // bs)
        kk = np.concatenate([np.asarray(k_pool[table[b, i]])
                             for i in range(nb)], 0)[:L]
        vv = np.concatenate([np.asarray(v_pool[table[b, i]])
                             for i in range(nb)], 0)[:L]
        kk = np.repeat(kk, g, 1)
        vv = np.repeat(vv, g, 1)
        s = np.einsum("hd,khd->hk", np.asarray(q[b, 0], np.float64),
                      kk.astype(np.float64)) * sc
        if mask.window:
            j = np.arange(L)
            s = np.where((L - 1 - j)[None, :] < mask.window, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b, 0] = np.einsum("hk,khd->hd", p, vv.astype(np.float64))
    return out


LAYOUTS = {
    # Hq, Hkv, Dq, Dv
    "mha": (4, 4, 32, 32),
    "gqa": (6, 2, 16, 16),
    "mla": (4, 1, 48, 32),    # latent layout: Dv = narrow slice of Dk
}


@pytest.mark.parametrize("impl", ["ref", "chunked-lax", "pallas-interpret"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("window", [0, 11])
def test_paged_vs_dense_decode_differential(impl, layout, window):
    rng = np.random.default_rng(hash((impl, layout, window)) % 2 ** 31)
    Hq, Hkv, Dq, Dv = LAYOUTS[layout]
    B, bs, N = 4, 8, 32
    lengths = np.array([1, 7, 23, 40], np.int64)
    nb = -(-int(lengths.max()) // bs) + 1          # extra null-padded column
    al = BlockAllocator(N)
    table = _fragmented_tables(rng, al, B, nb, lengths, bs)
    assert any(np.any(np.diff(table[b][table[b] > 0]) < 0)
               for b in range(B)), "tables should be out of pool order"
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dq)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((N, bs, Hkv, Dq)), jnp.float32)
    if layout == "mla":                            # value = narrow k view
        v_pool = k_pool[..., :Dv]
    else:
        v_pool = jnp.asarray(rng.standard_normal((N, bs, Hkv, Dv)),
                             jnp.float32)
    mask = mk.sliding_window(window) if window else mk.causal()
    scale = 1.0 / np.sqrt(Dq + 7) if layout == "mla" else None
    o = paged_decode_attn(q, k_pool, v_pool, jnp.asarray(table),
                          jnp.asarray(lengths, jnp.int32), mask=mask,
                          scale=scale, impl=impl)
    ref = _dense_rowwise_oracle(q, k_pool, v_pool, table, lengths, mask,
                                scale)
    assert np.abs(np.asarray(o, np.float32) - ref).max() < TOL


def test_paged_decode_rejects_bad_masks_and_shapes():
    q = jnp.zeros((1, 1, 4, 8))
    kp = vp = jnp.zeros((4, 4, 4, 8))
    bt = jnp.zeros((1, 1), jnp.int32)
    ln = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="causal/sliding_window"):
        paged_decode_attn(q, kp, vp, bt, ln, mask=mk.document())
    with pytest.raises(ValueError, match="offset-free"):
        paged_decode_attn(q, kp, vp, bt, ln, mask=mk.causal(rel_offset=3))
    with pytest.raises(ValueError, match="query token"):
        paged_decode_attn(jnp.zeros((1, 0, 4, 8)), kp, vp, bt, ln)


# ==========================================================================
# PagedKVCache page-in / gather round trip
# ==========================================================================

def test_cache_page_in_gather_roundtrip():
    from repro.core.config import get_config, smoke_config
    cfg = smoke_config(get_config("llama-gqa"))
    cache = PagedKVCache.create(cfg, block_size=8, n_blocks=16, max_reqs=2)
    rng = np.random.default_rng(0)
    a = cfg.attn
    L = cfg.n_layers
    # fragment: slot 1 allocated between slot 0's two assignments
    T0, T1 = 19, 10
    cache.assign(0, rid=0, n_tokens=T0)
    cache.assign(1, rid=1, n_tokens=T1)
    for slot, T in ((0, T0), (1, T1)):
        dense = {
            "k": jnp.asarray(rng.standard_normal(
                (L, 1, T, a.n_kv_heads, a.head_dim)), jnp.float32),
            "v": jnp.asarray(rng.standard_normal(
                (L, 1, T, a.n_kv_heads, a.head_dim)), jnp.float32)}
        cache.page_in(slot, dense, T)
        got = cache.gather(slot, T)
        for key in ("k", "v"):
            np.testing.assert_allclose(np.asarray(got[key]),
                                       np.asarray(dense[key][:, 0]),
                                       atol=1e-6)
    # release returns every block; conservation holds
    cache.release(0, 0)
    cache.release(1, 1)
    cache.allocator.check_conservation()
    assert cache.allocator.n_free == cache.allocator.n_usable


# ==========================================================================
# dense decode path: per-request (B,) positions (satellite fix)
# ==========================================================================

def test_dense_decode_per_request_positions():
    """Mixed-length batch against a per-row oracle — the shared-scalar
    behavior this replaces could not express this at all."""
    from repro.core.dist_attention import dist_decode_attn
    rng = np.random.default_rng(3)
    B, S, Hq, D = 3, 24, 4, 16
    mesh = make_mesh((1, 1), ("data", "model"))
    pos = np.array([5, 17, 24], np.int64)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k1 = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    v1 = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    for window in (0, 7):
        m = mk.sliding_window(window) if window else mk.causal()
        o = dist_decode_attn(q, kc, vc, k1, v1, mesh=mesh,
                             seq_axes=("model",), batch_axes=None,
                             mask=m, pos=jnp.asarray(pos, jnp.int32))
        for b in range(B):
            L = int(pos[b])
            kk = np.concatenate([np.asarray(kc[b, :L]),
                                 np.asarray(k1[b])], 0)
            vv = np.concatenate([np.asarray(vc[b, :L]),
                                 np.asarray(v1[b])], 0)
            s = np.einsum("hd,khd->hk", np.asarray(q[b, 0], np.float64),
                          kk.astype(np.float64)) / np.sqrt(D)
            if window:
                j = np.arange(L + 1)
                s = np.where((L - j)[None, :] < window, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("hk,khd->hd", p, vv.astype(np.float64))
            assert np.abs(np.asarray(o[b, 0], np.float64) - ref).max() \
                < TOL, (window, b)


def test_scalar_pos_shim_warns_once():
    from repro.core import mask as mkm
    from repro.core.dist_attention import dist_decode_attn
    site = "dist_decode_attn(pos=<scalar>)"
    mkm._DEPRECATION_WARNED.discard(site)
    mesh = make_mesh((1, 1), ("data", "model"))
    z = jnp.zeros((2, 1, 2, 4))
    zc = jnp.zeros((2, 8, 2, 4))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dist_decode_attn(z, zc, zc, z, z, mesh=mesh, seq_axes=("model",),
                         batch_axes=None, mask=mk.causal(),
                         pos=jnp.int32(8))
        dist_decode_attn(z, zc, zc, z, z, mesh=mesh, seq_axes=("model",),
                         batch_axes=None, mask=mk.causal(),
                         pos=jnp.int32(8))
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)
           and site in str(x.message)]
    assert len(dep) == 1                          # one-shot


# ==========================================================================
# registry: the paged capability flag
# ==========================================================================

def test_registry_paged_capability_and_fallback():
    for name in ("ref", "chunked-lax", "pallas", "pallas-interpret"):
        assert registry.get(name).paged, name
    assert not registry.get("null").paged
    # pallas on cpu walks its chain to a paged-capable host backend
    be = registry.resolve("pallas", "cpu", mask=mk.causal(), paged=True)
    assert be.paged and be.name in ("pallas-interpret", "chunked-lax")
    # null has no paged path and no fallback: explicit request raises
    with pytest.raises(ValueError, match="no paged"):
        registry.resolve("null", "cpu", mask=mk.causal(), paged=True)


# ==========================================================================
# 8-device mesh: sharded pool differential
# ==========================================================================

def test_paged_decode_8dev_sharded_pool(subproc):
    """The pool's block axis shards over the 8-device ``model`` axis; the
    gather crosses devices via GSPMD, and the result must equal the
    replicated single-mesh math to fp32 tolerance."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import mask as mk
from repro.core.attention import paged_decode_attn
rng = np.random.default_rng(0)
B, Hq, Hkv, Dq, bs, nb, N = 4, 4, 2, 16, 8, 4, 32
q = jnp.asarray(rng.standard_normal((B,1,Hq,Dq)), jnp.float32)
kp = jnp.asarray(rng.standard_normal((N,bs,Hkv,Dq)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((N,bs,Hkv,Dq)), jnp.float32)
bt = jnp.asarray(rng.permutation(np.arange(1, N))[:B*nb].reshape(B,nb),
                 jnp.int32)
lens = jnp.asarray([3, 9, 17, 31], jnp.int32)
mask = mk.sliding_window(13)
o_local = paged_decode_attn(q, kp, vp, bt, lens, mask=mask, impl="ref")
mesh = make_mesh((1, 8), ("data", "model"))
sh = NamedSharding(mesh, P("model", None, None, None))
kp_s, vp_s = jax.device_put(kp, sh), jax.device_put(vp, sh)
f = jax.jit(lambda *a: paged_decode_attn(*a, mask=mask, impl="ref"))
o_shard = f(q, kp_s, vp_s, bt, lens)
err = float(jnp.abs(o_shard - o_local).max())
assert err < 2e-5, err
print("OK sharded-pool err", err)
""")
    assert "OK sharded-pool" in out
