"""Schedule-plan IR tests.

Three layers:

1. **Property tests** (pure python, no devices): every causal plan covers
   each (q-chunk × kv-chunk) causal pair **exactly once** for P ∈ 1..8 —
   even and odd P, zigzag's 2P half-chunking included — via the
   ``plan_coverage`` simulator, which walks the executor's routing and
   evaluates every Work item's mask exactly as the kernel would.  Windowed
   and document plans additionally prove that **skipped steps are
   provably all-masked**: coverage still equals the global mask exactly
   even though steps were dropped.

2. **Differential tests vs the frozen seed implementations**
   (core/legacy_schedules.py): the plan executors reproduce the
   hand-written ring/balanced/zigzag loops bit-for-bit on 8 host devices,
   forward and backward, causal and document.

3. **Oracle differentials for the new capabilities**: windowed
   balanced/zigzag (strictly fewer ring steps than causal), static
   document boundaries on the ring family (no segment arrays shipped),
   and ``schedule="auto"`` resolution across every supported mask kind,
   forward and grads, on 1- and 8-device meshes.
"""
import numpy as np
import pytest

from repro.core import mask as mk
from repro.core import schedule as sp


# --------------------------------------------------------------------------
# 1. Exactly-once coverage properties (no devices needed)
# --------------------------------------------------------------------------

def _assert_exact(plan, segments=None):
    T = plan.P * plan.Tl
    cov = sp.plan_coverage(plan, segments=segments)
    truth = sp.global_allow(plan.mask, T, segments=segments).astype(np.int64)
    assert np.array_equal(cov, truth), (
        plan.name, plan.P, plan.mask,
        np.argwhere(cov != truth)[:4].tolist())


@pytest.mark.parametrize("sched", ["ring", "balanced", "zigzag"])
@pytest.mark.parametrize("P", list(range(1, 9)))
def test_causal_coverage_exactly_once(sched, P):
    """ACCEPTANCE: every causal (q, kv) pair computed exactly once, and no
    non-causal pair ever, for P ∈ 1..8 (zigzag splits into 2P chunks)."""
    _assert_exact(sp.build_plan(sched, mk.causal(), P, 8))


@pytest.mark.parametrize("sched", ["ring", "balanced", "zigzag"])
@pytest.mark.parametrize("P", [1, 3, 4, 7, 8])
@pytest.mark.parametrize("w", [1, 3, 9, 24, 1000])
def test_windowed_coverage_and_step_skipping(sched, P, w):
    """Windowed plans skip provably all-masked steps — coverage stays
    exactly-once against the banded global mask, and the executed step
    count shrinks when the window allows."""
    m = mk.sliding_window(w)
    plan = sp.build_plan(sched, m, P, 8)
    _assert_exact(plan)
    assert plan.exec_steps <= plan.total_steps
    if P >= 4 and w <= 3:
        # window inside one chunk: at most the distance-1 neighbours remain
        causal_steps = sp.build_plan(sched, mk.causal(), P, 8).exec_steps
        assert plan.exec_steps < causal_steps, (sched, P, w)


@pytest.mark.parametrize("sched", ["ring", "balanced", "zigzag"])
@pytest.mark.parametrize("P", [1, 2, 5, 8])
@pytest.mark.parametrize("n_docs", [1, 3, 6])
def test_document_boundary_coverage_and_pruning(sched, P, n_docs):
    """Static document boundaries: coverage is exact with no segment
    arrays at all, and steps no document spans are statically pruned."""
    Tl = 8
    T = P * Tl
    bnd = mk.doc_boundaries(T, n_docs)
    m = mk.document(boundaries=bnd)
    plan = sp.build_plan(sched, m, P, Tl)
    _assert_exact(plan)
    if sched in ("ring", "balanced") and P == 8 and n_docs == 6:
        # short docs cannot span distant chunk pairs: steps must drop
        assert plan.exec_steps < plan.total_steps


@pytest.mark.parametrize("sched", ["ring", "balanced", "zigzag"])
@pytest.mark.parametrize("P", [2, 5, 8])
def test_dynamic_segment_coverage(sched, P):
    """Dynamic (runtime segment-ID) document masks: the plan can't prune,
    but per-step segment shipping still yields exactly-once coverage."""
    Tl = 8
    T = P * Tl
    seg = mk.segments_from_boundaries(T, mk.doc_boundaries(T, 4))
    plan = sp.build_plan(sched, mk.document(), P, Tl)
    _assert_exact(plan, segments=seg)
    assert plan.exec_steps == plan.total_steps   # nothing provable


def test_windowed_document_combined_coverage():
    """window ∧ document compose: both pruning sources apply."""
    P, Tl = 8, 8
    bnd = mk.doc_boundaries(P * Tl, 4)
    m = mk.document(boundaries=bnd, window=10)
    for sched in ("ring", "balanced", "zigzag"):
        plan = sp.build_plan(sched, m, P, Tl)
        _assert_exact(plan)
        assert plan.exec_steps < plan.total_steps, sched


def test_full_mask_ring_coverage():
    """Bidirectional (encoder) ring: P steps cover everything once."""
    for P in (1, 3, 8):
        _assert_exact(sp.build_plan("ring", mk.full(), P, 8))


def test_plan_static_shape_properties():
    """Plan bookkeeping the benchmarks publish: step counts, kernel
    calls, container usage."""
    p_c = sp.build_plan("balanced", mk.causal(), 8, 8)
    assert (p_c.exec_steps, p_c.total_steps) == (4, 4)
    assert p_c.ship_q and p_c.uses_ring
    p_w = sp.build_plan("balanced", mk.sliding_window(17), 8, 8)
    assert p_w.exec_steps == 2 and not p_w.ship_q  # helper-free band
    p_z = sp.build_plan("zigzag", mk.causal(), 8, 8)
    assert p_z.n_chunks == 2 and not p_z.ship_q
    p_r = sp.build_plan("ring", mk.sliding_window(1), 8, 8)
    assert p_r.exec_steps == 0                     # diagonal-only window
    # multi-hop shift folding: skipped steps accumulate into shifts
    p_zw = sp.build_plan("zigzag", mk.sliding_window(9), 8, 16)
    assert sum(s.shift for s in p_zw.steps) <= p_zw.total_steps
    assert p_zw.exec_steps < p_zw.total_steps


def test_plan_cost_model_sanity():
    """Cost model: windowed plans are strictly cheaper than causal on the
    same schedule; balanced ships more bytes but runs fewer steps than
    ring; auto picks a capable schedule for every supported kind."""
    kw = dict(B=1, Hq=8, Hkv=8, Dqk=64, Dv=64, bpe=2)
    c_bal = sp.build_plan("balanced", mk.causal(), 8, 1024).cost(**kw)
    c_ring = sp.build_plan("ring", mk.causal(), 8, 1024).cost(**kw)
    assert c_bal.exec_steps < c_ring.exec_steps
    assert c_bal.flops_fwd < c_ring.flops_fwd      # helpers rebalance
    w_bal = sp.build_plan("balanced", mk.sliding_window(512), 8,
                          1024).cost(**kw)
    assert w_bal.flops_fwd < c_bal.flops_fwd
    assert w_bal.comm_bytes_fwd < c_bal.comm_bytes_fwd
    t = c_bal.time_estimate()
    assert t["step_s_lower_bound"] >= max(0.0, t["compute_s"] * 0.99)
    for m, seg in [(mk.causal(), False), (mk.sliding_window(64), False),
                   (mk.full(), False), (mk.document(), True),
                   (mk.document(boundaries=(0, 512)), False)]:
        name = sp.choose_schedule(m, 8, Tl=1024, Hq=6, Hkv=3, Dqk=64,
                                  dynamic_seg=seg)
        assert name in ("balanced", "ring", "ulysses")
    # prefix_lm: only ulysses can serve, and only FORWARD — the baselines
    # reuse the ring backward, which raises on prefix masks, so the
    # trace-time filter must mirror that (the capability/runtime
    # consistency bugfix): with include_bwd the resolution raises cleanly
    # instead of handing back a name that explodes at execution time
    assert sp.choose_schedule(mk.prefix_lm(8), 8, Tl=64, Hq=8, Hkv=8,
                              include_bwd=False) == "ulysses"
    with pytest.raises(ValueError, match="auto"):
        sp.choose_schedule(mk.prefix_lm(8), 8, Tl=64, Hq=8, Hkv=8,
                           include_bwd=True)
    # same for a non-causal sliding window
    assert sp.choose_schedule(mk.sliding_window(64, causal=False), 8,
                              Tl=64, Hq=8, Hkv=8,
                              include_bwd=False) == "ulysses"
    with pytest.raises(ValueError, match="auto"):
        sp.choose_schedule(mk.sliding_window(64, causal=False), 8, Tl=64,
                           Hq=8, Hkv=8)
    with pytest.raises(ValueError, match="auto"):
        sp.choose_schedule(mk.prefix_lm(8), 8, Tl=64, Hq=6, Hkv=3)


# --------------------------------------------------------------------------
# 2. Differential vs the frozen seed implementations (8 host devices)
# --------------------------------------------------------------------------

def test_plans_match_seed_implementations(subproc):
    """ACCEPTANCE: the plan executors reproduce the seed hand-written
    schedule loops (core/legacy_schedules.py) — forward, lse, and
    backward — for ring/balanced/zigzag × causal/windowed/document."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core import legacy_schedules as LS
from repro.core.dist_attention import (DistAttnSpec, dist_attn_fwd,
                                       dist_attn_bwd, zigzag_perm)
mesh = make_mesh((1,8), ("data","model"))
PS = jax.sharding.PartitionSpec
B,N,H,Hkv,D = 2,512,4,2,32
ks = jax.random.split(jax.random.PRNGKey(0),4)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D))
v = jax.random.normal(ks[2],(B,N,Hkv,D)); do = jax.random.normal(ks[3],(B,N,H,D))
bnd = mk.doc_boundaries(N, 5)
seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, bnd), (B,1)))
perm = zigzag_perm(N, 8)
qs = PS(None,"model",None,None); ls = PS(None,"model",None); gs = PS(None,"model")
def smap(f, ins, outs):
    return jax.shard_map(f, mesh=mesh, in_specs=ins, out_specs=outs,
                         check_vma=False)
cases = [
    ("ring", LS._fwd_ring, LS._bwd_ring, mk.causal(), False, False),
    ("ring", LS._fwd_ring, LS._bwd_ring, mk.sliding_window(100), False, False),
    ("ring", LS._fwd_ring, LS._bwd_ring, mk.full(), False, False),
    ("ring", LS._fwd_ring, LS._bwd_ring, mk.document(), True, False),
    ("balanced", LS._fwd_balanced, LS._bwd_balanced, mk.causal(), False, False),
    ("balanced", LS._fwd_balanced, LS._bwd_balanced, mk.document(), True, False),
    ("zigzag", LS._fwd_zigzag, LS._bwd_zigzag, mk.causal(), False, True),
    ("zigzag", LS._fwd_zigzag, LS._bwd_zigzag, mk.document(), True, True),
]
for sched, lf, lb, m, use_seg, zz in cases:
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=m)
    qq,kk_,vv,dd = (tuple(x[:,perm] for x in (q,k,v,do)) if zz
                    else (q,k,v,do))
    ss = seg[:,perm] if zz else seg
    if use_seg:
        fl = smap(lambda a,b,c,s: lf(spec,a,b,c,s), (qs,)*3+(gs,), (qs,ls))
        o_l, s_l = jax.jit(fl)(qq,kk_,vv,ss)
    else:
        fl = smap(lambda a,b,c: lf(spec,a,b,c), (qs,)*3, (qs,ls))
        o_l, s_l = jax.jit(fl)(qq,kk_,vv)
    segarg = ss if use_seg else None
    o_n, s_n = jax.jit(lambda *a: dist_attn_fwd(*a[:3], mesh=mesh, spec=spec,
        batch_axes=None, segments=segarg))(qq,kk_,vv)
    ef = float(jnp.abs(o_n-o_l).max()); es = float(jnp.abs(s_n-s_l).max())
    if use_seg:
        bl = smap(lambda a,b,c,o,s,d,g: lb(spec,a,b,c,o,s,d,g),
                  (qs,)*4+(ls,qs,gs), (qs,)*3)
        g_l = jax.jit(bl)(qq,kk_,vv,o_l,s_l,dd,ss)
    else:
        bl = smap(lambda a,b,c,o,s,d: lb(spec,a,b,c,o,s,d),
                  (qs,)*4+(ls,qs), (qs,)*3)
        g_l = jax.jit(bl)(qq,kk_,vv,o_l,s_l,dd)
    g_n = jax.jit(lambda *a: dist_attn_bwd(*a, mesh=mesh, spec=spec,
        batch_axes=None, segments=segarg))(qq,kk_,vv,o_l,s_l,dd)
    eb = max(float(jnp.abs(x-y).max()) for x,y in zip(g_n,g_l))
    assert max(ef,es,eb) < 5e-5, (sched, m.kind, ef, es, eb)
    print("OK seed-diff", sched, m.kind, ef, es, eb)
""")
    assert out.count("OK") == 8


# --------------------------------------------------------------------------
# 3. Oracle differentials for the new capabilities
# --------------------------------------------------------------------------

def test_windowed_balanced_zigzag_vs_oracle(subproc):
    """ACCEPTANCE: windowed balanced/zigzag (new with the plan IR) match
    the oracle forward + grads on 8 devices, and execute strictly fewer
    ring steps than their causal plans."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core import schedule as sp
from repro.core.dist_attention import (DistAttnSpec, dist_flash_attn,
                                       zigzag_perm)
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,8), ("data","model"))
B,N,H,Hkv,D = 2,512,4,2,32
ks = jax.random.split(jax.random.PRNGKey(1),3)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D))
v = jax.random.normal(ks[2],(B,N,Hkv,D))
perm = zigzag_perm(N, 8); inv = np.argsort(perm)
for w in (10, 60, 300):
    m = mk.sliding_window(w)
    g_ref = jax.grad(lambda a,b,c: jnp.sum(full_attn_ref(a,b,c,mask=m)
        .astype(jnp.float32)**2),(0,1,2))(q,k,v)
    o_ref = full_attn_ref(q,k,v,mask=m)
    for sched, zz in (("balanced",False), ("zigzag",True)):
        plan = sp.build_plan(sched, m, 8, N//8)
        causal = sp.build_plan(sched, mk.causal(), 8, N//8)
        # bands smaller than a shard must prune steps (zigzag keeps both
        # sequence-end steps, so its cut needs w below the half-chunk span)
        if w <= 60:
            assert plan.exec_steps < causal.exec_steps, (sched, w)
        assert plan.exec_steps <= causal.exec_steps, (sched, w)
        spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=m)
        a,b,c = ((q[:,perm],k[:,perm],v[:,perm]) if zz else (q,k,v))
        def loss(a,b,c):
            o,_ = dist_flash_attn(a,b,c,mesh,spec,None)
            return jnp.sum(o.astype(jnp.float32)**2), o
        (l,o), g = jax.jit(jax.value_and_grad(loss,(0,1,2),has_aux=True))(a,b,c)
        if zz:
            eo = float(jnp.abs(o[:,inv]-o_ref).max())
            eg = max(float(jnp.abs(x[:,inv]-y).max()) for x,y in zip(g,g_ref))
        else:
            eo = float(jnp.abs(o-o_ref).max())
            eg = max(float(jnp.abs(x-y).max()) for x,y in zip(g,g_ref))
        assert max(eo,eg) < 5e-5, (sched, w, eo, eg)
        print("OK windowed", sched, w, plan.exec_steps, "/", plan.total_steps)
""")
    assert out.count("OK") == 6


def test_boundary_documents_on_ring_family(subproc):
    """ACCEPTANCE: document(boundaries=…) now runs on ring/balanced/zigzag
    with NO segment arrays — executors derive per-shard segment IDs from
    the static layout — matching the segment-array oracle, fwd + grads."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import (DistAttnSpec, dist_flash_attn,
                                       zigzag_perm)
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,8), ("data","model"))
B,N,H,Hkv,D = 2,512,4,2,32
ks = jax.random.split(jax.random.PRNGKey(2),3)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D))
v = jax.random.normal(ks[2],(B,N,Hkv,D))
bnd = mk.doc_boundaries(N, 5)
seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, bnd), (B,1)))
m = mk.document(boundaries=bnd)
o_ref = full_attn_ref(q,k,v, mask=mk.document(), segments=seg)
g_ref = jax.grad(lambda a,b,c: jnp.sum(full_attn_ref(a,b,c,
    mask=mk.document(), segments=seg).astype(jnp.float32)**2),(0,1,2))(q,k,v)
perm = zigzag_perm(N, 8); inv = np.argsort(perm)
for sched, zz in (("ring",False), ("balanced",False), ("zigzag",True)):
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=m)
    a,b,c = ((q[:,perm],k[:,perm],v[:,perm]) if zz else (q,k,v))
    def loss(a,b,c):
        o,_ = dist_flash_attn(a,b,c,mesh,spec,None)   # NO segments arg
        return jnp.sum(o.astype(jnp.float32)**2), o
    (l,o), g = jax.jit(jax.value_and_grad(loss,(0,1,2),has_aux=True))(a,b,c)
    if zz:
        eo = float(jnp.abs(o[:,inv]-o_ref).max())
        eg = max(float(jnp.abs(x[:,inv]-y).max()) for x,y in zip(g,g_ref))
    else:
        eo = float(jnp.abs(o-o_ref).max())
        eg = max(float(jnp.abs(x-y).max()) for x,y in zip(g,g_ref))
    assert max(eo,eg) < 5e-5, (sched, eo, eg)
    print("OK bnd-doc", sched, eo, eg)
""")
    assert out.count("OK") == 3


def test_auto_schedule_resolution(subproc):
    """ACCEPTANCE: schedule="auto" resolves to a valid schedule for every
    supported mask kind (exact vs oracle, fwd + grads where a distributed
    backward exists) and raises nowhere the explicit names succeed."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import (DistAttnSpec, dist_attn_fwd,
                                       dist_flash_attn)
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,8), ("data","model"))
B,N,H,D = 2,512,8,32
ks = jax.random.split(jax.random.PRNGKey(3),3)
q,k,v = (jax.random.normal(kk,(B,N,H,D)) for kk in ks)
bnd = mk.doc_boundaries(N, 5)
seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, bnd), (B,1)))
cases = [
    (mk.causal(), None, full_attn_ref(q,k,v,causal=True)),
    (mk.sliding_window(64), None, full_attn_ref(q,k,v,mask=mk.sliding_window(64))),
    (mk.full(), None, full_attn_ref(q,k,v,causal=False)),
    (mk.document(), seg, full_attn_ref(q,k,v,mask=mk.document(),segments=seg)),
    (mk.document(boundaries=bnd), None,
     full_attn_ref(q,k,v,mask=mk.document(),segments=seg)),
    (mk.prefix_lm(100), None, full_attn_ref(q,k,v,mask=mk.prefix_lm(100))),
]
for m, segarg, o_ref in cases:
    spec = DistAttnSpec(axis="model", axis_size=8, schedule="auto", mask=m)
    o,_ = jax.jit(lambda *a: dist_attn_fwd(*a, mesh=mesh, spec=spec,
        batch_axes=None, segments=segarg))(q,k,v)
    err = float(jnp.abs(o-o_ref).max())
    assert err < 2e-5, (m.kind, err)
    print("OK auto fwd", m.kind, err)
# grads through auto (causal — the training path)
spec = DistAttnSpec(axis="model", axis_size=8, schedule="auto",
                    mask=mk.causal())
g = jax.jit(jax.grad(lambda a,b,c: jnp.sum(dist_flash_attn(a,b,c,mesh,spec,
    None)[0].astype(jnp.float32)**2),(0,1,2)))(q,k,v)
g_ref = jax.grad(lambda a,b,c: jnp.sum(full_attn_ref(a,b,c,causal=True)
    .astype(jnp.float32)**2),(0,1,2))(q,k,v)
err = max(float(jnp.abs(x-y).max()) for x,y in zip(g,g_ref))
assert err < 5e-5, err
print("OK auto grads", err)
# auto must not raise where explicit names succeed: GQA heads that break
# ulysses still resolve (to a plan schedule)
kg = jax.random.normal(ks[1],(B,N,2,D))
spec = DistAttnSpec(axis="model", axis_size=8, schedule="auto",
                    mask=mk.causal())
o,_ = jax.jit(lambda a,b,c: dist_attn_fwd(a,b,c, mesh=mesh, spec=spec,
    batch_axes=None))(q,kg,kg)
print("OK auto gqa")
""")
    assert out.count("OK") == 8


def test_single_device_mesh_plan_paths(subproc):
    """Differential on a 1-device mesh: every schedule (and auto)
    collapses to the local kernel with identical results."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,1), ("data","model"))
B,N,H,D = 2,128,4,16
ks = jax.random.split(jax.random.PRNGKey(4),3)
q,k,v = (jax.random.normal(kk,(B,N,H,D)) for kk in ks)
o_ref = full_attn_ref(q,k,v,causal=True)
for sched in ("auto","balanced","ring","zigzag","ulysses","rsa"):
    spec = DistAttnSpec(axis="model", axis_size=1, schedule=sched,
                        mask=mk.causal())
    o,_ = jax.jit(lambda a,b,c: dist_attn_fwd(a,b,c, mesh=mesh, spec=spec,
        batch_axes=None))(q,k,v)
    err = float(jnp.abs(o-o_ref).max())
    assert err < 2e-5, (sched, err)
    print("OK 1dev", sched)
""", devices=1)
    assert out.count("OK") == 6


# --------------------------------------------------------------------------
# 4. 2D (seq×head) factored plans
# --------------------------------------------------------------------------

def _factorizations(P):
    return [(r, u) for (r, u) in sp.factorizations(P) if u > 1]


def _head_partition_ok(p2):
    """Head routing simulator: the u devices partition the query heads
    exactly, and every local query slot's KV slot holds the KV head its
    GQA group maps to — scatter and replicate modes alike."""
    Hq, Hkv, u = p2.Hq, p2.Hkv, p2.u
    g = Hq // Hkv
    Hql = Hq // u
    seen = []
    for j in range(u):
        q_ids, kv_ids = sp.plan2d_head_map(p2, j)
        seen += list(q_ids)
        for i, gq in enumerate(q_ids):
            local_kv = kv_ids[i] if p2.kv_mode == "replicate" \
                else kv_ids[i // g]
            assert local_kv == gq // g, (p2.name, j, i, gq, local_kv)
    assert sorted(seen) == list(range(Hq))


@pytest.mark.parametrize("P", [2, 4, 8])
@pytest.mark.parametrize("mcase", ["causal", "windowed", "document"])
@pytest.mark.parametrize("heads", [(8, 8), (8, 2)], ids=["mha", "gqa"])
def test_plan2d_coverage_exactly_once(P, mcase, heads):
    """ACCEPTANCE: for every factorization r·u = P and every ring-family
    inner schedule, the 2D plan covers each global (q × kv) pair exactly
    once — the inner plan simulator runs at (P=r, Tl=u·Tl_dev) on the
    post-scatter layout — and the head partition is exact (GQA group map
    included, scatter and replicate KV modes)."""
    Hq, Hkv = heads
    Tl_dev = 8
    for r, u in _factorizations(P):
        T = r * u * Tl_dev
        m = {"causal": mk.causal(),
             "windowed": mk.sliding_window(max(3, T // 8)),
             "document": mk.document(boundaries=mk.doc_boundaries(T, 3)),
             }[mcase]
        for sched in ("ring", "balanced", "zigzag"):
            if not sp.plan2d_capable(sched, m, r=r, u=u, Hq=Hq, Hkv=Hkv):
                continue
            p2 = sp.build_plan2d(sched, m, r, u, Tl_dev, Hq=Hq, Hkv=Hkv)
            assert p2.inner.P == r and p2.inner.Tl == u * Tl_dev
            assert p2.kv_mode == ("scatter" if Hkv % u == 0
                                  else "replicate")
            _assert_exact(p2.inner)
            _head_partition_ok(p2)


def test_plan2d_windowed_pruning_intact():
    """Step pruning survives the factorization: a small window on the
    inner ring/balanced plan at r = 4 executes strictly fewer steps than
    causal, exactly as in 1D — the head scatter changes nothing about the
    seq-axis schedule."""
    for sched in ("ring", "balanced"):
        pc = sp.build_plan2d(sched, mk.causal(), 4, 2, 16, Hq=8, Hkv=8)
        pw = sp.build_plan2d(sched, mk.sliding_window(5), 4, 2, 16,
                             Hq=8, Hkv=8)
        assert pw.inner.exec_steps < pc.inner.exec_steps, sched
        _assert_exact(pw.inner)


def test_plan2d_capability_and_build_errors():
    """Capability edges: Hq must divide u; non-uniform GQA groups are
    rejected; r == 1 serves any mask kind through the local kernel; r > 1
    follows the 1D plan rules (no prefix_lm, no non-causal windows)."""
    assert not sp.plan2d_capable("ring", mk.causal(), r=2, u=4, Hq=6,
                                 Hkv=2)
    assert not sp.plan2d_capable("ring", mk.causal(), r=2, u=4, Hq=8,
                                 Hkv=3)
    assert sp.plan2d_capable("ring", mk.prefix_lm(8), r=1, u=8, Hq=8,
                             Hkv=2)
    assert sp.plan2d_capable(
        "ring", mk.sliding_window(9, causal=False), r=1, u=8, Hq=8, Hkv=8)
    assert not sp.plan2d_capable("ring", mk.prefix_lm(8), r=2, u=4, Hq=8,
                                 Hkv=8)
    assert not sp.plan2d_capable("balanced", mk.full(), r=4, u=2, Hq=8,
                                 Hkv=8)
    with pytest.raises(ValueError, match="factorization"):
        sp.build_plan2d("balanced", mk.full(), 4, 2, 8, Hq=8, Hkv=8)
    with pytest.raises(ValueError, match="factorization"):
        sp.build_plan2d("ring", mk.causal(), 2, 4, 8, Hq=6, Hkv=6)


def test_plan2d_cost_and_factorized_auto():
    """The (r, u) factorization space in the cost model: plan2d_cost
    reduces head-axis traffic claims to the roofline helpers, and
    ``choose_schedule(factorize=True)`` returns the cheapest capable
    triple — nontrivial (r > 1 and u > 1) for the causal-GQA bench
    regime, (r = 1, u = P) for prefix_lm (healing the no-backward gap of
    every 1D multi-shard schedule), and a clean error when nothing is
    capable."""
    kw = dict(Tl=256, B=1, Hq=8, Hkv=2, Dqk=64, bpe=4)
    name, r, u = sp.choose_schedule(mk.causal(), 8, factorize=True, **kw)
    assert r * u == 8 and r > 1 and u > 1, (name, r, u)
    assert name in ("ring", "balanced")
    # the chosen 2D factorization is analytically cheaper than both pure
    # extremes for this shape
    def t_of(nm, rr, uu):
        if uu == 1:
            c = sp.plan_cost(sp.build_plan(nm, mk.causal(), 8, 256),
                             B=1, Hq=8, Hkv=2, Dqk=64, bpe=4)
        else:
            c = sp.plan2d_cost(
                sp.build_plan2d(nm, mk.causal(), rr, uu, 256, Hq=8,
                                Hkv=2), B=1, Dqk=64, bpe=4)
        return c.time_estimate()["step_s_lower_bound"]
    assert t_of(name, r, u) <= t_of("ring", 8, 1)
    assert t_of(name, r, u) <= t_of("ring", 1, 8)
    # prefix_lm: the only backward-capable factorization is head-only
    nm, r1, u1 = sp.choose_schedule(mk.prefix_lm(64), 8, factorize=True,
                                    **kw)
    assert (nm, r1, u1) == ("ring", 1, 8)
    # heads that divide nothing: no factorization with u > 1 exists and
    # 1D plans still win where capable…
    nm, r2, u2 = sp.choose_schedule(mk.causal(), 8, Tl=256, Hq=7, Hkv=7,
                                    factorize=True)
    assert (r2, u2) == (8, 1)
    # …but prefix_lm + indivisible heads has no capable triple at all
    with pytest.raises(ValueError, match="factorization"):
        sp.choose_schedule(mk.prefix_lm(8), 8, Tl=64, Hq=7, Hkv=7,
                           factorize=True)
    assert sp.choose_schedule(mk.causal(), 1, Tl=64,
                              factorize=True) == ("ring", 1, 1)
    # cost model consistency: a 2D plan's comm includes the inner plan's
    c2 = sp.plan2d_cost(sp.build_plan2d("ring", mk.causal(), 4, 2, 256,
                                        Hq=8, Hkv=2), B=1, Dqk=64, bpe=4)
    ci = sp.plan_cost(sp.build_plan("ring", mk.causal(), 4, 512), B=1,
                      Hq=4, Hkv=1, Dqk=64, bpe=4)
    assert c2.comm_bytes_fwd > ci.comm_bytes_fwd
    assert c2.flops_fwd == ci.flops_fwd


def test_plans2d_match_1d_and_oracle(subproc):
    """ACCEPTANCE: 2D forward + grads == the 1D ring reference == the
    single-device oracle, for MHA and GQA (scatter and replicate KV
    modes), causal / windowed / document masks, across the (2, 4) and
    (4, 2) factorizations of the 8-device host mesh."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, Mesh2DSpec, dist_flash_attn
from repro.core.attention import chunk_attn
B,N,D = 2,512,32
mesh1 = make_mesh((1,8), ("data","model"))
key = jax.random.PRNGKey(0)
for (Hq,Hkv) in ((4,4),(4,2)):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B,N,Hq,D), jnp.float32)
    k = jax.random.normal(ks[1], (B,N,Hkv,D), jnp.float32)
    v = jax.random.normal(ks[2], (B,N,Hkv,D), jnp.float32)
    do = jax.random.normal(ks[3], (B,N,Hq,D), jnp.float32)
    bnd = mk.doc_boundaries(N, 3)
    for m in (mk.causal(), mk.sliding_window(60), mk.document(boundaries=bnd)):
        def loss_ref(q,k,v):
            o,_ = chunk_attn(q,k,v,mask=m,impl="ref")
            return jnp.sum(o*do)
        o_ref,_ = chunk_attn(q,k,v,mask=m,impl="ref")
        g_ref = jax.grad(loss_ref, argnums=(0,1,2))(q,k,v)
        spec1 = DistAttnSpec(axis="model", axis_size=8, schedule="ring", mask=m)
        def loss1(q,k,v):
            o,_ = dist_flash_attn(q,k,v,mesh1,spec1,batch_axes=None)
            return jnp.sum(o*do)
        o1,_ = jax.jit(lambda q,k,v: dist_flash_attn(
            q,k,v,mesh1,spec1,batch_axes=None))(q,k,v)
        g1 = jax.jit(jax.grad(loss1, argnums=(0,1,2)))(q,k,v)
        for (r,u) in ((2,4),(4,2)):
            mesh2 = make_mesh((1,r,u), ("data","seq","head"))
            sched = "balanced" if m.causal else "ring"
            spec2 = DistAttnSpec(axis="seq", axis_size=8, schedule=sched,
                                 mask=m, mesh2d=Mesh2DSpec(r=r,u=u))
            def loss2(q,k,v):
                o,_ = dist_flash_attn(q,k,v,mesh2,spec2,batch_axes=None)
                return jnp.sum(o*do)
            o2,_ = jax.jit(lambda q,k,v: dist_flash_attn(
                q,k,v,mesh2,spec2,batch_axes=None))(q,k,v)
            g2 = jax.jit(jax.grad(loss2, argnums=(0,1,2)))(q,k,v)
            eo = max(float(jnp.max(jnp.abs(o2-o_ref))),
                     float(jnp.max(jnp.abs(o2-o1))))
            eg = max(max(float(jnp.max(jnp.abs(a-b))) for a,b in zip(g2,g_ref)),
                     max(float(jnp.max(jnp.abs(a-b))) for a,b in zip(g2,g1)))
            assert eo < 5e-5 and eg < 5e-5, (Hq,Hkv,m.kind,r,u,eo,eg)
            print(f"OK 2d {Hq}/{Hkv} {m.kind} r{r}u{u}")
""")
    assert out.count("OK") == 12


def test_plan2d_r1_zigzag_and_auto(subproc):
    """The factorization edges on real devices: the r == 1 head-only
    scatter serves prefix_lm and non-causal windows *with grads* (no 1D
    multi-shard schedule can), zigzag-2D matches the oracle under the
    caller's zigzag_perm(T, r) pre-permutation, and ``schedule="auto"``
    on a 2D spec resolves an inner schedule that runs."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import (DistAttnSpec, Mesh2DSpec,
                                       dist_flash_attn, zigzag_perm)
from repro.core.attention import chunk_attn
B,N,Hq,Hkv,D = 2,512,8,2,32
ks = jax.random.split(jax.random.PRNGKey(1), 4)
q = jax.random.normal(ks[0], (B,N,Hq,D), jnp.float32)
k = jax.random.normal(ks[1], (B,N,Hkv,D), jnp.float32)
v = jax.random.normal(ks[2], (B,N,Hkv,D), jnp.float32)
do = jax.random.normal(ks[3], (B,N,Hq,D), jnp.float32)
def check(label, m, r, u, sched):
    def loss_ref(q,k,v):
        o,_ = chunk_attn(q,k,v,mask=m,impl="ref")
        return jnp.sum(o*do)
    o_ref,_ = chunk_attn(q,k,v,mask=m,impl="ref")
    g_ref = jax.grad(loss_ref, argnums=(0,1,2))(q,k,v)
    mesh = make_mesh((1,r,u), ("data","seq","head"))
    spec = DistAttnSpec(axis="seq", axis_size=8, schedule=sched,
                        mask=m, mesh2d=Mesh2DSpec(r=r,u=u))
    def loss(q,k,v):
        o,_ = dist_flash_attn(q,k,v,mesh,spec,batch_axes=None)
        return jnp.sum(o*do)
    o,_ = jax.jit(lambda q,k,v: dist_flash_attn(
        q,k,v,mesh,spec,batch_axes=None))(q,k,v)
    g = jax.jit(jax.grad(loss, argnums=(0,1,2)))(q,k,v)
    eo = float(jnp.max(jnp.abs(o-o_ref)))
    eg = max(float(jnp.max(jnp.abs(a-b))) for a,b in zip(g,g_ref))
    assert eo < 5e-5 and eg < 5e-5, (label, eo, eg)
    print("OK", label)
check("prefix r1u8", mk.prefix_lm(100), 1, 8, "ring")
check("noncausal-window r1u8", mk.MaskSpec(causal=False, window=64), 1, 8, "ring")
check("auto r4u2", mk.causal(), 4, 2, "auto")
check("auto r2u4 windowed", mk.sliding_window(60), 2, 4, "auto")
# zigzag-2D under the caller pre-permutation with r (not P) chunks
r, u = 4, 2
perm = zigzag_perm(N, r); inv = np.argsort(perm)
m = mk.causal()
mesh = make_mesh((1,r,u), ("data","seq","head"))
spec = DistAttnSpec(axis="seq", axis_size=8, schedule="zigzag",
                    mask=m, mesh2d=Mesh2DSpec(r=r,u=u))
o_ref,_ = chunk_attn(q,k,v,mask=m,impl="ref")
o2p,_ = jax.jit(lambda q,k,v: dist_flash_attn(
    q,k,v,mesh,spec,batch_axes=None))(q[:,perm],k[:,perm],v[:,perm])
assert float(jnp.max(jnp.abs(o2p[:,inv]-o_ref))) < 5e-5
print("OK zigzag2d r4u2")
""")
    assert out.count("OK") == 5
