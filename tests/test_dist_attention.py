"""Distribution tests: the DISTFLASHATTN schedules against the monolithic
oracle, on 8 forced host devices (subprocess so the main pytest process
keeps its single real device)."""
import pytest
from repro.launch.mesh import make_mesh


def test_schedules_match_oracle(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd, dist_flash_attn
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((2,4), ("data","model"))
B,N,H,Hkv,D = 4,256,4,2,32
ks = jax.random.split(jax.random.PRNGKey(0),3)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D)); v = jax.random.normal(ks[2],(B,N,Hkv,D))
o_ref = full_attn_ref(q,k,v,causal=True)
for sched in ["balanced","ring","rsa"]:
    spec = DistAttnSpec(axis="model", axis_size=4, schedule=sched, mask=mk.causal())
    o,_ = jax.jit(lambda q,k,v: dist_attn_fwd(q,k,v,mesh=mesh,spec=spec,batch_axes=("data",)))(q,k,v)
    err = float(jnp.abs(o-o_ref).max())
    assert err < 2e-5, (sched, err)
    print("OK", sched, err)
# grads via custom_vjp (balanced) vs autodiff oracle
def loss_ref(q,k,v): return jnp.sum(full_attn_ref(q,k,v,causal=True).astype(jnp.float32)**2)
g_ref = jax.grad(loss_ref,(0,1,2))(q,k,v)
spec = DistAttnSpec(axis="model", axis_size=4, schedule="balanced", mask=mk.causal())
def loss_d(q,k,v):
    o,_ = dist_flash_attn(q,k,v,mesh,spec,("data",))
    return jnp.sum(o.astype(jnp.float32)**2)
g_d = jax.jit(jax.grad(loss_d,(0,1,2)))(q,k,v)
for a,b in zip(g_d,g_ref):
    assert float(jnp.abs(a-b).max()) < 5e-5
print("OK grads")
""")
    assert out.count("OK") == 4


def test_window_and_bidirectional(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,8), ("data","model"))
B,N,H,D = 2,128,2,16
ks = jax.random.split(jax.random.PRNGKey(1),3)
q,k,v = (jax.random.normal(kk,(B,N,H,D)) for kk in ks)
for window in [10, 40, 200]:
    o_ref = full_attn_ref(q,k,v,causal=True,window=window)
    spec = DistAttnSpec(axis="model", axis_size=8, schedule="ring", mask=mk.sliding_window(window))
    o,_ = jax.jit(lambda q,k,v: dist_attn_fwd(q,k,v,mesh=mesh,spec=spec,batch_axes=("data",)))(q,k,v)
    assert float(jnp.abs(o-o_ref).max()) < 2e-5, window
    print("OK window", window)
o_ref = full_attn_ref(q,k,v,causal=False)
spec = DistAttnSpec(axis="model", axis_size=8, schedule="ring", mask=mk.full())
o,_ = jax.jit(lambda q,k,v: dist_attn_fwd(q,k,v,mesh=mesh,spec=spec,batch_axes=("data",)))(q,k,v)
assert float(jnp.abs(o-o_ref).max()) < 2e-5
print("OK bidir")
""")
    assert out.count("OK") == 4


def test_odd_p_schedule(subproc):
    """Odd worker counts (paper: zero idle when P odd) stay exact."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,7), ("data","model"))
B,N,H,D = 2,7*16,2,16
ks = jax.random.split(jax.random.PRNGKey(2),3)
q,k,v = (jax.random.normal(kk,(B,N,H,D)) for kk in ks)
o_ref = full_attn_ref(q,k,v,causal=True)
spec = DistAttnSpec(axis="model", axis_size=7, schedule="balanced", mask=mk.causal())
o,_ = jax.jit(lambda q,k,v: dist_attn_fwd(q,k,v,mesh=mesh,spec=spec,batch_axes=("data",)))(q,k,v)
assert float(jnp.abs(o-o_ref).max()) < 2e-5
print("OK P=7 balanced")
""", devices=7)
    assert "OK" in out


def test_even_p2_balanced_schedule(subproc):
    """P = 2: the balanced plan's one ring step is the split last step
    (each device computes half of the off-diagonal pair) — forward and
    grads exact against the oracle, causal and dynamic document."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_flash_attn
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,2), ("data","model"))
B,N,H,Hkv,D = 2,128,4,2,16
ks = jax.random.split(jax.random.PRNGKey(5),3)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D))
v = jax.random.normal(ks[2],(B,N,Hkv,D))
seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, mk.doc_boundaries(N, 3)), (B,1)))
for m, s in ((mk.causal(), None), (mk.document(), seg)):
    def loss_ref(q,k,v):
        o = full_attn_ref(q,k,v,mask=m,segments=s)
        return jnp.sum(o.astype(jnp.float32)**2), o
    (_, o_ref), g_ref = jax.value_and_grad(loss_ref,(0,1,2),has_aux=True)(q,k,v)
    spec = DistAttnSpec(axis="model", axis_size=2, schedule="balanced", mask=m)
    def loss_d(q,k,v):
        o,_ = dist_flash_attn(q,k,v,mesh,spec,None,segments=s)
        return jnp.sum(o.astype(jnp.float32)**2), o
    (_, o), g = jax.jit(jax.value_and_grad(loss_d,(0,1,2),has_aux=True))(q,k,v)
    eo = float(jnp.abs(o-o_ref).max())
    eg = max(float(jnp.abs(a-b).max()) for a,b in zip(g,g_ref))
    assert eo < 2e-5 and eg < 5e-5, (m.kind, eo, eg)
    print("OK P=2 balanced", m.kind, eo, eg)
""", devices=2)
    assert out.count("OK") == 2


def test_decode_attention(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import dist_decode_attn
from repro.kernels.ref import chunk_attn_ref
mesh = make_mesh((2,4), ("data","model"))
B,N,H,Hkv,D = 4,256,4,2,32
ks = jax.random.split(jax.random.PRNGKey(0),6)
k = jax.random.normal(ks[1],(B,N,Hkv,D)); v = jax.random.normal(ks[2],(B,N,Hkv,D))
qd = jax.random.normal(ks[3],(B,1,H,D))
k1 = jax.random.normal(ks[4],(B,1,Hkv,D)); v1 = jax.random.normal(ks[5],(B,1,Hkv,D))
kf = jnp.concatenate([k,k1],1); vf = jnp.concatenate([v,v1],1)
o_ref,_ = chunk_attn_ref(qd,kf,vf)
for axes, bspec in [(("model",),("data",)), (("data","model"),None)]:
    o = jax.jit(lambda *a: dist_decode_attn(*a,mesh=mesh,seq_axes=axes,batch_axes=bspec))(qd,k,v,k1,v1)
    assert float(jnp.abs(o-o_ref).max()) < 2e-5, axes
    print("OK decode", axes)
ow_ref,_ = chunk_attn_ref(qd,kf,vf,causal=False,q_offset=N,window=100)
ow = jax.jit(lambda *a: dist_decode_attn(*a,mesh=mesh,seq_axes=("model",),batch_axes=("data",),mask=mk.sliding_window(100)))(qd,k,v,k1,v1)
assert float(jnp.abs(ow-ow_ref).max()) < 2e-5
print("OK decode window")
""")
    assert out.count("OK") == 3


def test_models_distributed_match_single(subproc):
    """Per-arch loss on an 8-device mesh equals the 1-device value (the
    smoke matrix checked visually during bring-up, now locked in)."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.config import ARCH_IDS, get_config, smoke_config, ShapeSpec
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.data.pipeline import SyntheticTokens
shape = ShapeSpec("smoke", 64, 4, "train")
for arch in ["smollm-360m", "deepseek-v2-lite-16b", "zamba2-2.7b", "whisper-tiny"]:
    cfg = smoke_config(get_config(arch))
    losses = {}
    for (d, s) in [(1, 1), (2, 4)]:
        mesh = make_mesh((d, s), ("data", "model"))
        par = make_parallel_config(mesh, shape)
        model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
        params = model.init(jax.random.PRNGKey(0))
        batch = SyntheticTokens(cfg, shape, par, mesh).batch(0)
        loss, _ = jax.jit(model.loss)(params, batch)
        losses[(d, s)] = float(loss)
    a, b = losses[(1, 1)], losses[(2, 4)]
    assert abs(a - b) < 5e-3 * max(1, abs(a)), (arch, losses)
    print("OK", arch, a, b)
""")
    assert out.count("OK") == 4


def test_zigzag_and_ulysses(subproc):
    """Beyond-paper zigzag placement and the Ulysses baseline are exact."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import (DistAttnSpec, dist_attn_fwd,
                                       dist_flash_attn, zigzag_perm)
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,8), ("data","model"))
B,N,H,Hkv,D = 2,512,4,2,32
ks = jax.random.split(jax.random.PRNGKey(0),3)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D)); v = jax.random.normal(ks[2],(B,N,Hkv,D))
perm = zigzag_perm(N, 8)
o_ref = full_attn_ref(q,k,v,causal=True)
spec = DistAttnSpec(axis="model", axis_size=8, schedule="zigzag", mask=mk.causal())
o,_ = jax.jit(lambda a,b,c: dist_attn_fwd(a,b,c,mesh=mesh,spec=spec,batch_axes=None))(q[:,perm],k[:,perm],v[:,perm])
assert float(jnp.abs(o - o_ref[:,perm]).max()) < 2e-5
print("OK zigzag fwd")
def loss(a,b,c):
    o,_ = dist_flash_attn(a,b,c,mesh,spec,None)
    return jnp.sum(o.astype(jnp.float32)**2)
gz = jax.jit(jax.grad(loss,(0,1,2)))(q[:,perm],k[:,perm],v[:,perm])
gr = jax.grad(lambda a,b,c: jnp.sum(full_attn_ref(a,b,c,causal=True).astype(jnp.float32)**2),(0,1,2))(q,k,v)
inv = np.argsort(perm)
for a,b in zip(gz,gr):
    assert float(jnp.abs(a[:,inv]-b).max()) < 5e-5
print("OK zigzag bwd")
# ulysses (divisible heads)
q8 = jax.random.normal(ks[0],(B,N,8,D)); k8 = jax.random.normal(ks[1],(B,N,8,D)); v8 = jax.random.normal(ks[2],(B,N,8,D))
specu = DistAttnSpec(axis="model", axis_size=8, schedule="ulysses", mask=mk.causal())
ou,_ = jax.jit(lambda a,b,c: dist_attn_fwd(a,b,c,mesh=mesh,spec=specu,batch_axes=None))(q8,k8,v8)
assert float(jnp.abs(ou - full_attn_ref(q8,k8,v8,causal=True)).max()) < 2e-5
print("OK ulysses")
# ulysses head-divisibility failure (paper 4.2/4.6)
q33 = jax.random.normal(ks[0],(B,N,3,D))
try:
    dist_attn_fwd(q33,q33,q33,mesh=mesh,spec=specu,batch_axes=None)
    raise SystemExit("should have raised")
except ValueError:
    print("OK ulysses raises on indivisible heads")
""")
    assert out.count("OK") == 4


@pytest.mark.parametrize("P", [5, 8], ids=["odd-P", "even-P"])
def test_cross_schedule_golden(subproc, P):
    """Golden cross-schedule agreement: balanced vs ring vs single-device
    full attention match within fp32 tolerance on odd and even P, and the
    registry's chunked-lax backend gives the same answer as ref inside the
    distributed schedules."""
    out = subproc(f"""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
from repro.kernels.ref import full_attn_ref
P = {P}
mesh = make_mesh((1, P), ("data", "model"))
B, H, Hkv, D = 2, 4, 2, 16
N = P * 32
ks = jax.random.split(jax.random.PRNGKey(3), 3)
q = jax.random.normal(ks[0], (B, N, H, D))
k = jax.random.normal(ks[1], (B, N, Hkv, D))
v = jax.random.normal(ks[2], (B, N, Hkv, D))
o_single = full_attn_ref(q, k, v, causal=True)   # single-device oracle
outs = {{}}
for sched, impl in [("balanced", None), ("ring", None),
                    ("balanced", "chunked-lax")]:
    spec = DistAttnSpec(axis="model", axis_size=P, schedule=sched,
                        mask=mk.causal(), impl=impl)
    o, _ = jax.jit(lambda a, b, c: dist_attn_fwd(
        a, b, c, mesh=mesh, spec=spec, batch_axes=None))(q, k, v)
    err = float(jnp.abs(o - o_single).max())
    assert err < 2e-5, (sched, impl, err)
    outs[(sched, impl)] = o
    print("OK", sched, impl or "ref", err)
d_sched = float(jnp.abs(outs[("balanced", None)]
                        - outs[("ring", None)]).max())
assert d_sched < 2e-5, d_sched
d_impl = float(jnp.abs(outs[("balanced", None)]
                       - outs[("balanced", "chunked-lax")]).max())
assert d_impl < 2e-5, d_impl
print("OK cross", d_sched, d_impl)
""", devices=P)
    assert out.count("OK") == 4


def test_mla_latent_ring_prefill(subproc):
    """Latent-ring MLA prefill == materialized-KV prefill (model level)."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.config import get_config, smoke_config, ShapeSpec
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.data.pipeline import SyntheticTokens
cfg = smoke_config(get_config("deepseek-v2-lite-16b"))
mesh = make_mesh((2,4), ("data","model"))
shape = ShapeSpec("z", 64, 4, "prefill")
outs = {}
for name, sched, lat in [("base","balanced",False), ("latent","zigzag",True)]:
    par = make_parallel_config(mesh, shape, schedule=sched)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref", latent_ring=lat))
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticTokens(cfg, shape, par, mesh).batch(0)
    logits, _ = jax.jit(model.prefill)(params, batch)
    outs[name] = logits
d = float(jnp.abs(outs["base"]-outs["latent"]).max())
assert d < 5e-5, d
print("OK latent ring", d)
""")
    assert "OK" in out


# ------------------------------------------------------- MaskSpec era tests

def test_spec_validation_and_removed_kwargs():
    """Satellite: schedule typos raise at spec construction (no silent ring
    fallthrough), schedule-capability mismatches raise, and the removed
    pre-MaskSpec causal/window kwargs raise ``TypeError`` with the
    migration hint (they were deprecation shims for five PRs with zero
    in-repo callers).  Plan-IR era: balanced/zigzag accept sliding windows
    (plans truncate) and the ring family accepts static document
    boundaries (executors derive per-shard segment IDs) — those
    constructions must NOT raise."""
    import pytest as pt

    from repro.core import mask as mk
    from repro.core import dist_attention as da

    with pt.raises(ValueError, match="unknown schedule"):
        da.DistAttnSpec(schedule="blanced")
    with pt.raises(ValueError, match="unknown schedule"):
        da.DistAttnSpec(schedule="rsa ")
    with pt.raises(ValueError, match="causal-kind"):
        da.DistAttnSpec(axis_size=8, schedule="zigzag", mask=mk.full())
    with pt.raises(ValueError, match="causal-kind"):
        da.DistAttnSpec(axis_size=8, schedule="balanced",
                        mask=mk.prefix_lm(64))
    with pt.raises(ValueError, match="prefix_lm"):
        da.DistAttnSpec(axis_size=8, schedule="ring", mask=mk.prefix_lm(64))
    with pt.raises(ValueError, match="sliding-window"):
        da.DistAttnSpec(axis_size=8, schedule="rsa",
                        mask=mk.sliding_window(64))
    # a non-causal band has future-direction pairs no ring step can see
    with pt.raises(ValueError, match="future-direction"):
        da.DistAttnSpec(axis_size=8, schedule="ring",
                        mask=mk.sliding_window(64, causal=False))
    # plan-era capability widenings: these construct fine now
    da.DistAttnSpec(axis_size=8, schedule="balanced",
                    mask=mk.sliding_window(64))
    da.DistAttnSpec(axis_size=8, schedule="zigzag",
                    mask=mk.sliding_window(64))
    da.DistAttnSpec(axis_size=8, schedule="ring",
                    mask=mk.document(boundaries=(0, 64)))
    da.DistAttnSpec(axis_size=8, schedule="auto", mask=mk.prefix_lm(8))
    # prefix_lm has no distributed backward anywhere (the baselines reuse
    # the ring backward, which can't see absolute positions)
    spec_p = da.DistAttnSpec(axis_size=8, schedule="ulysses",
                             mask=mk.prefix_lm(8))
    with pt.raises(ValueError, match="prefix_lm"):
        da._bwd_local(spec_p, *([None] * 6))
    # rsa must demand segments for a dynamic-segment document mask, like
    # every other schedule does (via the backends)
    spec_r = da.DistAttnSpec(axis_size=8, schedule="rsa", mask=mk.document())
    with pt.raises(ValueError, match="segments"):
        da._fwd_local(spec_r, None, None, None, None)
    # the removed legacy kwargs are hard errors now — alone or mixed
    with pt.raises(TypeError, match="was removed"):
        da.DistAttnSpec(schedule="ring", mask=mk.causal(), causal=True)
    with pt.raises(TypeError, match="mask=repro.core.mask"):
        da.DistAttnSpec(axis_size=8, schedule="ring", causal=True,
                        window=40)
    with pt.raises(TypeError, match="was removed"):
        da.DistAttnSpec(window=40)
    # the mask=None default stays causal — and balanced accepts it
    assert da.DistAttnSpec(axis_size=8).mask == mk.causal()
    # the decode entry point's window= kwarg is removed too
    import jax
    import jax.numpy as jnp
    mesh = make_mesh((1, 1), ("data", "model"))
    z4 = jnp.zeros((1, 1, 2, 8))
    zc = jnp.zeros((1, 4, 2, 8))
    with pt.raises(TypeError, match=r"dist_decode_attn\(window=\) was "
                                    r"removed"):
        da.dist_decode_attn(z4, zc, zc, z4, z4, mesh=mesh,
                            seq_axes=("model",), batch_axes=None, window=2)
    with pt.raises(TypeError, match="was removed"):
        da.dist_decode_attn(z4, zc, zc, z4, z4, mesh=mesh,
                            seq_axes=("model",), batch_axes=None,
                            mask=mk.causal(), window=2)
    with pt.raises(ValueError, match="causal/sliding_window"):
        da.dist_decode_attn(z4, zc, zc, z4, z4, mesh=mesh,
                            seq_axes=("model",), batch_axes=None,
                            mask=mk.document())
    # 2D (seq×head) factorization validation
    with pt.raises(ValueError, match="must equal"):
        da.DistAttnSpec(axis_size=8, mesh2d=da.Mesh2DSpec(r=2, u=2))
    with pt.raises(ValueError, match="ring-family plans only"):
        da.DistAttnSpec(axis_size=8, schedule="ulysses", mask=mk.causal(),
                        mesh2d=da.Mesh2DSpec(r=4, u=2))
    with pt.raises(ValueError, match="distinct"):
        da.Mesh2DSpec(r=2, u=4, seq_axis="x", head_axis="x")
    # prefix_lm: rejected on a multi-shard seq sub-axis, served at r == 1
    # (head-only scatter — the local kernel sees absolute positions)
    with pt.raises(ValueError, match="prefix_lm"):
        da.DistAttnSpec(axis_size=8, schedule="ring", mask=mk.prefix_lm(8),
                        mesh2d=da.Mesh2DSpec(r=4, u=2))
    da.DistAttnSpec(axis_size=8, schedule="ring", mask=mk.prefix_lm(8),
                    mesh2d=da.Mesh2DSpec(r=1, u=8))


def test_document_mask_all_schedules(subproc):
    """ACCEPTANCE: packed-sequence (document) masking is differentially
    exact vs the oracle across ring / balanced / zigzag (and the ulysses /
    rsa baselines), forward and backward, with segment IDs traveling the
    ring alongside KV. Boundaries intentionally do not align with shards."""
    out = subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import (DistAttnSpec, dist_attn_fwd,
                                       dist_flash_attn, zigzag_perm)
from repro.kernels.ref import full_attn_ref
mesh = make_mesh((1,8), ("data","model"))
B,N,H,Hkv,D = 2,512,4,2,32
ks = jax.random.split(jax.random.PRNGKey(0),3)
q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D)); v = jax.random.normal(ks[2],(B,N,Hkv,D))
bnd = mk.doc_boundaries(N, 5)
seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, bnd), (B,1)))
o_ref = full_attn_ref(q,k,v, mask=mk.document(), segments=seg)
perm = zigzag_perm(N, 8); inv = np.argsort(perm)
for sched in ["ring","balanced","zigzag","rsa"]:
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=mk.document())
    if sched == "zigzag":
        o,_ = jax.jit(lambda a,b,c,s: dist_attn_fwd(a,b,c,mesh=mesh,spec=spec,batch_axes=None,segments=s))(q[:,perm],k[:,perm],v[:,perm],seg[:,perm])
        err = float(jnp.abs(o - o_ref[:,perm]).max())
    else:
        o,_ = jax.jit(lambda a,b,c,s: dist_attn_fwd(a,b,c,mesh=mesh,spec=spec,batch_axes=None,segments=s))(q,k,v,seg)
        err = float(jnp.abs(o - o_ref).max())
    assert err < 2e-5, (sched, err)
    print("OK doc fwd", sched, err)
# ulysses (divisible heads)
q8 = jax.random.normal(ks[0],(B,N,8,D))
specu = DistAttnSpec(axis="model", axis_size=8, schedule="ulysses", mask=mk.document())
ou,_ = jax.jit(lambda a,s: dist_attn_fwd(a,a,a,mesh=mesh,spec=specu,batch_axes=None,segments=s))(q8,seg)
erru = float(jnp.abs(ou - full_attn_ref(q8,q8,q8, mask=mk.document(), segments=seg)).max())
assert erru < 2e-5, erru
print("OK doc fwd ulysses", erru)
# grads via the seg-aware custom_vjp
g_ref = jax.grad(lambda a,b,c: jnp.sum(full_attn_ref(a,b,c, mask=mk.document(), segments=seg).astype(jnp.float32)**2),(0,1,2))(q,k,v)
for sched in ["ring","balanced","zigzag"]:
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=mk.document())
    if sched == "zigzag":
        def loss(a,b,c):
            o,_ = dist_flash_attn(a,b,c,mesh,spec,None,seg[:,perm])
            return jnp.sum(o.astype(jnp.float32)**2)
        g = jax.jit(jax.grad(loss,(0,1,2)))(q[:,perm],k[:,perm],v[:,perm])
        err = max(float(jnp.abs(a[:,inv]-b).max()) for a,b in zip(g,g_ref))
    else:
        def loss(a,b,c):
            o,_ = dist_flash_attn(a,b,c,mesh,spec,None,seg)
            return jnp.sum(o.astype(jnp.float32)**2)
        g = jax.jit(jax.grad(loss,(0,1,2)))(q,k,v)
        err = max(float(jnp.abs(a-b).max()) for a,b in zip(g,g_ref))
    assert err < 5e-5, (sched, err)
    print("OK doc bwd", sched, err)
""")
    assert out.count("OK") == 8


def test_windowed_decode_vs_bruteforce(subproc):
    """Satellite: windowed dist_decode_attn against a brute-force oracle —
    window sizes from sub-shard to beyond-context, on 1D and 2D sequence
    sharding."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import dist_decode_attn
from repro.kernels.ref import chunk_attn_ref
mesh = make_mesh((2,4), ("data","model"))
B,N,H,Hkv,D = 2,256,4,2,32
ks = jax.random.split(jax.random.PRNGKey(3),6)
k = jax.random.normal(ks[1],(B,N,Hkv,D)); v = jax.random.normal(ks[2],(B,N,Hkv,D))
qd = jax.random.normal(ks[3],(B,1,H,D))
k1 = jax.random.normal(ks[4],(B,1,Hkv,D)); v1 = jax.random.normal(ks[5],(B,1,Hkv,D))
kf = jnp.concatenate([k,k1],1); vf = jnp.concatenate([v,v1],1)
for axes, bspec in [(("model",),("data",)), (("data","model"),None)]:
    for window in [1, 7, 64, 100, 257, 10_000]:
        # brute force: the new token sits at absolute position N; the
        # window keeps keys with position > N - window
        o_ref,_ = chunk_attn_ref(qd, kf, vf, mask=mk.MaskSpec(window=window, q_offset=N))
        o = jax.jit(lambda *a: dist_decode_attn(*a, mesh=mesh, seq_axes=axes,
                    batch_axes=bspec, mask=mk.sliding_window(window)))(qd,k,v,k1,v1)
        err = float(jnp.abs(o-o_ref).max())
        assert err < 2e-5, (axes, window, err)
    print("OK windowed decode", axes)
""")
    assert out.count("OK") == 2


def test_ulysses_head_divisibility_error_paths(subproc):
    """Satellite: the ulysses ValueError fires for indivisible Hq, for
    indivisible Hkv (GQA), and inside jit tracing — and never fires when
    both divide P."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
mesh = make_mesh((1,4), ("data","model"))
B,N,D = 1,128,16
spec = DistAttnSpec(axis="model", axis_size=4, schedule="ulysses", mask=mk.causal())
def run(Hq, Hkv):
    q = jax.random.normal(jax.random.PRNGKey(0),(B,N,Hq,D))
    kv = jax.random.normal(jax.random.PRNGKey(1),(B,N,Hkv,D))
    return dist_attn_fwd(q,kv,kv,mesh=mesh,spec=spec,batch_axes=None)
for Hq, Hkv, ok in [(8,4,True), (6,4,False), (8,2,False), (3,3,False)]:
    try:
        jax.jit(lambda: run(Hq,Hkv))()
        assert ok, (Hq,Hkv)
        print("OK ulysses runs", Hq, Hkv)
    except ValueError as e:
        assert not ok and "heads % P" in str(e), (Hq,Hkv,e)
        print("OK ulysses raises", Hq, Hkv)
""", devices=4)
    assert out.count("OK") == 4
