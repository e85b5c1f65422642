"""Auto-schedule capability/runtime consistency sweep (the trace-time
filter must agree with execution): every architecture in the config zoo ×
P ∈ {2, 4, 8} × every MaskSpec kind goes through ``choose_schedule``, and
whatever name (or 2D factorization triple) it resolves must be one the
runtime accepts — ``plan_capable`` holds, the plan builds, and the
``DistAttnSpec`` validation that guards execution passes.  A clean
"no capable" ``ValueError`` at trace time is the only acceptable
alternative; the resolved schedule raising later, inside shard_map, is
exactly the bug class this sweep pins down."""
import pytest

from repro.core import dist_attention as da
from repro.core import mask as mk
from repro.core import schedule as sp
from repro.core.config import ARCH_IDS, PAPER_ARCH_IDS, get_config

ALL_ARCHS = ARCH_IDS + PAPER_ARCH_IDS


def _head_shapes():
    """(arch, Hq, Hkv, Dqk) for every config with an attention block."""
    out = []
    for a in ALL_ARCHS:
        cfg = get_config(a)
        if cfg.attn is None:          # mamba2: no attention sites
            continue
        out.append((a, cfg.attn.n_heads, cfg.attn.n_kv_heads,
                    cfg.attn.head_dim))
    assert len(out) >= 10
    return out


def _mask_cases(T):
    """One MaskSpec per declarative kind, plus the dynamic-segment
    document variant (dynamic_seg mirrors segments= at the call site)."""
    return {
        "causal":           (mk.causal(), False),
        "full":             (mk.full(), False),
        "window":           (mk.sliding_window(max(3, T // 4)), False),
        "noncausal-window": (mk.sliding_window(max(3, T // 4),
                                               causal=False), False),
        "prefix":           (mk.prefix_lm(max(2, T // 4)), False),
        "doc-static":       (mk.document(boundaries=(0, T // 2)), False),
        "doc-dynamic":      (mk.document(), True),
    }


def _assert_runtime_accepts(name, mask, P, Hq, Hkv, *, include_bwd):
    """The runtime-side mirror of the trace-time filter.  Any assertion
    tripping here means ``choose_schedule`` resolved a schedule that
    execution would reject — the fix belongs in the filter."""
    if name == "ulysses":
        # head scatter needs exact divisibility on both head counts
        assert Hq % P == 0 and Hkv % P == 0, (Hq, Hkv, P)
        if include_bwd:
            # the ulysses backward reuses the ring plan: masks the ring
            # cannot express must have been filtered out at trace time
            assert not mask.prefix_len, mask
            assert not (mask.window and not mask.causal), mask
    else:
        assert sp.plan_capable(name, mask), (name, mask)
        sp.build_plan(name, mask, P, 64)          # must not raise
    # spec-level validation guards every execution entry point
    da.DistAttnSpec(axis_size=P, schedule=name, mask=mask)


@pytest.mark.parametrize("P", [2, 4, 8])
def test_choose_schedule_consistent_with_runtime_across_zoo(P):
    """ACCEPTANCE (satellite): sweep every config in the zoo × mask kind
    × cost horizon through ``choose_schedule`` and assert the resolved
    schedule never raises at execution time."""
    resolved = 0
    for arch, Hq, Hkv, D in _head_shapes():
        T = P * 32
        for mname, (mask, dyn) in _mask_cases(T).items():
            for include_bwd in (False, True):
                try:
                    name = sp.choose_schedule(
                        mask, P, Tl=T // P, Hq=Hq, Hkv=Hkv, Dqk=D,
                        dynamic_seg=dyn, include_bwd=include_bwd)
                except ValueError as e:
                    # the only legal trace-time outcome besides a name
                    assert "no capable" in str(e), (arch, mname, e)
                    continue
                _assert_runtime_accepts(name, mask, P, Hq, Hkv,
                                        include_bwd=include_bwd)
                resolved += 1
    assert resolved > 0


@pytest.mark.parametrize("P", [2, 4, 8])
def test_factorized_choice_consistent_with_runtime(P):
    """Same sweep over the 2D (r, u) factorization space: every returned
    triple must build (``build_plan2d`` for u > 1, ``build_plan`` for
    u == 1) and pass ``DistAttnSpec`` validation with the matching
    ``Mesh2DSpec``."""
    for arch, Hq, Hkv, D in _head_shapes():
        T = P * 32
        for mname, (mask, dyn) in _mask_cases(T).items():
            for include_bwd in (False, True):
                try:
                    name, r, u = sp.choose_schedule(
                        mask, P, Tl=T // P, Hq=Hq, Hkv=Hkv, Dqk=D,
                        dynamic_seg=dyn, include_bwd=include_bwd,
                        factorize=True)
                except ValueError as e:
                    assert "factorization" in str(e), (arch, mname, e)
                    continue
                assert r * u == P, (name, r, u)
                if u == 1:
                    assert sp.plan_capable(name, mask)
                    sp.build_plan(name, mask, r, 64)
                    da.DistAttnSpec(axis_size=P, schedule=name, mask=mask)
                else:
                    sp.build_plan2d(name, mask, r, u, 64, Hq=Hq, Hkv=Hkv)
                    da.DistAttnSpec(
                        axis="seq", axis_size=P, schedule=name, mask=mask,
                        mesh2d=da.Mesh2DSpec(r=r, u=u))


def test_auto_resolution_executes_on_devices(subproc):
    """Representative end-to-end slice of the sweep on a real 8-device
    mesh: ``schedule="auto"`` traces and runs (fwd, and grads where the
    horizon allows) for divisible, GQA, and indivisible head shapes
    across the mask kinds — and where nothing is capable the failure is
    the clean trace-time ValueError, never a mid-execution raise."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_flash_attn
B,N,D = 1,128,16
mesh = make_mesh((1,8), ("data","model"))
def run(Hq, Hkv, m, seg=None, grad=False):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B,N,Hq,D), jnp.float32)
    k = jax.random.normal(ks[1], (B,N,Hkv,D), jnp.float32)
    v = jax.random.normal(ks[2], (B,N,Hkv,D), jnp.float32)
    spec = DistAttnSpec(axis="model", axis_size=8, schedule="auto", mask=m)
    if grad:
        def loss(q,k,v):
            o,_ = dist_flash_attn(q,k,v,mesh,spec,segments=seg,batch_axes=None)
            return jnp.sum(o**2)
        jax.jit(jax.grad(loss, argnums=(0,1,2)))(q,k,v)
    else:
        jax.jit(lambda q,k,v: dist_flash_attn(
            q,k,v,mesh,spec,segments=seg,batch_axes=None))(q,k,v)
seg = jnp.concatenate([jnp.zeros((B,N//2),jnp.int32),
                       jnp.ones((B,N-N//2),jnp.int32)], axis=1)
for (Hq,Hkv) in ((16,16),(32,8),(15,5)):
    for m in (mk.causal(), mk.sliding_window(32), mk.full(),
              mk.document(boundaries=(0, N//2))):
        run(Hq,Hkv,m)
    run(Hq,Hkv,mk.document(),seg=seg)
    run(Hq,Hkv,mk.causal(),grad=True)
    print("OK fwd+grad", Hq, Hkv)
# prefix_lm: forward-capable only through ulysses (divisible heads)...
run(16,16,mk.prefix_lm(32))
print("OK prefix fwd 16/16")
# ...its backward must fail at TRACE time with the clean chooser error
try:
    run(16,16,mk.prefix_lm(32),grad=True)
    raise SystemExit("prefix bwd should have raised")
except ValueError as e:
    assert "no capable" in str(e), e
    print("OK prefix bwd trace-time error")
# indivisible heads + prefix: not even a forward candidate exists
try:
    run(15,5,mk.prefix_lm(32))
    raise SystemExit("prefix fwd 15/5 should have raised")
except ValueError as e:
    assert "no capable" in str(e), e
    print("OK prefix indivisible trace-time error")
""")
    assert out.count("OK") == 6
