"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family (2 layers, d_model ≤ 512, ≤ 4 experts) runs one forward +
one train step on CPU; output shapes and finiteness asserted."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.config import (ARCH_IDS, TrainConfig, get_config,
                               smoke_config, ShapeSpec)
from repro.data.pipeline import SyntheticTokens, cache_specs
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config
from repro.train.step import make_train_step
from repro.launch.mesh import make_mesh

SHAPE = ShapeSpec("smoke", 64, 2, "train")

# the heaviest smoke params (deep MoE/MTP stacks) are slow-marked so the
# default tier-1 run stays under ~5 minutes; tools/run_tier1.sh --all runs
# them too
SLOW_ARCHS = {"deepseek-v2-lite-16b", "deepseek-v3-671b"}
ARCH_PARAMS = [pytest.param(a, marks=pytest.mark.slow) if a in SLOW_ARCHS
               else a for a in ARCH_IDS]


def _setup(arch):
    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    par = make_parallel_config(mesh, SHAPE)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticTokens(cfg, SHAPE, par, mesh).batch(0)
    return cfg, model, params, batch, mesh, par


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_config_matches_family(arch):
    cfg = smoke_config(get_config(arch))
    full = get_config(arch)
    assert cfg.arch_type == full.arch_type
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_routed <= 4
    # family-defining features preserved
    if full.attn:
        assert (cfg.attn.is_mla == full.attn.is_mla
                and cfg.attn.qkv_bias == full.attn.qkv_bias
                and cfg.attn.qk_norm == full.attn.qk_norm)


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_forward_and_train_step(arch):
    cfg, model, params, batch, mesh, par = _setup(arch)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == () and bool(jnp.isfinite(loss)), arch
    step = make_train_step(model, TrainConfig(warmup_steps=1, total_steps=10))
    opt = adamw.init(params)
    p2, o2, m = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(p2)), arch
    # params actually moved
    moved = any(bool(jnp.any(a != b)) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved, arch


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_prefill_and_decode_shapes(arch):
    cfg, model, params, batch, mesh, par = _setup(arch)
    B = SHAPE.global_batch
    logits, _ = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, 1, cfg.vocab)
    assert not bool(jnp.isnan(logits).any()), arch
    dshape = ShapeSpec("smoke_dec", 64, B, "decode")
    dpar = make_parallel_config(mesh, dshape)
    cstruct, _ = cache_specs(cfg, dshape, dpar)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cstruct)
    dmodel = build_model(cfg, Runtime(mesh=mesh, par=dpar, impl="ref"))
    lg, cache2 = jax.jit(dmodel.decode)(
        params, cache, {"token": jnp.zeros((B, 1), jnp.int32),
                        "pos": jnp.int32(64)})
    assert lg.shape == (B, 1, cfg.vocab) and not bool(jnp.isnan(lg).any())
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_param_counts_match_spec():
    """Full configs approximate their nameplate sizes."""
    expect = {
        "smollm-360m": (0.30e9, 0.50e9),
        "qwen3-8b": (7e9, 9.5e9),
        "qwen2.5-14b": (12e9, 16e9),
        "qwen1.5-32b": (29e9, 36e9),
        "mamba2-2.7b": (2.2e9, 3.2e9),
        "deepseek-v2-lite-16b": (12e9, 18e9),
        "deepseek-v3-671b": (550e9, 720e9),
        "zamba2-2.7b": (2.0e9, 3.3e9),
        "internvl2-2b": (1.5e9, 2.5e9),
        "whisper-tiny": (25e6, 60e6),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
