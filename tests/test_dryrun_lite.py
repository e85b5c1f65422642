"""Dry-run plumbing on a tiny in-process mesh: lower+compile smoke configs
for each step kind and check the analyses surface (the production 512-dev
sweep runs via ``python -m repro.launch.dryrun --all``)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import roofline as R
from repro.core.config import (ShapeSpec, TrainConfig, get_config,
                               smoke_config)
from repro.data.pipeline import cache_specs, input_specs
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config, param_shardings
from repro.train.step import make_train_step
from repro.launch.mesh import make_mesh


@pytest.mark.parametrize("arch,shape_kind", [
    ("smollm-360m", "train"), ("deepseek-v2-lite-16b", "train"),
    ("mamba2-2.7b", "decode"), ("whisper-tiny", "prefill"),
    ("zamba2-2.7b", "decode"),
])
def test_lower_compile_and_analyses(arch, shape_kind):
    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("lite", 64, 2, shape_kind)
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    p_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = param_shardings(p_struct, mesh, par)
    batch_struct, batch_spec = input_specs(cfg, shape, par, mesh)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_spec,
                            is_leaf=lambda x: isinstance(x, P))
    if shape_kind == "train":
        step = make_train_step(model, TrainConfig())
        opt_struct = jax.eval_shape(adamw.init, p_struct)
        lowered = jax.jit(step).lower(p_struct, opt_struct, batch_struct)
    elif shape_kind == "prefill":
        lowered = jax.jit(lambda p, b: model.prefill(p, b)[0]).lower(
            p_struct, batch_struct)
    else:
        cache_struct = batch_struct.pop("cache")
        lowered = jax.jit(lambda p, c, b: model.decode(p, c, b)).lower(
            p_struct, cache_struct, batch_struct)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0


def test_collective_parser_on_known_hlo():
    txt = """
  %x = bf16[16,128]{1,0} collective-permute(%p), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %y = f32[4,256]{1,0} all-gather(%p2), replica_groups={{0,1,2,3}}, dimensions={0}
  %z = f32[64]{0} all-reduce(%p3), replica_groups={{0,1}}
"""
    st = R.collective_stats(txt)
    assert st.op_counts == {"collective-permute": 1, "all-gather": 1,
                            "all-reduce": 1}
    assert st.bytes_by_kind["collective-permute"] == 16 * 128 * 2
    assert abs(st.bytes_by_kind["all-gather"] - 4 * 256 * 4 * 3 / 4) < 1
    assert abs(st.bytes_by_kind["all-reduce"] - 2 * 64 * 4 / 2) < 1


def test_attention_analytic_sane():
    from repro.core.config import get_shape
    cfg = get_config("qwen3-8b")
    fl, by = R.attention_analytic(cfg, get_shape("train_4k"),
                                  seq_shards=16, batch_shards=16)
    # per-chip causal attention flops: L·B_loc·T²/2/P·H·2·2·2hd ~ 1e12 scale
    assert 1e10 < fl < 1e14 and 1e7 < by < 1e12


def test_roofline_terms_bounds():
    t = R.roofline_terms(197e12, 819e9, 50e9)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
    assert t["step_s_lower_bound"] == pytest.approx(1.0)
