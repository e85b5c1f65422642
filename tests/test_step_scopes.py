"""The names the training step gives its device work, read back from the
compiled step with the benchmark's own reduction (``bench/scopes.py``):
every op of the step falls in exactly one phase, and the remat-aware
combinator recomputes no attention while layer-boundary checkpointing
does."""
import sys
from pathlib import Path

import jax
import pytest

from repro.core.config import ShapeSpec, TrainConfig, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.launch.mesh import make_mesh
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config
from repro.train.step import make_train_step

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.append(str(BENCH))
import scopes  # noqa: E402


def _step_ops(remat):
    """{instruction: op_name} of the compiled smoke-sized train step."""
    cfg = smoke_config(get_config("smollm-360m")).replace(vocab=128)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("scopes", 64, 2, "train")
    par = make_parallel_config(mesh, shape, schedule="balanced", remat=remat)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    tc = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    batch = SyntheticTokens(cfg, shape, par, mesh).batch(0)
    text = jax.jit(make_train_step(model, tc)).lower(
        params, adamw.init(params), batch).compile().as_text()
    return {n: op for n, (op, _) in scopes.instructions(text).items() if op}


@pytest.fixture(scope="module", params=["remat_aware", "hf"])
def step_ops(request):
    return request.param, _step_ops(request.param)


def test_every_op_falls_in_exactly_one_phase(step_ops):
    _, ops = step_ops
    placed = {p: [n for n, op in ops.items() if scopes.phase(op) == p]
              for p in scopes.PHASES}
    assert sum(map(len, placed.values())) == len(ops)
    assert all(placed.values()), {p: len(v) for p, v in placed.items()}
    for n in placed["optimizer"]:
        assert "transpose(" not in ops[n]          # outside the gradient
    for tag in scopes.TAGS:
        phases = {scopes.phase(op) for op in ops.values()
                  if tag in scopes.tags(op)}
        assert {"forward", "backward"} <= phases, (tag, phases)


def test_only_layer_boundary_checkpointing_recomputes_attention(step_ops):
    remat, ops = step_ops
    recomputed = [op for op in ops.values()
                  if scopes.phase(op) == "recompute"
                  and "attn" in scopes.tags(op)]
    if remat == "remat_aware":
        assert not recomputed, recomputed[:5]
    else:
        assert recomputed


@pytest.mark.parametrize("op_name, phase, tags", [
    ("jit(step)/optimizer/jit(clip)/min", "optimizer", []),
    ("jit(step)/transpose(jvp())/while/body/closed_call/recompute/jvp()/dot",
     "recompute", []),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/exp", "recompute", ["attn"]),
    ("jit(step)/transpose(jvp())/while/body/closed_call/attn/jit(flash_bwd)/"
     "flash_bwd.dq/pallas_call", "backward", ["attn"]),
    ("jit(step)/transpose(jvp(head))/dot_general", "backward", ["head"]),
    ("jit(step)/jvp(head)/reduce_max", "forward", ["head"]),
    ("jit(step)/jvp()/while/body/closed_call/attn/jit(flash_fwd)/flash_fwd/"
     "pallas_call", "forward", ["attn"]),
    ("", None, []),
])
def test_phase_rule_by_hand(op_name, phase, tags):
    assert scopes.phase(op_name) == phase
    assert scopes.tags(op_name) == tags
