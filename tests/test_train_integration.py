"""End-to-end training integration: a tiny model trains for a few dozen
steps on the synthetic Markov stream and the loss must drop substantially
(system-level behaviour, paper-faithful config: balanced schedule +
remat-aware checkpointing)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.config import (TrainConfig, get_config, smoke_config,
                               ShapeSpec)
from repro.data.pipeline import SyntheticTokens
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config
from repro.train.step import make_train_step
from repro.launch.mesh import make_mesh


def _train(arch, steps=30, remat="remat_aware", schedule="balanced"):
    cfg = smoke_config(get_config(arch)).replace(vocab=128)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("ti", 64, 4, "train")
    par = make_parallel_config(mesh, shape, schedule=schedule, remat=remat)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params)
    tc = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=steps)
    step = jax.jit(make_train_step(model, tc))
    ds = SyntheticTokens(cfg, shape, par, mesh)
    losses = []
    for i in range(steps):
        params, opt, m = step(params, opt, ds.batch(i))
        losses.append(float(m["loss"]))
    return losses


def test_loss_decreases_dense():
    losses = _train("smollm-360m")
    assert losses[-1] < losses[0] - 0.5, losses[::10]


def test_loss_decreases_ssm():
    losses = _train("mamba2-2.7b", steps=25)
    assert losses[-1] < losses[0] - 0.5, losses[::8]


def test_remat_policies_agree():
    """The three checkpointing policies give the same loss trajectory
    (the paper's 'no numerical difference' claim, end to end)."""
    base = _train("smollm-360m", steps=4, remat="none")
    for pol in ("hf", "remat_aware"):
        other = _train("smollm-360m", steps=4, remat=pol)
        for a, b in zip(base, other):
            assert abs(a - b) < 2e-3, (pol, base, other)


def test_schedules_agree():
    base = _train("smollm-360m", steps=3, schedule="balanced")
    other = _train("smollm-360m", steps=3, schedule="ring")
    for a, b in zip(base, other):
        assert abs(a - b) < 2e-3


def test_nonfinite_step_is_skipped_params_bit_identical():
    """Non-finite guard: a poisoned step (NaN injected into a param leaf —
    batch tokens are integers, so the NaN enters through the forward the
    same way a poisoned batch would: NaN loss and NaN grads) must skip the
    optimizer update, report ``skipped_nonfinite``, and leave params AND
    optimizer state bit-identical; a healthy step afterwards updates
    normally."""
    cfg = smoke_config(get_config("smollm-360m")).replace(vocab=128)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("ti", 64, 4, "train")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params)
    tc = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=10)
    step = jax.jit(make_train_step(model, tc))
    ds = SyntheticTokens(cfg, shape, par, mesh)

    # one healthy step compiles + moves state off the init values
    params, opt, m = step(params, opt, ds.batch(0))
    assert int(m["skipped_nonfinite"]) == 0

    # poison one scalar: the loss and every grad go non-finite
    poisoned = jax.tree_util.tree_map(lambda x: x, params)
    poisoned["embed"] = poisoned["embed"].at[0, 0].set(jnp.nan)
    p2, o2, m2 = step(poisoned, opt, ds.batch(1))
    assert int(m2["skipped_nonfinite"]) == 1
    assert not jnp.isfinite(m2["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(p2),
                    jax.tree_util.tree_leaves(poisoned)):
        assert a.dtype == b.dtype
        assert jnp.array_equal(a, b, equal_nan=True), "params changed " \
            "on a skipped step"
    for a, b in zip(jax.tree_util.tree_leaves(o2),
                    jax.tree_util.tree_leaves(opt)):
        assert jnp.array_equal(a, b, equal_nan=True), "optimizer state " \
            "changed on a skipped step"
    assert int(o2.step) == int(opt.step)

    # recovery: the next healthy step updates params again
    p3, o3, m3 = step(params, opt, ds.batch(2))
    assert int(m3["skipped_nonfinite"]) == 0
    assert int(o3.step) == int(opt.step) + 1
    assert any(not jnp.array_equal(a, b)
               for a, b in zip(jax.tree_util.tree_leaves(p3),
                               jax.tree_util.tree_leaves(params)))
