"""Substrate tests: optimizer, data pipeline, checkpoint I/O, sharding
rules, config registry."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import (ARCH_IDS, SHAPES, TrainConfig, get_config,
                               get_shape)
from repro.data.pipeline import SyntheticTokens
from repro.io import checkpoint as ckpt
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config, param_shardings
from repro.launch.mesh import make_mesh


def test_adamw_converges_on_quadratic():
    tc = TrainConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                     total_steps=200)
    params = {"w": jnp.array([3.0, -2.0])}
    opt = adamw.init(params)
    for _ in range(150):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, m = adamw.update(g, opt, params, tc)
    assert float(jnp.abs(params["w"]).max()) < 0.05
    assert m["gnorm"] >= 0


def test_adamw_clips_gradients():
    tc = TrainConfig(max_grad_norm=1.0)
    g = {"w": jnp.full((4,), 100.0)}
    clipped, gn = adamw.clip_by_global_norm(g, tc.max_grad_norm)
    assert float(jnp.linalg.norm(clipped["w"])) <= 1.0 + 1e-5
    assert float(gn) == pytest.approx(200.0)


def test_synthetic_data_deterministic_and_learnable():
    cfg = get_config("smollm-360m")
    from repro.core.config import smoke_config, ShapeSpec
    cfg = smoke_config(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("s", 32, 2, "train")
    par = make_parallel_config(mesh, shape)
    ds = SyntheticTokens(cfg, shape, par, mesh, seed=7)
    b1 = ds.batch(3)
    b2 = ds.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = ds.batch(4)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # markov structure: next-token often equals (31·x+7) mod v
    t = np.asarray(b1["tokens"])[0]
    l = np.asarray(b1["labels"])[0]
    v = min(cfg.vocab, 1024)
    frac = np.mean(l == (t * 31 + 7) % v)
    assert frac > 0.5


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ckpt.save(str(tmp_path / "x"), tree, step=17)
    back = ckpt.restore(str(tmp_path / "x"), tree)
    assert ckpt.latest_step(str(tmp_path / "x")) == 17
    np.testing.assert_array_equal(np.asarray(back["a"]),
                                  np.asarray(tree["a"]))
    assert back["b"]["c"].dtype == jnp.bfloat16


def test_parallel_config_resolution():
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("pod", "data", "model")

        class devices:
            shape = (2, 16, 16)
    for name, shape in SHAPES.items():
        par = make_parallel_config(FakeMesh, shape)
        if name == "train_4k":
            assert par.batch_axes == ("pod", "data")
        if name == "long_500k":
            assert par.batch_axes == () and "data" in par.extra_seq_axes
        if name == "decode_32k":
            assert par.batch_axes == ("pod", "data")


def test_param_shardings_cover_all_leaves():
    from repro.core.config import smoke_config, ShapeSpec
    from repro.models.transformer import Runtime, build_model
    cfg = smoke_config(get_config("deepseek-v2-lite-16b"))
    mesh = make_mesh((1, 1), ("data", "model"))
    par = make_parallel_config(mesh, ShapeSpec("s", 32, 2, "train"))
    model = build_model(cfg, Runtime(mesh=mesh, par=par, impl="ref"))
    ps = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    sh = param_shardings(ps, mesh, par)
    assert jax.tree.structure(ps) == jax.tree.structure(sh)


def test_registry_loads_all_archs():
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.name == a and cfg.citation
    assert get_shape("train_4k").global_batch == 256
