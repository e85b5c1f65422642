"""Quickstart: train a tiny DISTFLASHATTN-powered LLaMA-family model for a
few steps on CPU, then generate from it.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.core.config import ShapeSpec, TrainConfig, get_config, smoke_config
from repro.data.pipeline import SyntheticTokens
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine
from repro.train.step import make_train_step


def main():
    cfg = smoke_config(get_config("smollm-360m")).replace(vocab=128)
    mesh = make_local_mesh()
    shape = ShapeSpec("quick", 64, 4, "train")
    # balanced schedule + rematerialization-aware checkpointing — the
    # paper's configuration — are the defaults
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))

    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params)
    step = jax.jit(make_train_step(model, TrainConfig(lr=3e-3,
                                                      warmup_steps=5,
                                                      total_steps=40)))
    ds = SyntheticTokens(cfg, shape, par, mesh)
    for i in range(40):
        params, opt, m = step(params, opt, ds.batch(i))
        if i % 10 == 0 or i == 39:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}")

    print("\ngenerating…")
    eng = Engine(model, params)                  # paged continuous batching
    toks = eng.generate(ds.batch(0), n_tokens=8)
    print("greedy continuation of request 0:", [int(t) for t in toks[0]])


if __name__ == "__main__":
    main()
