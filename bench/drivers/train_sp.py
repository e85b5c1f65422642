"""Training cells whose sequence is split over several chips: the ``train``
driver as it is (the program's Trainer, its first steps, the window), with
its reference followed by ``reference_sp``, the same plain reference with
its attention split over the chips, so that the check after the window
takes seconds, not minutes, at tens of thousands of tokens.

``build``, ``put``, ``first_steps`` and ``N_CHECK`` are the ``train``
driver's; ``reference_reading`` is this one's.
"""
from __future__ import annotations

import numpy as np

import checks
import harness as H
import reference_sp
import weights

_train = H.load_module("drivers", "train")
N_CHECK = _train.N_CHECK
build, put, first_steps = _train.build, _train.put, _train.first_steps


def reference_reading(ctx: H.Ctx, rows, precision="fp32"):
    """The reference's readings over the same first steps, from the seed's
    weights made again, its work split over the chips."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    mesh = jax.sharding.Mesh(np.array(ctx.devices), ("s",))
    p = weights.make(ctx.config, H.jax_key(ctx.seed),
                     shardings=SingleDeviceSharding(ctx.devices[0]))
    rep = NamedSharding(mesh, PartitionSpec())
    bs = [{k: jax.device_put(v, rep) for k, v in r.items()} for r in rows]
    losses, gnorms, first, p3 = reference_sp.train_steps(
        ctx.config, ctx.workload["optimizer"], p, bs, precision, mesh)
    g1 = np.asarray(weights.leaf_norms(first))
    p0 = weights.make(ctx.config, H.jax_key(ctx.seed),
                      shardings=jax.tree.map(lambda x: x.sharding, p3))
    change = np.asarray(weights.diff_norms(p3, p0))
    del p0, p3
    return checks.TrainReading(losses, gnorms, g1, change, first)


# the train driver's run, following this driver's reference
_train.reference_reading = reference_reading
run = _train.run
