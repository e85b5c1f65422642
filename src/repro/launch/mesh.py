"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips;
multi-pod: (pod=2, data=16, model=16) = 512 chips. The ``model`` axis is
the DISTFLASHATTN sequence-parallel axis.

Every mesh of the program is built by :func:`make_mesh`, with ``Auto``
axis types: the model code places arrays with ``NamedSharding`` and
``shard_map`` and relies on the compiler to propagate shardings, which
``jax.make_mesh``'s default ``Explicit`` axes would turn into type errors
(a gather such as ``embed[tokens]`` then needs an ``out_sharding=``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(seq: int = 1, data: int | None = None):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if data is None:
        data = n // seq
    return make_mesh((data, seq), ("data", "model"))


def make_seq2d_mesh(r: int, u: int, data: int = 1):
    """Factored sequence×head mesh for the 2D (ring×ulysses) attention
    plans: ``r·u`` sequence-parallel workers as a (``seq`` = r,
    ``head`` = u) grid, head minor so the head-axis all-to-all stays
    intra-group (intra-node on real hardware — BurstAttention's split).
    Activations shard the sequence over the ("seq", "head") axis *pair*;
    ``parallel.sharding.make_parallel_config`` picks the axes up by name."""
    return make_mesh((data, r, u), ("data", "seq", "head"))
