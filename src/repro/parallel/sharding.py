"""Sharding rules: mesh axes, FSDP parameter layout, activation specs.

Axis roles (DESIGN.md §4):
  * ``pod``, ``data``  — batch / FSDP axes (ZeRO-3 parameter+optimizer
    sharding, gather-on-use), matching the paper's use of FSDP alongside
    DISTFLASHATTN (§E).
  * ``model``          — the sequence-parallel axis (the paper's P workers);
    also hosts expert parallelism for MoE FFNs.

Parameters are sharded by a path/shape rule: routed-expert stacks shard
their expert dim over ``model`` and their FFN dim over the FSDP axes; every
other ≥2-D tensor shards its largest FSDP-divisible dim; small/1-D tensors
replicate.
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.config import ParallelConfig, ShapeSpec

MOE_EXPERT_KEYS = ("wg", "wu", "wd")


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def make_parallel_config(mesh: Mesh, shape: ShapeSpec,
                         schedule: str = "balanced",
                         remat: str = "remat_aware") -> ParallelConfig:
    """Resolve axis roles for a given input shape on a given mesh.

    Batch shards over as many of (pod, data) as divide it; for long-context
    decode with batch=1 the freed ``data`` axis is folded into the sequence
    sharding (2D sequence sharding — beyond-paper, DESIGN.md §4).
    """
    names = list(mesh.axis_names)
    cand = [a for a in ("pod", "data") if a in names]
    batch_axes, extra_seq = [], []
    b = shape.global_batch
    for a in cand:
        sz = mesh_axis_size(mesh, a)
        if b % sz == 0 and b >= sz:
            batch_axes.append(a)
            b //= sz
        elif shape.kind == "decode" and a == "data":
            extra_seq.append(a)
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    # a 2D (seq × head) mesh (launch.mesh.make_seq2d_mesh) names its
    # sequence sub-axis "seq" and exposes "head" for the ulysses-style
    # head scatter; legacy meshes keep the single "model" axis
    seq_axis = "seq" if "seq" in names else "model"
    head_axis = "head" if "head" in names else None
    return ParallelConfig(batch_axes=tuple(batch_axes), seq_axis=seq_axis,
                          extra_seq_axes=tuple(extra_seq), fsdp_axes=fsdp,
                          schedule=schedule, remat=remat,
                          head_axis=head_axis)


def _largest_divisible_dim(shape, skip, n):
    best, best_size = None, 0
    for i, s in enumerate(shape):
        if i in skip:
            continue
        if s % n == 0 and s > best_size:
            best, best_size = i, s
    return best


def param_spec(path: str, shape: Tuple[int, ...], par: ParallelConfig,
               fsdp_size: int) -> P:
    """FSDP PartitionSpec for one parameter."""
    spec = [None] * len(shape)
    skip = set()
    if "moe" in path and path.split("/")[-1] in MOE_EXPERT_KEYS:
        # (L?, E, d, de): expert dim → seq axis
        e_dim = len(shape) - 3
        spec[e_dim] = par.seq_axis
        skip.add(e_dim)
    if fsdp_size > 1:
        i = _largest_divisible_dim(shape, skip | {j for j, s in
                                                  enumerate(shape) if
                                                  spec[j] is not None}, fsdp_size)
        # never FSDP-shard the stacked-layer dim (dim 0 of stacked params) if
        # another dim qualifies; prefer the last dims
        if i is not None and len(shape) >= 2:
            spec[i] = tuple(par.fsdp_axes) if len(par.fsdp_axes) > 1 \
                else par.fsdp_axes[0]
    return P(*spec)


def param_shardings(params, mesh: Mesh, par: ParallelConfig):
    """NamedShardings for a parameter pytree (keyed by tree path)."""
    fsdp_size = 1
    for a in par.fsdp_axes:
        fsdp_size *= mesh_axis_size(mesh, a)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, 'key', getattr(k, 'idx', k)))
                        for k in path)
        if leaf.ndim <= 1:
            specs.append(P())
        else:
            specs.append(param_spec(pstr, leaf.shape, par, fsdp_size))
    specs = jax.tree_util.tree_unflatten(treedef, specs)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def act_spec(par: ParallelConfig, seq_sharded=True) -> P:
    b = tuple(par.batch_axes) if par.batch_axes else None
    if not seq_sharded:
        return P(b, None, None)
    s = par.seq_axes if len(par.seq_axes) > 1 else par.seq_axis
    return P(b, s, None)


def batch_spec(par: ParallelConfig) -> P:
    b = tuple(par.batch_axes) if par.batch_axes else None
    s = par.seq_axes if len(par.seq_axes) > 1 else par.seq_axis
    return P(b, s)


def constrain(x, mesh, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
