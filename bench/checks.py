"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

Training (per cell, the program's first steps against the reference's):

* ``loss``: the largest relative gap of a step's loss;
* ``gnorm``: the largest relative gap of a step's global gradient norm
  before clipping;
* ``grad_leaf``: over the leaves, the largest gap between the program's
  and the reference's norm of the first (clipped) gradient, as a share of
  the reference's norm of that leaf or of the median leaf, whichever is
  larger;
* ``update_leaf``: the same of the weights' change over the steps, leaving
  out leaves whose reference gradient is under a thousandth of the median
  leaf's (they move by round-off alone);
* ``grad_diff``: over the leaves, the norm of the difference between the
  program's first gradient and the reference's, as a share of the
  reference's norm of that leaf or of the median leaf, whichever is
  larger. Gaps of norms cancel random rounding of either sign; this one
  does not, and it is the number that tells a float8 computation from
  the program's bfloat16 one (PERF.md, section 2).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from harness import Check

QUIET_LEAF = 1e-3


@dataclasses.dataclass
class TrainReading:
    losses: List[float]
    gnorms: List[float]
    grad_leaf: np.ndarray         # per-leaf norms of the first gradient
    change_leaf: np.ndarray       # per-leaf norms of the weights' change
    first: object = None          # the first gradient: {path: array}
    grad_diff_leaf: np.ndarray = None   # per-leaf norms of got - want


def grad_diff(got: TrainReading, want: TrainReading) -> None:
    """Per leaf, the norm of the difference between the program's first
    gradient and the reference's, set on ``got``; both gradients are then
    dropped. The reference's is on the device, the program's on the host;
    one leaf at a time goes over."""
    import jax
    import jax.numpy as jnp
    from weights import flatten
    ref, mine = flatten(want.first), flatten(got.first)
    out = []
    for k in sorted(ref):
        r = ref[k]
        g = jax.device_put(mine[k], r.sharding)
        out.append(float(jnp.sqrt(jnp.sum(jnp.square(
            g - r.astype(jnp.float32))))))
    got.grad_diff_leaf = np.asarray(out)
    got.first = want.first = None


def rel(a, b) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def leaf_gaps(got: np.ndarray, want: np.ndarray, keep=None) -> np.ndarray:
    """Per leaf: the gap between the two norms, as a share of the
    reference's norm of that leaf or of the median leaf, if larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if keep is not None:
        got, want = got[keep], want[keep]
    floor = np.maximum(want, np.median(want))
    return np.abs(got - want) / np.maximum(floor, 1e-30)


def train_numbers(got: TrainReading, want: TrainReading) -> Dict[str, float]:
    keep = want.grad_leaf >= QUIET_LEAF * np.median(want.grad_leaf)
    g = leaf_gaps(got.grad_leaf, want.grad_leaf)
    u = leaf_gaps(got.change_leaf, want.change_leaf, keep)
    out = {
        "loss": max(rel(a, b) for a, b in zip(got.losses, want.losses)),
        "loss0": rel(got.losses[0], want.losses[0]),
        "gnorm": max(rel(a, b) for a, b in zip(got.gnorms, want.gnorms)),
        "gnorm0": rel(got.gnorms[0], want.gnorms[0]),
        "grad_leaf": float(np.max(g)),
        "grad_leaf_median": float(np.median(g)),
        "update_leaf": float(np.max(u)),
        "update_leaf_median": float(np.median(u)),
    }
    if got.grad_diff_leaf is not None:
        floor = np.maximum(want.grad_leaf, np.median(want.grad_leaf))
        out["grad_diff"] = float(np.max(got.grad_diff_leaf / floor))
    return out


def as_checks(numbers: Dict[str, float], limits: Dict[str, float]):
    """One Check per limit the cell sets; a number that is not finite
    fails."""
    out = []
    for name, limit in limits.items():
        v = float(numbers.get(name, float("nan")))
        out.append(Check(name, v if np.isfinite(v) else float("inf"),
                         float(limit)))
    return out


def train_checks(got: TrainReading, want: TrainReading,
                 limits: Dict[str, float]):
    return as_checks(train_numbers(got, want), limits)

