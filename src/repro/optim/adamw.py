"""AdamW with warmup-cosine schedule and global-norm clipping (pure JAX).

Optimizer state mirrors the parameter pytree (and therefore the parameter
FSDP sharding — the m/v moments shard identically to their parameter).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import TrainConfig


class AdamWState(NamedTuple):
    step: jnp.ndarray
    m: dict
    v: dict


def init(params) -> AdamWState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros,
                      v=jax.tree.map(jnp.copy, zeros))


def schedule(step, tc: TrainConfig):
    warm = tc.lr * (step + 1) / max(tc.warmup_steps, 1)
    prog = jnp.clip((step - tc.warmup_steps) /
                    max(tc.total_steps - tc.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * tc.lr * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < tc.warmup_steps, warm, cos)


def clip_by_global_norm(grads, max_norm):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-6))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gn


def update(grads, state: AdamWState, params, tc: TrainConfig):
    """Returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, tc.max_grad_norm)
    step = state.step + 1
    lr = schedule(state.step, tc)
    b1, b2 = tc.beta1, tc.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + tc.eps)
        upd = upd + tc.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), m2, v2

    out = jax.tree.map(upd, params, grads, state.m, state.v)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t:
                         isinstance(t, tuple) and len(t) == 3)
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t:
                         isinstance(t, tuple) and len(t) == 3)
    new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t:
                         isinstance(t, tuple) and len(t) == 3)
    return new_p, AdamWState(step, new_m, new_v), {"lr": lr, "gnorm": gnorm}
