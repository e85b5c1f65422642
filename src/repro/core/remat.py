"""Rematerialization-aware gradient checkpointing (paper §3.3).

Standard ("HuggingFace-style") gradient checkpointing puts the checkpoint at
the Transformer-layer boundary: during the backward pass the *entire* layer
forward — including the FlashAttention kernel — is recomputed, even though
the FA backward kernel already rematerializes the softmax internally from
``(q, k, v, o, lse)``. The paper moves the checkpoint boundary to the
attention *output*: save ``(o, lse)``, recompute only the cheap
pre/post-attention projections, and feed the FA backward directly. Zero
numerical difference; the FA forward (and, distributed, its forward
communication) runs exactly once per step.

We implement this as an explicit ``jax.custom_vjp`` *combinator* rather than
relying on ``jax.checkpoint`` policies reaching through ``custom_vjp``
residuals (fragile — see DESIGN.md §6). The combinator takes the three
stages of a layer and hand-assembles fwd/bwd:

    y = post_attn(params, x, o)   where  (o, lse) = attn_fwd(pre_attn(params, x))

* fwd: run all three, save ``(params, x, o, lse)``.
* bwd: ``jax.vjp``-recompute ``pre_attn`` and ``post_attn`` (cheap GEMMs),
  call ``attn_bwd(qkv, o, lse, do)`` — **no attention forward**.

Memory per layer: layer input ``x`` (same as HF checkpointing) plus
``(o, lse)`` — the paper's Figure-3 budget.

Three policies, selectable per run (``ParallelConfig.remat``):
  * ``remat_aware`` — the combinator (paper's strategy)
  * ``hf``          — ``jax.checkpoint`` at layer boundary (the baseline the
                      paper's Table 5 compares against)
  * ``none``        — no checkpointing (store everything)
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


def _tree_add(a, b):
    def add(x, y):
        # integer leaves (e.g. document segment IDs threaded through the
        # attention stages) carry float0 cotangents — pass them through
        if getattr(x, "dtype", None) == jax.dtypes.float0:
            return x
        return jnp.add(x, y)
    return jax.tree.map(add, a, b)


def remat_aware(pre_attn: Callable, attn_fwd: Callable, attn_bwd: Callable,
                post_attn: Callable) -> Callable:
    """Build ``layer(params, x) -> y`` with the paper's checkpoint placement.

    Args:
      pre_attn:  (params, x) -> qkv_pytree       (projections, norms, rope)
      attn_fwd:  (qkv_pytree) -> (o, lse)        (DISTFLASHATTN forward)
      attn_bwd:  (qkv_pytree, o, lse, do) -> dqkv_pytree  (FA2 backward from
                 saved stats — never reruns the forward)
      post_attn: (params, x, o) -> y             (out-proj, residual, MLP)

    ``x`` and ``y`` may be arbitrary pytrees (e.g. ``(hidden, enc_out)``).
    """

    @jax.custom_vjp
    def layer(params, x):
        qkv = pre_attn(params, x)
        o, _lse = attn_fwd(qkv)
        return post_attn(params, x, o)

    def layer_fwd(params, x):
        qkv = pre_attn(params, x)
        o, lse = attn_fwd(qkv)
        y = post_attn(params, x, o)
        return y, (params, x, o, lse)

    def layer_bwd(res, dy):
        params, x, o, lse = res
        # recompute the cheap stages under vjp; attention fwd is NOT rerun
        with jax.named_scope("recompute"):
            qkv, pre_vjp = jax.vjp(pre_attn, params, x)
            _y, post_vjp = jax.vjp(post_attn, params, x, o)
        dparams2, dx2, do = post_vjp(dy)
        dqkv = attn_bwd(qkv, o, lse, do)
        dparams1, dx1 = pre_vjp(dqkv)
        return _tree_add(dparams1, dparams2), _tree_add(dx1, dx2)

    layer.defvjp(layer_fwd, layer_bwd)
    return layer


def apply_policy(layer: Callable, policy: str) -> Callable:
    """Wrap a ``layer(params, x) -> y`` according to the checkpoint policy.

    For ``remat_aware`` the layer must already be built with the combinator
    above (this function is then the identity). ``hf`` wraps with
    layer-boundary ``jax.checkpoint`` — the paper's baseline, which
    recomputes the attention forward. ``none`` stores all activations.
    """
    if policy == "remat_aware" or policy == "none":
        return layer
    if policy == "hf":
        return jax.checkpoint(layer)
    raise ValueError(f"unknown remat policy: {policy}")
