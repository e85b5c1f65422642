"""Train/serve step factories: jit-compiled with explicit in/out shardings.

``make_train_step`` returns a donated-argument pjit step:
    (params, opt_state, batch) -> (params, opt_state, metrics)
with FSDP parameter/optimizer shardings over (pod, data) and DISTFLASHATTN
sequence parallelism over ``model`` inside the model forward.

Packed-sequence batches flow through unchanged: when the pipeline emits a
``segment_ids`` entry (``ShapeSpec.docs > 1``) it is sharded like the
tokens and the model masks cross-document attention (MaskSpec kind
``document``); the step factories are batch-schema agnostic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.config import ModelConfig, ShapeSpec, TrainConfig
from repro.data.pipeline import input_specs
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import param_shardings


def make_train_step(model, tc: TrainConfig):
    """The step carries a non-finite guard: when the loss or any gradient
    leaf is NaN/Inf (loss-scale overflow, poisoned batch, kernel bug) the
    optimizer update is *skipped* — params and optimizer state pass
    through bit-identical (selected leaf-wise, so it composes with
    argument donation) — and the skip is surfaced in the metrics as
    ``skipped_nonfinite`` for the loop to count and log."""
    def step(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        finite = jnp.isfinite(loss)
        for g in jax.tree_util.tree_leaves(grads):
            finite &= jnp.all(jnp.isfinite(g))
        # the update itself runs unconditionally (one trace, no host
        # sync); ``finite`` selects between new and old leaves
        with jax.named_scope("optimizer"):
            params2, opt2, om = adamw.update(grads, opt_state, params, tc)
            keep = partial(jnp.where, finite)
            params2 = jax.tree.map(keep, params2, params)
            opt2 = adamw.AdamWState(
                step=keep(opt2.step, opt_state.step),
                m=jax.tree.map(keep, opt2.m, opt_state.m),
                v=jax.tree.map(keep, opt2.v, opt_state.v))
            om = {k: keep(v, jnp.zeros_like(v)) for k, v in om.items()}
        return params2, opt2, {"loss": loss, **metrics, **om,
                               "skipped_nonfinite":
                                   (1 - finite).astype(jnp.int32)}
    return step


def jit_train_step(model, tc: TrainConfig, params_sh, batch_sh):
    """jit with explicit shardings + donated params/opt."""
    opt_sh = adamw.AdamWState(
        step=NamedSharding(model.rt.mesh, P()),
        m=params_sh, v=jax.tree.map(lambda s: s, params_sh))
    step = make_train_step(model, tc)
    return jax.jit(step,
                   in_shardings=(params_sh, opt_sh, batch_sh),
                   out_shardings=(params_sh, opt_sh, None),
                   donate_argnums=(0, 1))


def make_decode_step(model):
    def step(params, cache, token, pos):
        logits, cache2 = model.decode(params, cache, {"token": token,
                                                      "pos": pos})
        return logits, cache2
    return step


def make_prefill_step(model):
    def step(params, batch):
        return model.prefill(params, batch)
    return step


def init_sharded(model, tc: TrainConfig, rng):
    """Initialize params + optimizer state directly into their FSDP
    shardings (via jit out_shardings so large models never materialize
    replicated)."""
    rt = model.rt
    shapes = jax.eval_shape(model.init, rng)
    p_sh = param_shardings(shapes, rt.mesh, rt.par)
    params = jax.jit(model.init, out_shardings=p_sh)(rng)
    opt = jax.jit(adamw.init,
                  out_shardings=adamw.AdamWState(
                      step=NamedSharding(rt.mesh, P()), m=p_sh,
                      v=jax.tree.map(lambda s: s, p_sh)))(params)
    return params, opt, p_sh
