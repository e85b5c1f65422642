"""Mixture-of-Experts FFN with expert parallelism over the sequence axis.

DeepSeek-style MoE [arXiv:2405.04434, 2412.19437]: ``n_shared`` always-on
experts + ``n_routed`` routed experts with top-k softmax gating and a
load-balance auxiliary loss. Routed experts are sharded over the ``model``
mesh axis (expert parallelism composes with DISTFLASHATTN's sequence
parallelism on the same axis — tokens are already sequence-local when they
hit the router). Dispatch/return are two ``lax.all_to_all``s with fixed
per-expert capacity (dropped tokens fall back to the shared experts +
residual path). Expert weights are additionally FSDP-sharded on their FFN
dim over the batch axes in GSPMD land; the shard_map ``in_specs`` declare
the gathered layout, so XLA inserts the ZeRO-3 gather-on-use automatically.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.config import ModelConfig
from repro.models.layers import dense_init, rms_norm


def moe_params(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "ln": jnp.ones((d,), dtype),
        "router": dense_init(ks[0], d, m.n_routed, jnp.float32),
        # routed experts, stacked: (E, d, d_e) / (E, d_e, d)
        "wg": jax.vmap(lambda k: dense_init(k, d, m.d_expert, dtype))(
            jax.random.split(ks[1], m.n_routed)),
        "wu": jax.vmap(lambda k: dense_init(k, d, m.d_expert, dtype))(
            jax.random.split(ks[2], m.n_routed)),
        "wd": jax.vmap(lambda k: dense_init(k, m.d_expert, d, dtype))(
            jax.random.split(ks[3], m.n_routed)),
    }
    if m.n_shared:
        ds = m.n_shared * m.d_expert     # fused shared experts (equivalent)
        p["sh_wg"] = dense_init(ks[4], d, ds, dtype)
        p["sh_wu"] = dense_init(ks[5], d, ds, dtype)
        p["sh_wd"] = dense_init(ks[6], ds, d, dtype)
    return p


def _expert_ffn(p, x):
    """x: (E_loc, n, d); weights (E_loc, d, de)/(E_loc, de, d)."""
    h = jax.nn.silu(jnp.einsum("end,edf->enf", x, p["wg"])) * \
        jnp.einsum("end,edf->enf", x, p["wu"])
    return jnp.einsum("enf,efd->end", h, p["wd"])


def _moe_local(cfg: ModelConfig, seq_axis, all_axes, p, x):
    """Per-device MoE body (inside shard_map). x: (b, t, d) local."""
    m = cfg.moe
    b, t, d = x.shape
    n = b * t
    S = lax.axis_size(seq_axis)
    e_loc = m.n_routed // S
    h = rms_norm(x, p["ln"], cfg.norm_eps).reshape(n, d)

    # ---- router (fp32) + top-k
    logits = h.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)                   # (n, E)
    top_p, top_e = lax.top_k(probs, m.top_k)                  # (n, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalize

    # ---- load-balance aux loss (replicated scalar)
    counts = jnp.zeros((m.n_routed,), jnp.float32).at[
        top_e.reshape(-1)].add(1.0)
    f = lax.psum(counts, all_axes)
    f = f / jnp.maximum(jnp.sum(f), 1.0)
    pm = lax.pmean(jnp.mean(probs, axis=0), all_axes)
    aux = m.n_routed * jnp.sum(f * pm) * m.aux_loss_coef

    # ---- capacity-based dispatch
    cap = int(max(4, -(-n * m.top_k * m.capacity_factor // m.n_routed)))
    flat_e = top_e.reshape(-1)                                # (n*K,)
    onehot = jax.nn.one_hot(flat_e, m.n_routed, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)                          # overflow slot
    xk = jnp.repeat(h, m.top_k, axis=0)                       # (n*K, d)
    buf = jnp.zeros((m.n_routed, cap + 1, d), h.dtype)
    buf = buf.at[flat_e, slot].add(xk)[:, :cap]               # (E, cap, d)

    # ---- all_to_all: ship per-expert slices to their owner shard
    buf = lax.all_to_all(buf.reshape(S, e_loc * cap, d), seq_axis,
                         split_axis=0, concat_axis=0, tiled=True)
    buf = buf.reshape(S, e_loc, cap, d).transpose(1, 0, 2, 3) \
             .reshape(e_loc, S * cap, d)

    out = _expert_ffn(p, buf)                                 # local experts

    # ---- return all_to_all + weighted combine
    out = out.reshape(e_loc, S, cap, d).transpose(1, 0, 2, 3) \
             .reshape(S, e_loc * cap, d)
    out = lax.all_to_all(out, seq_axis, split_axis=0, concat_axis=0,
                         tiled=True)
    out = jnp.pad(out.reshape(m.n_routed, cap, d),
                  ((0, 0), (0, 1), (0, 0)))                   # overflow → 0
    got = out[flat_e, slot]                                   # (n*K, d)
    got = got * (keep.astype(got.dtype) * top_p.reshape(-1).astype(
        got.dtype))[:, None]
    y = jnp.sum(got.reshape(n, m.top_k, d), axis=1)

    # ---- shared experts (dense, local)
    if m.n_shared:
        sh = (jax.nn.silu(h @ p["sh_wg"]) * (h @ p["sh_wu"])) @ p["sh_wd"]
        y = y + sh
    return x + y.reshape(b, t, d).astype(x.dtype), aux


def moe_apply(p, x, cfg: ModelConfig, *, mesh, seq_axis="model",
              batch_axes=("data",)):
    """Global-array MoE layer. Returns (y, aux_loss_scalar)."""
    bspec = tuple(batch_axes) if batch_axes else None
    all_axes = tuple(batch_axes) + (seq_axis,) if batch_axes else (seq_axis,)
    x_s = P(bspec, seq_axis, None)
    e_spec = P(seq_axis, None, None)
    pspec = {k: (e_spec if k in ("wg", "wu", "wd")
                 else P(*(None,) * p[k].ndim)) for k in p}
    fn = jax.shard_map(
        partial(_moe_local, cfg, seq_axis, all_axes),
        mesh=mesh, in_specs=(pspec, x_s), out_specs=(x_s, P()),
        check_vma=False)
    return fn(p, x)


# --------------------------------------------------------------------------
# decode path: tokens are replicated over the sequence axis (a single new
# token cannot be sequence-sharded), so instead of an all_to_all each shard
# evaluates its LOCAL experts for all tokens and the partial outputs are
# psum-combined — expert parallelism without dispatch.
# --------------------------------------------------------------------------

def _moe_decode_local(cfg: ModelConfig, seq_axis, p, x):
    m = cfg.moe
    b, t, d = x.shape
    n = b * t
    S = lax.axis_size(seq_axis)
    e_loc = m.n_routed // S
    sh = lax.axis_index(seq_axis)
    h = rms_norm(x, p["ln"], cfg.norm_eps).reshape(n, d)
    logits = h.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # per-token weight for every expert (n, E), zero if not in top-k
    w = jnp.zeros((n, m.n_routed), jnp.float32)
    w = w.at[jnp.arange(n)[:, None], top_e].set(top_p)
    w_loc = lax.dynamic_slice_in_dim(w, sh * e_loc, e_loc, axis=1)
    xe = jnp.broadcast_to(h[None], (e_loc, n, d))
    oe = _expert_ffn(p, xe)                               # (e_loc, n, d)
    y = jnp.einsum("ne,end->nd", w_loc, oe.astype(jnp.float32))
    y = lax.psum(y, seq_axis)
    if m.n_shared:
        sh_out = (jax.nn.silu(h @ p["sh_wg"]) * (h @ p["sh_wu"])) @ p["sh_wd"]
        y = y + sh_out.astype(jnp.float32)
    return x + y.reshape(b, t, d).astype(x.dtype)


def moe_decode_apply(p, x, cfg: ModelConfig, *, mesh, seq_axis="model",
                     batch_axes=("data",)):
    bspec = tuple(batch_axes) if batch_axes else None
    x_s = P(bspec, None, None)
    e_spec = P(seq_axis, None, None)
    pspec = {k: (e_spec if k in ("wg", "wu", "wd")
                 else P(*(None,) * p[k].ndim)) for k in p}
    fn = jax.shard_map(partial(_moe_decode_local, cfg, seq_axis),
                       mesh=mesh, in_specs=(pspec, x_s), out_specs=x_s,
                       check_vma=False)
    return fn(p, x)
