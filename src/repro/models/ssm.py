"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) with
sequence-parallel cross-device state relay.

The paper's attention scheduling is inapplicable to an attention-free SSM
(DESIGN.md §5); what transfers is the *sequence-parallel decomposition*:
tokens are sharded over the ``model`` axis, each shard runs the chunked SSD
algorithm locally, and the (tiny, O(d_state·d_head)) inter-shard recurrent
state is combined with a log₂(P)-step Hillis–Steele parallel prefix over
``ppermute`` — the recurrent-scan analogue of the paper's ring.

Chunked SSD (exact, matches the sequential recurrence):
  y_i  = Σ_{j≤i} (C_i·B_j) · exp(cum_i − cum_j) · dt_j · x_j   (intra-chunk)
       + C_i · exp(cum_i) · S_init                              (inter-chunk)
  S'   = exp(cum_L) · S_init + Σ_j exp(cum_L − cum_j) dt_j B_j ⊗ x_j
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.config import ModelConfig
from repro.models.layers import dense_init, rms_norm


def ssm_params(key, cfg: ModelConfig, dtype):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_ch = di + 2 * s.d_state
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.ones((d,), dtype),
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * s.d_state + nh, dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_ch, s.d_conv)) * 0.2
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((nh,), jnp.float32),          # A = −exp(A_log) = −1
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "gln": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[2], di, d, dtype),
    }


def _causal_conv(xbc, w, b, tail):
    """Depthwise causal conv. xbc: (b,t,ch); w: (ch,k); tail: (b,k-1,ch)
    carry from the previous sequence shard (zeros on shard 0)."""
    k = w.shape[1]
    xp = jnp.concatenate([tail, xbc], axis=1)            # (b, t+k-1, ch)
    # w[:, k-1] multiplies the current token, w[:, 0] the oldest
    out = jnp.zeros_like(xbc)
    for i in range(k):
        out = out + xp[:, i:i + xbc.shape[1]] * w[:, i][None, None, :]
    return out + b[None, None, :]


def _ssd_chunked(x, B, C, dt, adt, s_init, chunk):
    """Exact chunked SSD. x: (b,t,nh,hd); B,C: (b,t,N); dt,adt: (b,t,nh);
    s_init: (b,nh,N,hd) carry-in. Returns (y (b,t,nh,hd), s_out)."""
    b, t, nh, hd = x.shape
    N = B.shape[-1]
    L = min(chunk, t)
    assert t % L == 0, (t, L)
    c = t // L
    f32 = jnp.float32
    xc = x.reshape(b, c, L, nh, hd).astype(f32)
    Bc = B.reshape(b, c, L, N).astype(f32)
    Cc = C.reshape(b, c, L, N).astype(f32)
    dtc = dt.reshape(b, c, L, nh).astype(f32)
    adtc = adt.reshape(b, c, L, nh).astype(f32)
    cum = jnp.cumsum(adtc, axis=2)                        # inclusive (b,c,L,nh)
    # intra-chunk (dual / attention-like form)
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)        # (b,c,L,L)
    dd = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (b,c,i,j,nh)
    ii = jnp.arange(L)
    causal = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    w = jnp.where(causal, jnp.exp(dd), 0.0) * dtc[:, :, None, :, :]
    y = jnp.einsum("bcij,bcijh,bcjhp->bcihp", scores, w, xc)
    # chunk summaries
    decay_out = jnp.exp(cum[:, :, -1, :])                 # (b,c,nh)
    wS = jnp.exp(cum[:, :, -1:, :] - cum) * dtc           # (b,c,L,nh)
    S_chunk = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", Bc, wS, xc)
    # inter-chunk: log-depth associative prefix over chunks (TPU-friendly,
    # and fully visible to cost_analysis unlike a while-loop scan)
    def comb(a, b):
        da, sa = a
        db, sb = b
        return da * db, sa * db[:, :, :, None, None] + sb
    d_inc, s_inc = lax.associative_scan(
        comb, (decay_out, S_chunk), axis=1)               # inclusive (b,c,..)
    s0 = s_init.astype(f32)[:, None]                      # (b,1,nh,N,hd)
    # exclusive prefix with carry-in: E_0 = s0; E_c = I_{c−1} + s0·D_{c−1}
    s_shift = jnp.concatenate([jnp.zeros_like(s_inc[:, :1]),
                               s_inc[:, :-1]], axis=1)
    d_shift = jnp.concatenate([jnp.ones_like(d_inc[:, :1]),
                               d_inc[:, :-1]], axis=1)
    s_prefix = s_shift + s0 * d_shift[:, :, :, None, None]
    y = y + jnp.einsum("bcin,bcih,bchnp->bcihp", Cc, jnp.exp(cum), s_prefix)
    # final state: full inclusive combine with the carry-in
    s_last = s_inc[:, -1] + s0[:, 0] * d_inc[:, -1, :, None, None]
    return y.reshape(b, t, nh, hd), s_last


def _device_prefix(axis, decay, state):
    """Hillis–Steele exclusive prefix of (decay, state) over the sequence
    axis. decay: (b,nh); state: (b,nh,N,hd). Monoid: apply segment2 after
    segment1 → (d1·d2, s1·d2 + s2)."""
    P_ = lax.axis_size(axis)
    p = lax.axis_index(axis)
    d_acc, s_acc = decay, state                           # inclusive running
    shift = 1
    while shift < P_:
        perm = [(i, (i + shift) % P_) for i in range(P_)]
        d_in = lax.ppermute(d_acc, axis, perm)
        s_in = lax.ppermute(s_acc, axis, perm)
        valid = (p >= shift).astype(decay.dtype)
        # combine: incoming (earlier) segment before ours
        s_acc = s_in * valid[..., None, None] * d_acc[:, :, None, None] + s_acc
        d_acc = jnp.where(p >= shift, d_in * d_acc, d_acc)
        shift *= 2
    # exclusive = inclusive of device p−1 (identity on device 0)
    perm1 = [(i, (i + 1) % P_) for i in range(P_)]
    d_ex = lax.ppermute(d_acc, axis, perm1)
    s_ex = lax.ppermute(s_acc, axis, perm1)
    first = (p == 0)
    s_ex = jnp.where(first, jnp.zeros_like(s_ex), s_ex)
    return s_ex


def _ssm_local(cfg: ModelConfig, seq_axis, p, x):
    """Mamba2 mixer, per-shard (inside shard_map). x: (b,t,d) local."""
    s = cfg.ssm
    b, t, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    N = s.d_state
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    zxbcdt = h @ p["in_proj"]
    z, xin, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    # causal depthwise conv with cross-shard halo
    xbc = jnp.concatenate([xin, B, C], axis=-1)
    k = s.d_conv
    P_ = lax.axis_size(seq_axis)
    if P_ > 1:
        perm = [(i, (i + 1) % P_) for i in range(P_)]
        tail = lax.ppermute(xbc[:, -(k - 1):], seq_axis, perm)
        tail = jnp.where(lax.axis_index(seq_axis) == 0,
                         jnp.zeros_like(tail), tail)
    else:
        tail = jnp.zeros((b, k - 1, xbc.shape[-1]), xbc.dtype)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"], tail))
    xin, B, C = jnp.split(xbc, [di, di + N], axis=-1)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    adt = a * dtf                                          # (b,t,nh)
    xh = xin.reshape(b, t, nh, -1)
    # cross-device recurrent prefix: local totals first
    f32 = jnp.float32
    decay_tot = jnp.exp(jnp.sum(adt, axis=1))              # (b,nh)
    zero_state = jnp.zeros((b, nh, N, di // nh), f32)
    _, s_total = _ssd_chunked(xh, B, C, dtf, adt, zero_state, s.chunk)
    if P_ > 1:
        s_init = _device_prefix(seq_axis, decay_tot, s_total)
    else:
        s_init = zero_state
    y, _ = _ssd_chunked(xh, B, C, dtf, adt, s_init, s.chunk)
    y = y + p["D"][None, None, :, None] * xh.astype(f32)
    y = y.reshape(b, t, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gln"], cfg.norm_eps)
    return x + (y @ p["out_proj"]).astype(x.dtype)


def ssm_apply(p, x, cfg: ModelConfig, *, mesh, seq_axis="model",
              batch_axes=("data",)):
    """Global-array Mamba2 layer (residual included)."""
    bspec = tuple(batch_axes) if batch_axes else None
    x_s = P(bspec, seq_axis, None)
    pspec = {k: P(*(None,) * p[k].ndim) for k in p}
    fn = jax.shard_map(partial(_ssm_local, cfg, seq_axis), mesh=mesh,
                       in_specs=(pspec, x_s), out_specs=x_s, check_vma=False)
    return fn(p, x)


# ----------------------------------------------------------------- decode

def ssm_decode_step(p, x, state, conv_tail, cfg: ModelConfig):
    """Single-token recurrent update. x: (b,1,d); state: (b,nh,N,hd);
    conv_tail: (b,k−1,conv_ch). Returns (y, state', conv_tail')."""
    s = cfg.ssm
    b, _, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    N = s.d_state
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    zxbcdt = h @ p["in_proj"]
    z, xin, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    xbc = jnp.concatenate([xin, B, C], axis=-1)            # (b,1,ch)
    window = jnp.concatenate([conv_tail, xbc], axis=1)     # (b,k,ch)
    conv = jnp.sum(window * p["conv_w"].T[None], axis=1) + p["conv_b"]
    xbc1 = jax.nn.silu(conv)                               # (b,ch)
    xin1, B1, C1 = jnp.split(xbc1, [di, di + N], axis=-1)
    dtf = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    dec = jnp.exp(a * dtf)                                 # (b,nh)
    xh = xin1.reshape(b, nh, -1).astype(jnp.float32)
    state = state * dec[:, :, None, None] + jnp.einsum(
        "bn,bh,bhp->bhnp", B1.astype(jnp.float32), dtf, xh)
    y = jnp.einsum("bn,bhnp->bhp", C1.astype(jnp.float32), state)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gln"], cfg.norm_eps)
    return x + (y @ p["out_proj"]).astype(x.dtype), state, window[:, 1:]


# ------------------------------------------------------------ test oracle

def ssm_sequential_ref(p, x, cfg: ModelConfig):
    """Token-by-token recurrence oracle (single device, for tests)."""
    s = cfg.ssm
    b, t, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    N = s.d_state
    state = jnp.zeros((b, nh, N, di // nh), jnp.float32)
    tail = jnp.zeros((b, s.d_conv - 1, di + 2 * N), x.dtype)
    outs = []
    for i in range(t):
        y, state, tail = ssm_decode_step(p, x[:, i:i + 1], state, tail, cfg)
        outs.append(y)
    return jnp.concatenate(outs, axis=1)
