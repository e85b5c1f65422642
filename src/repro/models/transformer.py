"""Model assembly: decoder LMs (dense / GQA / MLA / MoE / SSM / hybrid /
VLM), Whisper-style encoder–decoder, and DeepSeek MTP — all built on
DISTFLASHATTN sequence parallelism with the rematerialization-aware
checkpointing combinator.

Every architecture exposes the same surface:
  * ``init(rng) -> params``
  * ``loss(params, batch) -> (scalar, metrics)``       (training forward)
  * ``prefill(params, batch) -> (last_logits, cache)`` (inference prefill)
  * ``decode(params, cache, batch) -> (logits, cache)``(one-token decode)

Layers are stacked and scanned (``lax.scan``) so the HLO stays compact for
the 61-layer/671B dry-runs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import mask as mk
from repro.core.config import ModelConfig, ParallelConfig
from repro.core.dist_attention import (DistAttnSpec, Mesh2DSpec,
                                       dist_attn_bwd, dist_attn_fwd,
                                       dist_decode_attn, dist_flash_attn)
from repro.core.mask import MaskSpec
from repro.core.remat import remat_aware
from repro.core.attention import chunk_attn, paged_decode_attn
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.parallel.sharding import act_spec, constrain, mesh_axis_size



# Scan-unroll switch: the dry-run's cost-measurement compiles flip this so
# XLA's cost_analysis sees every layer (a while-loop body is only counted
# once). Production lowering keeps rolled scans (compact HLO).
_SCAN_UNROLL = [False]


def set_scan_unroll(v: bool) -> None:
    _SCAN_UNROLL[0] = bool(v)


def xscan(f, init, xs):
    return lax.scan(f, init, xs, unroll=True if _SCAN_UNROLL[0] else 1)


@dataclass(frozen=True)
class Runtime:
    mesh: Mesh
    par: ParallelConfig
    impl: Optional[str] = None          # attention backend override
    latent_ring: bool = False           # MLA: ship the latent, not K/V

    @property
    def seq_size(self) -> int:
        """Total sequence-parallel workers P — the (seq × head) product
        on a factored 2D mesh."""
        return mesh_axis_size(self.mesh, self.par.seq_axis) \
            * self.head_size

    @property
    def head_size(self) -> int:
        """Size u of the head sub-axis (1 without a 2D mesh)."""
        if self.par.head_axis is None:
            return 1
        return mesh_axis_size(self.mesh, self.par.head_axis)


def _zigzag_ok(cfg: ModelConfig) -> bool:
    """Zigzag relayout is valid only for purely positionwise decoders:
    no cross-position ops outside attention (SSM scan/conv, MTP roll) and
    no windowed masks (window masks assume contiguous shard positions)."""
    return (cfg.arch_type in ("dense", "vlm", "moe")
            and not cfg.mtp_depth
            and cfg.attn is not None and not cfg.attn.window)


def _attn_spec(cfg: ModelConfig, rt: Runtime, *, causal=True, window=None,
               scale=None, document=False) -> DistAttnSpec:
    w = cfg.attn.window if window is None else window
    sched = rt.par.schedule
    if sched == "zigzag" and not _zigzag_ok(cfg):
        sched = "balanced"                      # graceful fallback
    mask = MaskSpec(causal=causal, window=int(w or 0), document=document)
    mesh2d = None
    if rt.head_size > 1:
        # factored 2D mesh: ring-family plans on the seq sub-axis after
        # the head scatter; baselines don't exist on the axis pair
        mesh2d = Mesh2DSpec(
            r=rt.seq_size // rt.head_size, u=rt.head_size,
            seq_axis=rt.par.seq_axis, head_axis=rt.par.head_axis)
        if sched not in ("auto", "ring", "balanced", "zigzag"):
            sched = "balanced" if (causal and mesh2d.r > 1) else "ring"
        if mesh2d.r > 1 and not causal and sched != "ring":
            sched = "ring"                       # bidirectional encoders
    elif sched != "auto":                        # auto defers to the plans
        if not causal and sched not in ("ulysses", "rsa"):
            # bidirectional encoders; a non-causal *window* has future-
            # direction bands only absolute-position schedules can see
            sched = "ulysses" if w else "ring"
        elif causal and w and sched not in ("balanced", "ring", "ulysses"):
            sched = "balanced"                   # windowed plans truncate
    return DistAttnSpec(
        axis=rt.par.seq_axis, axis_size=rt.seq_size, schedule=sched,
        mask=mask, scale=scale, impl=rt.impl, mesh2d=mesh2d)


def _decode_mask(window) -> MaskSpec:
    """Decode-time mask: the new token is last, so the only kinds are the
    whole cache (causal) or a sliding window."""
    return mk.sliding_window(int(window)) if window else mk.causal()


def _norm_pos(pos, B):
    """Per-request decode positions: (B,) int32.  A scalar (the pre-paged
    shared position — it silently mis-masks mixed-length batches once
    requests are admitted at different times) broadcasts with a one-shot
    DeprecationWarning."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        mk.warn_legacy_once('decode(batch={"pos": <scalar>})',
                            'a (B,) per-request position vector')
        pos = jnp.broadcast_to(pos, (B,))
    return pos.astype(jnp.int32)


def _decode_rope(pos, dim, theta):
    """Per-request rope tables for the decode token: (B, 1, dim/2)."""
    cos, sin = L.rope_tables(pos, dim, theta)
    return cos[:, None], sin[:, None]


def _is_paged(cache) -> bool:
    return isinstance(cache, dict) and "block_table" in cache


# ==========================================================================
# Layer builders (stage functions feed the remat-aware combinator)
# ==========================================================================

def _dense_stages(cfg, rt, is_mla, document=False):
    """Stage functions take x = (h, cos, sin, seg): custom_vjp functions
    must not close over traced values, so the rope tables — and the packed-
    sequence segment IDs (``seg``; None when the batch is unpacked) —
    travel in the input pytree."""
    spec = _attn_spec(cfg, rt, scale=L.mla_scale(cfg) if is_mla else None,
                      document=document)
    batch_axes = rt.par.batch_axes

    def pre(p, x):
        h, cos, sin, seg = x
        if is_mla:
            return L.mla_qkv(p["attn"], h, cfg, cos, sin) + (seg,)
        return L.attn_qkv(p["attn"], h, cfg, cos, sin) + (seg,)

    # "attn" names the attention's device work in the trace, whether it
    # runs locally or through a schedule's executor
    @jax.named_scope("attn")
    def attn_fwd(qkv):
        q, k, v, seg = qkv
        return dist_attn_fwd(q, k, v, mesh=rt.mesh, spec=spec,
                             batch_axes=batch_axes, segments=seg)

    @jax.named_scope("attn")
    def attn_bwd(qkv, o, lse, do):
        q, k, v, seg = qkv
        dq, dk, dv = dist_attn_bwd(q, k, v, o, lse, do, mesh=rt.mesh,
                                   spec=spec, batch_axes=batch_axes,
                                   segments=seg)
        dseg = None if seg is None else np.zeros(seg.shape,
                                                 jax.dtypes.float0)
        return dq, dk, dv, dseg

    @jax.named_scope("attn")
    def attn_diff(qkv):
        q, k, v, seg = qkv
        return dist_flash_attn(q, k, v, rt.mesh, spec, batch_axes, seg)

    return pre, attn_fwd, attn_bwd, attn_diff


def build_dense_layer(cfg, rt, *, is_mla=False, use_moe=False,
                      d_ff=None, document=False):
    """layer(params, (h, cos, sin, seg)) -> (h', aux)."""
    pre, attn_fwd, attn_bwd, attn_diff = _dense_stages(cfg, rt, is_mla,
                                                       document)

    def post(p, x, o):
        h = x[0]
        h2 = L.attn_out(p["attn"], h, o, cfg)
        h2 = constrain(h2, rt.mesh, act_spec(rt.par))
        if use_moe:
            h3, aux = M.moe_apply(p["moe"], h2, cfg, mesh=rt.mesh,
                                  seq_axis=rt.par.seq_axis,
                                  batch_axes=rt.par.batch_axes)
        else:
            h3, aux = L.mlp_apply(p["mlp"], h2, cfg.norm_eps), jnp.float32(0)
        h3 = constrain(h3, rt.mesh, act_spec(rt.par))
        return (h3, aux)

    if rt.par.remat == "remat_aware":
        return remat_aware(pre, attn_fwd, attn_bwd, post)

    def plain(p, x):
        o, _ = attn_diff(pre(p, x))
        return post(p, x, o)

    if rt.par.remat == "hf":
        return jax.checkpoint(plain)
    return plain


def dense_layer_params(key, cfg, dtype, *, is_mla=False, use_moe=False,
                       d_ff=None):
    k1, k2 = jax.random.split(key)
    p = {"attn": (L.mla_params(k1, cfg, dtype) if is_mla
                  else L.attn_params(k1, cfg, dtype))}
    if use_moe:
        p["moe"] = M.moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = L.mlp_params(k2, cfg.d_model, d_ff or cfg.d_ff, dtype)
    return p


def _stack(key, n, make):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[make(k) for k in jax.random.split(key, max(n, 1))])


def _scan_layers(layer_fn, h, stacked, rt, cos=None, sin=None, seg=None):
    def body(carry, lp):
        h, aux = carry
        h2, aux2 = layer_fn(lp, (h, cos, sin, seg))
        return (h2, aux + aux2), None
    (h, aux), _ = xscan(body, (h, jnp.float32(0)), stacked)
    return h, aux


# ==========================================================================
# DecoderLM — dense / moe / ssm / hybrid / vlm
# ==========================================================================

class DecoderLM:
    def __init__(self, cfg: ModelConfig, rt: Runtime):
        self.cfg = cfg
        self.rt = rt
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------- init
    def init(self, rng):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(rng, 8)
        p = {"embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model, dt),
             "ln_f": jnp.ones((cfg.d_model,), dt)}
        if not cfg.tie_embeddings:
            p["head"] = L.dense_init(ks[7], cfg.d_model, cfg.vocab, dt)
        at = cfg.arch_type
        if at in ("dense", "vlm"):
            p["layers"] = _stack(ks[1], cfg.n_layers, lambda k:
                                 dense_layer_params(k, cfg, dt))
        elif at == "moe":
            is_mla = cfg.attn.is_mla
            nd = cfg.moe.n_dense_layers
            p["dense_layers"] = _stack(ks[1], nd, lambda k:
                                       dense_layer_params(
                                           k, cfg, dt, is_mla=is_mla,
                                           d_ff=cfg.moe.d_dense_ff))
            p["moe_layers"] = _stack(ks[2], cfg.n_layers - nd, lambda k:
                                     dense_layer_params(k, cfg, dt,
                                                        is_mla=is_mla,
                                                        use_moe=True))
            if cfg.mtp_depth:
                p["mtp"] = {
                    "proj": L.dense_init(ks[3], 2 * cfg.d_model,
                                         cfg.d_model, dt),
                    "ln_h": jnp.ones((cfg.d_model,), dt),
                    "ln_e": jnp.ones((cfg.d_model,), dt),
                    "layer": dense_layer_params(ks[4], cfg, dt,
                                                is_mla=is_mla, use_moe=True),
                    "ln_f": jnp.ones((cfg.d_model,), dt),
                }
        elif at == "ssm":
            p["layers"] = _stack(ks[1], cfg.n_layers,
                                 lambda k: {"ssm": S.ssm_params(k, cfg, dt)})
        elif at == "hybrid":
            p["layers"] = _stack(ks[1], cfg.n_layers,
                                 lambda k: {"ssm": S.ssm_params(k, cfg, dt)})
            p["shared"] = self._shared_block_params(ks[2])
        else:
            raise ValueError(at)
        return p

    def _shared_cfg(self):
        """Zamba2 shared attention block operates on concat(h, emb) = 2d
        [arXiv:2411.15242]. The config's attn.head_dim must already satisfy
        n_heads · head_dim == 2·d_model (see configs/zamba2_2_7b.py)."""
        cfg = self.cfg
        assert cfg.attn.n_heads * cfg.attn.head_dim == 2 * cfg.d_model
        return cfg.replace(d_model=2 * cfg.d_model, arch_type="dense")

    def _shared_block_params(self, key):
        scfg = self._shared_cfg()
        k1, k2 = jax.random.split(key)
        p = dense_layer_params(k1, scfg, self.dtype)
        p["down"] = L.dense_init(k2, scfg.d_model, self.cfg.d_model,
                                 self.dtype)
        return p

    # ------------------------------------------------------- embeddings
    def _embed(self, p, batch):
        cfg, rt = self.cfg, self.rt
        toks = batch["tokens"]
        h = p["embed"][toks].astype(self.dtype)
        if cfg.arch_type == "vlm":
            img = batch["image_embeds"].astype(self.dtype)
            h = jnp.concatenate([img, h], axis=1)
        h = constrain(h, rt.mesh, act_spec(rt.par))
        return h

    def _head(self, p, h):
        cfg = self.cfg
        h = L.rms_norm(h, p["ln_f"], cfg.norm_eps)
        w = p["embed"].T if cfg.tie_embeddings else p["head"]
        return h @ w.astype(h.dtype)

    # ------------------------------------------------------------ train
    def _backbone(self, p, h, cos, sin, seg=None):
        """Shared trunk: returns (h, aux). ``seg`` = packed-sequence
        document IDs (B, T) or None."""
        cfg, rt = self.cfg, self.rt
        at = cfg.arch_type
        doc = seg is not None
        if at in ("dense", "vlm"):
            layer = build_dense_layer(cfg, rt, document=doc)
            return _scan_layers(layer, h, p["layers"], rt, cos, sin, seg)
        if at == "moe":
            is_mla = cfg.attn.is_mla
            dl = build_dense_layer(cfg, rt, is_mla=is_mla,
                                   d_ff=cfg.moe.d_dense_ff, document=doc)
            ml = build_dense_layer(cfg, rt, is_mla=is_mla, use_moe=True,
                                   document=doc)
            h, a1 = _scan_layers(dl, h, p["dense_layers"], rt, cos, sin, seg)
            h, a2 = _scan_layers(ml, h, p["moe_layers"], rt, cos, sin, seg)
            return h, a1 + a2
        if at == "ssm":
            layer = self._ssm_layer()
            def body(carry, lp):
                return layer(lp, carry), None
            h, _ = xscan(body, h, p["layers"])
            return h, jnp.float32(0)
        if at == "hybrid":
            return self._hybrid_backbone(p, h, cos, sin)
        raise ValueError(at)

    def _ssm_layer(self):
        cfg, rt = self.cfg, self.rt
        def layer(lp, h):
            y = S.ssm_apply(lp["ssm"], h, cfg, mesh=rt.mesh,
                            seq_axis=rt.par.seq_axis,
                            batch_axes=rt.par.batch_axes)
            return constrain(y, rt.mesh, act_spec(rt.par))
        if rt.par.remat in ("hf", "remat_aware"):
            # remat-aware boundary shift is attention-specific (§3.3); SSD
            # layers use layer-boundary checkpointing (DESIGN.md §5)
            return jax.checkpoint(layer)
        return layer

    def _shared_block(self, p, h, emb0, cos, sin):
        """Zamba2 shared attention+MLP on concat(h, emb)."""
        cfg, rt = self.cfg, self.rt
        scfg = self._shared_cfg()
        layer = build_dense_layer(scfg, rt)
        x2 = jnp.concatenate([h, emb0], axis=-1)
        y2, _ = layer(p, (x2, cos, sin, None))
        return h + (y2 @ p["down"]).astype(h.dtype)

    def _hybrid_backbone(self, p, h, cos, sin):
        cfg, rt = self.cfg, self.rt
        period = cfg.hybrid_period
        G = cfg.n_layers // period
        stacked = jax.tree.map(
            lambda a: a.reshape(G, period, *a.shape[1:]), p["layers"])
        ssm_layer = self._ssm_layer()
        emb0 = h

        def group(carry, gp):
            hh = carry
            def inner(c, lp):
                return ssm_layer(lp, c), None
            hh, _ = xscan(inner, hh, gp)
            hh = self._shared_block(p["shared"], hh, emb0, cos, sin)
            return hh, None
        h, _ = xscan(group, h, stacked)
        return h, jnp.float32(0)

    def loss(self, p, batch):
        cfg, rt = self.cfg, self.rt
        h = self._embed(p, batch)
        T = h.shape[1]
        cos, sin = (None, None)
        if cfg.uses_attention:
            pos = jnp.arange(T)
            dim = (cfg.attn.qk_rope_head_dim if cfg.attn.is_mla
                   else cfg.attn.head_dim)
            cos, sin = L.rope_tables(pos, dim, cfg.attn.rope_theta)
        labels = batch["labels"]
        seg = batch.get("segment_ids")      # packed-sequence document IDs
        if seg is not None:
            if cfg.arch_type not in ("dense", "moe"):
                raise ValueError(
                    f"packed (segment_ids) training is supported for "
                    f"dense/moe decoders, not {cfg.arch_type!r}")
            if cfg.mtp_depth:
                raise ValueError("packed training does not compose with "
                                 "MTP (the t+2 roll crosses documents)")
        if cfg.arch_type == "vlm":      # image positions carry no loss
            pad = jnp.full(batch["image_embeds"].shape[:2], -100,
                           labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        if rt.par.schedule == "zigzag" and _zigzag_ok(cfg) \
                and rt.seq_size > 1:
            # zigzag relayout (beyond-paper, see core/dist_attention.py):
            # one global gather after the embedding; rope tables, labels
            # and segment IDs follow. Loss is positionwise so no inverse
            # permutation needed.
            from repro.core.dist_attention import zigzag_perm
            perm = zigzag_perm(T, rt.seq_size)
            h = h[:, perm]
            labels = labels[:, perm]
            cos, sin = cos[perm], sin[perm]
            if seg is not None:
                seg = seg[:, perm]
            h = constrain(h, rt.mesh, act_spec(rt.par))
        h, aux = self._backbone(p, h, cos, sin, seg)
        with jax.named_scope("head"):
            logits = self._head(p, h)
            ce = L.cross_entropy(logits, labels)
        total = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp_depth and "mtp" in p:
            mtp_ce = self._mtp_loss(p, h, batch, cos, sin)
            total = total + 0.3 * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        return total, metrics

    def _mtp_loss(self, p, h, batch, cos, sin):
        """DeepSeek-V3 multi-token prediction [arXiv:2412.19437]: one extra
        transformer block predicts token t+2 from (h_t, emb_{t+1})."""
        cfg, rt = self.cfg, self.rt
        mp = p["mtp"]
        toks = batch["tokens"]
        emb = p["embed"][toks].astype(self.dtype)
        emb_next = jnp.roll(emb, -1, axis=1)         # emb_{t+1} (last invalid)
        hcat = jnp.concatenate([
            L.rms_norm(h, mp["ln_h"], cfg.norm_eps),
            L.rms_norm(emb_next, mp["ln_e"], cfg.norm_eps)], axis=-1)
        h2 = (hcat @ mp["proj"]).astype(self.dtype)
        h2 = constrain(h2, rt.mesh, act_spec(rt.par))
        layer = build_dense_layer(cfg, rt, is_mla=cfg.attn.is_mla,
                                  use_moe=True)
        h2, _aux = layer(mp["layer"], (h2, cos, sin, None))
        h2 = L.rms_norm(h2, mp["ln_f"], cfg.norm_eps)
        logits = h2 @ p["embed"].T.astype(h2.dtype)
        labels = jnp.roll(batch["labels"], -1, axis=1)
        labels = labels.at[:, -1].set(-100)          # t+2 shift boundary
        return L.cross_entropy(logits, labels)

    # ======================================================== inference
    def _infer_layer_dense(self, p, h, cos, sin, *, is_mla, use_moe,
                           collect_cache):
        """Plain (no-vjp) layer that also returns the KV cache entry."""
        cfg, rt = self.cfg, self.rt
        spec = _attn_spec(cfg, rt, scale=L.mla_scale(cfg) if is_mla else None)
        if is_mla:
            q, k, v, latent = L.mla_qkv(p["attn"], h, cfg, cos, sin,
                                        return_latent=True)
        else:
            q, k, v = L.attn_qkv(p["attn"], h, cfg, cos, sin)
        if is_mla and rt.latent_ring and spec.schedule == "zigzag":
            from repro.core.dist_attention import dist_attn_fwd_latent
            o, _ = dist_attn_fwd_latent(
                q, k, v, latent, p["attn"]["wkv_b"],
                partial(L.mla_expand, cfg=cfg), mesh=rt.mesh, spec=spec,
                batch_axes=rt.par.batch_axes)
        else:
            o, _ = dist_attn_fwd(q, k, v, mesh=rt.mesh, spec=spec,
                                 batch_axes=rt.par.batch_axes)
        h2 = L.attn_out(p["attn"], h, o, cfg)
        if use_moe:
            h3, _ = M.moe_apply(p["moe"], h2, cfg, mesh=rt.mesh,
                                seq_axis=rt.par.seq_axis,
                                batch_axes=rt.par.batch_axes)
        else:
            h3 = L.mlp_apply(p["mlp"], h2, cfg.norm_eps)
        h3 = constrain(h3, rt.mesh, act_spec(rt.par))
        cache = None
        if collect_cache:
            cache = (latent,) if is_mla else (k, v)
        return h3, cache

    def prefill(self, p, batch):
        """Full-context forward; returns (last-token logits, cache)."""
        cfg, rt = self.cfg, self.rt
        h = self._embed(p, batch)
        T = h.shape[1]
        cos = sin = None
        if cfg.uses_attention:
            dim = (cfg.attn.qk_rope_head_dim if cfg.attn.is_mla
                   else cfg.attn.head_dim)
            cos, sin = L.rope_tables(jnp.arange(T), dim, cfg.attn.rope_theta)
        last = T - 1
        if rt.par.schedule == "zigzag" and _zigzag_ok(cfg) \
                and rt.seq_size > 1:
            from repro.core.dist_attention import zigzag_perm
            perm = zigzag_perm(T, rt.seq_size)
            h = h[:, perm]
            cos, sin = cos[perm], sin[perm]
            last = int(np.nonzero(perm == T - 1)[0][0])
            h = constrain(h, rt.mesh, act_spec(rt.par))
        at = cfg.arch_type
        caches = {}
        if at in ("dense", "vlm"):
            def body(h, lp):
                h2, c = self._infer_layer_dense(h=h, p=lp, cos=cos, sin=sin,
                                                is_mla=False, use_moe=False,
                                                collect_cache=True)
                return h2, c
            h, (ck, cv) = xscan(body, h, p["layers"])
            caches = {"k": ck, "v": cv}
        elif at == "moe":
            is_mla = cfg.attn.is_mla
            def bodyd(h, lp):
                return self._infer_layer_dense(
                    h=h, p=lp, cos=cos, sin=sin, is_mla=is_mla,
                    use_moe=False, collect_cache=True)
            def bodym(h, lp):
                return self._infer_layer_dense(
                    h=h, p=lp, cos=cos, sin=sin, is_mla=is_mla,
                    use_moe=True, collect_cache=True)
            h, c1 = xscan(bodyd, h, p["dense_layers"])
            h, c2 = xscan(bodym, h, p["moe_layers"])
            if is_mla:
                caches = {"ckv": jnp.concatenate([c1[0], c2[0]])}
            else:
                caches = {"k": jnp.concatenate([c1[0], c2[0]]),
                          "v": jnp.concatenate([c1[1], c2[1]])}
        elif at in ("ssm", "hybrid"):
            # SSM prefill produces O(1) state, not a KV cache; reuse the
            # training backbone then rebuild decode state token-free.
            h, _ = self._backbone(p, h, cos, sin)
        logits = self._head(p, h[:, last:last + 1])
        return logits, caches

    def prefill_chunk(self, p, cache, batch):
        """Chunked paged prefill (Sarathi-style): forward a B=1 chunk
        ``batch = {"tokens": (1, C), "start": scalar, "n_valid": scalar}``
        occupying context positions ``[start, start + n_valid)`` through
        every layer, scattering the chunk's K/V (or MLA latent) into the
        slot's pool blocks (write-then-attend) and attending over the
        already-cached context gathered through the block table —
        ``chunk_attn`` with a *dynamic* ``q_offset = start`` (the MaskSpec
        offset machinery from the packed-sequence work).  Rows past
        ``n_valid`` (shape-bucket padding) are written to the reserved
        null block and their outputs are causal-masked garbage that is
        never read.  No logits are returned: the pending-token design
        keeps the last context token for decode.  ``cache`` is a paged
        view {k_pool, v_pool | ckv_pool, block_table (1, nkv)} whose
        updated pools are returned."""
        cfg, rt = self.cfg, self.rt
        at = cfg.arch_type
        if at not in ("dense", "moe"):
            raise ValueError(f"chunked paged prefill serves dense/moe "
                             f"decoders (got {at!r})")
        a = cfg.attn
        is_mla = a.is_mla
        tok = batch["tokens"]
        start = jnp.asarray(batch["start"], jnp.int32)
        end = start + jnp.asarray(batch["n_valid"], jnp.int32)
        bt = cache["block_table"]
        h = p["embed"][tok].astype(self.dtype)             # (1, C, d)
        C = tok.shape[1]
        dim = a.qk_rope_head_dim if is_mla else a.head_dim
        cos, sin = L.rope_tables(start + jnp.arange(C), dim, a.rope_theta)
        spec = _decode_mask(a.window)      # the chunk is a context suffix

        def gather(pool):
            # (N, bs, ...) -> (1, nkv·bs, ...) context view via the table
            g = pool[bt[0]]
            return g.reshape(1, g.shape[0] * g.shape[1], *g.shape[2:])

        def one(lp, h, kp, vp):
            if is_mla:
                h2, kp = self._chunk_mla(lp, h, kp, cos, sin, start, end,
                                         bt)
                return h2, kp, vp
            q, k, v = L.attn_qkv(lp["attn"], h, cfg, cos, sin)
            kp = _paged_write_chunk(kp, k, bt, start, end)
            vp = _paged_write_chunk(vp, v, bt, start, end)
            o, _ = chunk_attn(q, gather(kp), gather(vp), mask=spec,
                              impl=rt.impl, q_offset=start)
            h2 = L.attn_out(lp["attn"], h, o, cfg)
            return h2, kp, vp

        if at == "moe":
            nd = cfg.moe.n_dense_layers

            def moe_mlp(lp, h2):
                h3, _ = M.moe_apply(lp["moe"], h2, cfg, mesh=rt.mesh,
                                    seq_axis=rt.par.seq_axis,
                                    batch_axes=rt.par.batch_axes)
                return h3
            if is_mla:
                def bodyd(h, xs):
                    lp, cp = xs
                    h2, cp = self._chunk_mla(lp, h, cp, cos, sin, start,
                                             end, bt)
                    return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), cp

                def bodym(h, xs):
                    lp, cp = xs
                    h2, cp = self._chunk_mla(lp, h, cp, cos, sin, start,
                                             end, bt)
                    return moe_mlp(lp, h2), cp
                h, c1 = xscan(bodyd, h, (p["dense_layers"],
                                         cache["ckv_pool"][:nd]))
                h, c2 = xscan(bodym, h, (p["moe_layers"],
                                         cache["ckv_pool"][nd:]))
                return {"ckv_pool": jnp.concatenate([c1, c2]),
                        "block_table": bt}

            def bodyd(h, xs):
                lp, kp, vp = xs
                h2, kp, vp = one(lp, h, kp, vp)
                return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), (kp, vp)

            def bodym(h, xs):
                lp, kp, vp = xs
                h2, kp, vp = one(lp, h, kp, vp)
                return moe_mlp(lp, h2), (kp, vp)
            h, (k1, v1) = xscan(bodyd, h, (p["dense_layers"],
                                           cache["k_pool"][:nd],
                                           cache["v_pool"][:nd]))
            h, (k2, v2) = xscan(bodym, h, (p["moe_layers"],
                                           cache["k_pool"][nd:],
                                           cache["v_pool"][nd:]))
            return {"k_pool": jnp.concatenate([k1, k2]),
                    "v_pool": jnp.concatenate([v1, v2]),
                    "block_table": bt}

        def body(h, xs):
            lp, kp, vp = xs
            h2, kp, vp = one(lp, h, kp, vp)
            return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), (kp, vp)
        h, (kp, vp) = xscan(body, h, (p["layers"], cache["k_pool"],
                                      cache["v_pool"]))
        return {"k_pool": kp, "v_pool": vp, "block_table": bt}

    def _chunk_mla(self, lp, h, cp, cos, sin, start, end, bt):
        """One layer of chunked paged absorbed-MLA prefill: write the
        chunk's latents, then latent-space attention over the gathered
        context (the value view is the latent's first ``kv_lora`` dims)."""
        cfg, rt = self.cfg, self.rt
        a = cfg.attn
        c = a.kv_lora_rank
        q_full, new, w_uv = self._mla_decode_parts(lp, h, cos, sin)
        cp = _paged_write_chunk(cp, new, bt, start, end)
        g = cp[bt[0]]
        g = g.reshape(1, g.shape[0] * g.shape[1], 1, g.shape[2])
        o_lat, _ = chunk_attn(q_full, g, g[..., :c],
                              mask=_decode_mask(a.window),
                              scale=L.mla_scale(cfg), impl=rt.impl,
                              q_offset=start)
        h2 = self._mla_out(lp, h, o_lat, w_uv)
        return h2, cp

    # -------------------------------------------------------------- decode
    def decode(self, p, cache, batch):
        """One decode step: batch = {"token": (B,1) int32, "pos": (B,)}.

        ``pos`` holds each request's current context length (its new token's
        position); a scalar is a deprecated broadcast shim.  ``cache`` is
        either the dense contiguous cache from :meth:`prefill` or a *paged
        view* (``k_pool``/``v_pool`` or ``ckv_pool`` block pools +
        ``block_table`` — see serve/cache.py), in which case the new
        token's K/V is scattered into the request's current block and
        attention gathers through the block table."""
        cfg, rt = self.cfg, self.rt
        at = cfg.arch_type
        tok = batch["token"]
        pos = _norm_pos(batch["pos"], tok.shape[0])
        h = p["embed"][tok].astype(self.dtype)        # (B,1,d)
        cos = sin = None
        if cfg.uses_attention:
            dim = (cfg.attn.qk_rope_head_dim if cfg.attn.is_mla
                   else cfg.attn.head_dim)
            cos, sin = _decode_rope(pos, dim, cfg.attn.rope_theta)
        if at in ("dense", "vlm", "moe"):
            if _is_paged(cache):
                h, cache = self._decode_attn_stack_paged(p, cache, h, cos,
                                                         sin, pos)
            else:
                h, cache = self._decode_attn_stack(p, cache, h, cos, sin,
                                                   pos)
        elif at == "ssm":
            def body(h, xs):
                lp, st, cv = xs
                h2, st2, cv2 = S.ssm_decode_step(lp["ssm"], h, st, cv, cfg)
                return h2, (st2, cv2)
            h, (st, cv) = xscan(body, h,
                                   (p["layers"], cache["state"],
                                    cache["conv"]))
            cache = {"state": st, "conv": cv}
        elif at == "hybrid":
            h, cache = self._decode_hybrid(p, cache, h, cos, sin, pos)
        logits = self._head(p, h)
        return logits, cache

    def verify(self, p, cache, batch):
        """Multi-token speculative verification over a *paged* cache:
        batch = {"tokens": (B, T) int32, "pos": (B,), "n_write": (B,)}.

        Token t of request b sits at context position ``pos_b + t`` — row 0
        is the request's pending token, rows 1.. are draft proposals.  All
        T rows write-then-attend in one pass (the chunked-prefill pattern
        turned batched), but only the first ``n_write_b`` rows scatter into
        real blocks; the rest null-redirect so rejected drafts leave no
        trace that masking doesn't already hide.  With T = 1 and
        ``n_write = 1`` this is the vanilla :meth:`decode` computation.
        Returns (logits (B, T, V), cache)."""
        cfg, rt = self.cfg, self.rt
        if not _is_paged(cache):
            raise ValueError("verify() requires a paged cache view")
        if cfg.arch_type not in ("dense", "vlm", "moe"):
            raise NotImplementedError(
                f"verify(): arch_type={cfg.arch_type!r} has no paged "
                f"decode path")
        toks = jnp.asarray(batch["tokens"], jnp.int32)
        B, T = toks.shape
        pos = _norm_pos(batch["pos"], B)
        n_write = jnp.asarray(batch["n_write"], jnp.int32)
        h = p["embed"][toks].astype(self.dtype)       # (B,T,d)
        cos = sin = None
        if cfg.uses_attention:
            dim = (cfg.attn.qk_rope_head_dim if cfg.attn.is_mla
                   else cfg.attn.head_dim)
            flat = (pos[:, None]
                    + jnp.arange(T, dtype=jnp.int32)[None, :]).reshape(-1)
            c, s = L.rope_tables(flat, dim, cfg.attn.rope_theta)
            cos, sin = c.reshape(B, T, -1), s.reshape(B, T, -1)
        h, cache = self._decode_attn_stack_paged(p, cache, h, cos, sin,
                                                 pos, n_write=n_write)
        logits = self._head(p, h)
        return logits, cache

    def _decode_attn_stack(self, p, cache, h, cos, sin, pos):
        cfg, rt = self.cfg, self.rt
        a = cfg.attn
        is_mla = a is not None and a.is_mla

        def one(lp, h, ck, cv):
            if is_mla:
                return self._decode_mla(lp, h, ck, cv, cos, sin, pos)
            q, k, v = L.attn_qkv(lp["attn"], h, cfg, cos, sin)
            o = dist_decode_attn(q, ck, cv, k, v, mesh=rt.mesh,
                                 seq_axes=rt.par.seq_axes,
                                 batch_axes=rt.par.batch_axes,
                                 mask=_decode_mask(a.window), pos=pos)
            ck = _cache_write(ck, k, pos, rt)
            cv = _cache_write(cv, v, pos, rt)
            h2 = L.attn_out(lp["attn"], h, o, cfg)
            return h2, ck, cv

        if cfg.arch_type == "moe":
            nd = cfg.moe.n_dense_layers
            if is_mla:
                def bodyd(h, xs):
                    lp, ck = xs
                    h2, ck, _ = self._decode_mla(lp, h, ck, None, cos, sin,
                                                 pos)
                    return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), ck
                def bodym(h, xs):
                    lp, ck = xs
                    h2, ck, _ = self._decode_mla(lp, h, ck, None, cos, sin,
                                                 pos)
                    h3 = M.moe_decode_apply(lp["moe"], h2, cfg,
                                            mesh=rt.mesh,
                                            seq_axis=rt.par.seq_axis,
                                            batch_axes=rt.par.batch_axes)
                    return h3, ck
                h, c1 = xscan(bodyd, h, (p["dense_layers"],
                                            cache["ckv"][:nd]))
                h, c2 = xscan(bodym, h, (p["moe_layers"],
                                            cache["ckv"][nd:]))
                return h, {"ckv": jnp.concatenate([c1, c2])}
            def bodyd(h, xs):
                lp, ck, cv = xs
                h2, ck, cv = one(lp, h, ck, cv)
                h3 = L.mlp_apply(lp["mlp"], h2, cfg.norm_eps)
                return h3, (ck, cv)
            def bodym(h, xs):
                lp, ck, cv = xs
                h2, ck, cv = one(lp, h, ck, cv)
                h3 = M.moe_decode_apply(lp["moe"], h2, cfg, mesh=rt.mesh,
                                        seq_axis=rt.par.seq_axis,
                                        batch_axes=rt.par.batch_axes)
                return h3, (ck, cv)
            h, (k1, v1) = xscan(bodyd, h, (p["dense_layers"],
                                              cache["k"][:nd],
                                              cache["v"][:nd]))
            h, (k2, v2) = xscan(bodym, h, (p["moe_layers"],
                                              cache["k"][nd:],
                                              cache["v"][nd:]))
            cache = {"k": jnp.concatenate([k1, k2]),
                     "v": jnp.concatenate([v1, v2])}
            return h, cache

        def body(h, xs):
            lp, ck, cv = xs
            h2, ck, cv = one(lp, h, ck, cv)
            h3 = L.mlp_apply(lp["mlp"], h2, cfg.norm_eps)
            return h3, (ck, cv)
        h, (ck, cv) = xscan(body, h, (p["layers"], cache["k"],
                                         cache["v"]))
        return h, {"k": ck, "v": cv}

    def _decode_attn_stack_paged(self, p, cache, h, cos, sin, pos,
                                 n_write=None):
        """Decode through a paged cache view: per layer, the new tokens'
        K/V (or MLA latent) is scattered into the request's current block
        (write-then-attend), then attention gathers the context through the
        block table (``paged_decode_attn``).  ``cache`` = {"k_pool",
        "v_pool"} or {"ckv_pool"} pools with leading layer dim +
        "block_table" (B, nb); ``pos`` (B,) per-request context lengths.

        ``h`` carries T tokens (T = 1 for vanilla decode; T = K + 1 for a
        speculative verify pass, where row t sits at context position
        ``pos + t``).  ``n_write`` (B,) caps how many rows each request
        scatters into real blocks (the rest null-redirect); None means the
        single-token decode write path."""
        cfg, rt = self.cfg, self.rt
        a = cfg.attn
        is_mla = a is not None and a.is_mla
        bt = cache["block_table"]
        T = h.shape[1]
        lengths = pos + T                # incl. all written/draft tokens
        if n_write is None:              # vanilla decode: T = 1
            write = lambda pool, new: _paged_write(pool, new, bt, pos)
        else:
            write = lambda pool, new: _paged_write_multi(pool, new, bt,
                                                         pos, n_write)

        def one(lp, h, kp, vp):
            if is_mla:
                h2, kp = self._decode_mla_paged(lp, h, kp, cos, sin,
                                                bt, lengths, write)
                return h2, kp, vp
            q, k, v = L.attn_qkv(lp["attn"], h, cfg, cos, sin)
            kp = write(kp, k)
            vp = write(vp, v)
            o = paged_decode_attn(q, kp, vp, bt, lengths,
                                  mask=_decode_mask(a.window), impl=rt.impl)
            h2 = L.attn_out(lp["attn"], h, o, cfg)
            return h2, kp, vp

        if cfg.arch_type == "moe":
            nd = cfg.moe.n_dense_layers
            if is_mla:
                def bodyd(h, xs):
                    lp, cp = xs
                    h2, cp = self._decode_mla_paged(lp, h, cp, cos, sin,
                                                    bt, lengths, write)
                    return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), cp
                def bodym(h, xs):
                    lp, cp = xs
                    h2, cp = self._decode_mla_paged(lp, h, cp, cos, sin,
                                                    bt, lengths, write)
                    h3 = M.moe_decode_apply(lp["moe"], h2, cfg,
                                            mesh=rt.mesh,
                                            seq_axis=rt.par.seq_axis,
                                            batch_axes=rt.par.batch_axes)
                    return h3, cp
                h, c1 = xscan(bodyd, h, (p["dense_layers"],
                                         cache["ckv_pool"][:nd]))
                h, c2 = xscan(bodym, h, (p["moe_layers"],
                                         cache["ckv_pool"][nd:]))
                return h, {"ckv_pool": jnp.concatenate([c1, c2]),
                           "block_table": bt}
            def bodyd(h, xs):
                lp, kp, vp = xs
                h2, kp, vp = one(lp, h, kp, vp)
                return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), (kp, vp)
            def bodym(h, xs):
                lp, kp, vp = xs
                h2, kp, vp = one(lp, h, kp, vp)
                h3 = M.moe_decode_apply(lp["moe"], h2, cfg, mesh=rt.mesh,
                                        seq_axis=rt.par.seq_axis,
                                        batch_axes=rt.par.batch_axes)
                return h3, (kp, vp)
            h, (k1, v1) = xscan(bodyd, h, (p["dense_layers"],
                                           cache["k_pool"][:nd],
                                           cache["v_pool"][:nd]))
            h, (k2, v2) = xscan(bodym, h, (p["moe_layers"],
                                           cache["k_pool"][nd:],
                                           cache["v_pool"][nd:]))
            return h, {"k_pool": jnp.concatenate([k1, k2]),
                       "v_pool": jnp.concatenate([v1, v2]),
                       "block_table": bt}

        def body(h, xs):
            lp, kp, vp = xs
            h2, kp, vp = one(lp, h, kp, vp)
            return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps), (kp, vp)
        h, (kp, vp) = xscan(body, h, (p["layers"], cache["k_pool"],
                                      cache["v_pool"]))
        return h, {"k_pool": kp, "v_pool": vp, "block_table": bt}

    def _mla_decode_parts(self, lp, h, cos, sin):
        """Shared absorbed-MLA decode projections for ``T`` tokens (T = 1
        for decode, a chunk for paged prefill): effective latent-space
        query ``q_full`` (B,T,nh,c+dr), the tokens' latent cache entries
        ``new`` (B,T,c+dr), and the value up-projection ``w_uv``."""
        cfg = self.cfg
        a = cfg.attn
        p_ = lp["attn"]
        B, T = h.shape[0], h.shape[1]
        nh, dn, dr, c = a.n_heads, a.qk_nope_head_dim, a.qk_rope_head_dim, \
            a.kv_lora_rank
        dv = a.v_head_dim or a.head_dim
        hn = L.rms_norm(h, p_["ln"], cfg.norm_eps)
        if a.q_lora_rank:
            qc = L.rms_norm(hn @ p_["wq_a"], p_["q_ln"], cfg.norm_eps)
            q = (qc @ p_["wq_b"]).reshape(B, T, nh, dn + dr)
        else:
            q = (hn @ p_["wq"]).reshape(B, T, nh, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        q_pe = L.apply_rope(q_pe, cos, sin)
        wkv_b = p_["wkv_b"].reshape(c, nh, dn + dv)
        w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
        q_eff = jnp.einsum("bthn,chn->bthc", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32)).astype(h.dtype)
        q_full = jnp.concatenate([q_eff, q_pe], axis=-1)     # (B,T,nh,c+dr)
        kv_a = hn @ p_["wkv_a"]
        ckv1 = L.rms_norm(kv_a[..., :c], p_["kv_ln"], cfg.norm_eps)
        kpe1 = L.apply_rope(kv_a[..., c:].reshape(B, T, 1, dr), cos, sin)
        new = jnp.concatenate([ckv1, kpe1[:, :, 0, :]], axis=-1)  # (B,T,c+dr)
        return q_full, new, w_uv

    def _mla_out(self, lp, h, o_lat, w_uv):
        cfg = self.cfg
        a = cfg.attn
        nh = a.n_heads
        dv = a.v_head_dim or a.head_dim
        B = h.shape[0]
        o = jnp.einsum("bthc,chv->bthv", o_lat.astype(jnp.float32),
                       w_uv.astype(jnp.float32)).astype(h.dtype)
        return h + (o.reshape(B, o.shape[1], nh * dv) @
                    lp["attn"]["wo"]).astype(h.dtype)

    def _decode_mla(self, lp, h, ck, cv, cos, sin, pos):
        """Absorbed MLA decode: the cache stores the compressed latent
        (c_kv ⊕ rope-key), 576 dims/token instead of n_heads·(192+128) —
        the MLA memory saving [arXiv:2405.04434]."""
        cfg, rt = self.cfg, self.rt
        a = cfg.attn
        c = a.kv_lora_rank
        q_full, new, w_uv = self._mla_decode_parts(lp, h, cos, sin)
        new4 = new[:, :, None, :]
        o_lat = dist_decode_attn(
            q_full, ck[:, :, None, :], ck[:, :, None, :c], new4,
            new4[..., :c],
            mesh=rt.mesh, seq_axes=rt.par.seq_axes,
            batch_axes=rt.par.batch_axes, mask=_decode_mask(a.window),
            scale=L.mla_scale(cfg), pos=pos)                 # (B,1,nh,c)
        ck = _cache_write(ck, new, pos, rt)
        h2 = self._mla_out(lp, h, o_lat, w_uv)
        return h2, ck, cv

    def _decode_mla_paged(self, lp, h, cp, cos, sin, bt, lengths, write):
        """Paged absorbed-MLA decode: one latent pool (N, bs, c+dr); the
        value view is a narrow slice of the key view (Hkv = 1)."""
        cfg, rt = self.cfg, self.rt
        a = cfg.attn
        c = a.kv_lora_rank
        q_full, new, w_uv = self._mla_decode_parts(lp, h, cos, sin)
        cp = write(cp, new)
        kview = cp[:, :, None, :]                  # (N, bs, 1, c+dr)
        o_lat = paged_decode_attn(
            q_full, kview, kview[..., :c], bt, lengths,
            mask=_decode_mask(a.window), scale=L.mla_scale(cfg),
            impl=rt.impl)
        h2 = self._mla_out(lp, h, o_lat, w_uv)
        return h2, cp

    def _decode_hybrid(self, p, cache, h, cos, sin, pos):
        cfg, rt = self.cfg, self.rt
        period = cfg.hybrid_period
        G = cfg.n_layers // period
        stacked = jax.tree.map(
            lambda a: a.reshape(G, period, *a.shape[1:]), p["layers"])
        emb0 = h
        scfg = self._shared_cfg()
        sa = scfg.attn

        def group(carry, xs):
            hh = carry
            gp, st, cv, sk, sv = xs
            def inner(c, ys):
                lp, st1, cv1 = ys
                h2, st2, cv2 = S.ssm_decode_step(lp["ssm"], c, st1, cv1, cfg)
                return h2, (st2, cv2)
            hh, (st, cv) = xscan(inner, hh, (gp, st, cv))
            # shared attention block decode
            x2 = jnp.concatenate([hh, emb0], axis=-1)
            q, k, v = L.attn_qkv(p["shared"]["attn"], x2, scfg, cos, sin)
            o = dist_decode_attn(q, sk, sv, k, v, mesh=rt.mesh,
                                 seq_axes=rt.par.seq_axes,
                                 batch_axes=rt.par.batch_axes,
                                 mask=_decode_mask(0), pos=pos)
            sk = _cache_write(sk, k, pos, rt)
            sv = _cache_write(sv, v, pos, rt)
            y2 = L.attn_out(p["shared"]["attn"], x2, o, scfg)
            y2 = L.mlp_apply(p["shared"]["mlp"], y2, cfg.norm_eps)
            hh = hh + (y2 @ p["shared"]["down"]).astype(hh.dtype)
            return hh, (st, cv, sk, sv)
        st_g = cache["state"].reshape(G, period, *cache["state"].shape[1:])
        cv_g = cache["conv"].reshape(G, period, *cache["conv"].shape[1:])
        h, (st, cv, sk, sv) = xscan(
            group, h, (stacked, st_g, cv_g,
                       cache["shared_k"], cache["shared_v"]))
        st = st.reshape(cfg.n_layers, *st.shape[2:])
        cv = cv.reshape(cfg.n_layers, *cv.shape[2:])
        return h, {"state": st, "conv": cv, "shared_k": sk, "shared_v": sv}


# --------------------------------------------------------------------------
# Paged-cache write: scatter the new token's K/V through the block table
# --------------------------------------------------------------------------

def _paged_write(pool, new, block_table, pos):
    """Scatter ``new`` (B, 1, ...) into one layer's block ``pool``
    (N, bs, ...) at each request's slot for context position ``pos`` (B,):
    block ``block_table[b, pos_b // bs]``, offset ``pos_b % bs``.  Idle
    batch rows (all-zero table rows) land in the reserved null block 0,
    which ``lengths`` masking keeps unread."""
    bs = pool.shape[1]
    bidx = jnp.take_along_axis(block_table, (pos // bs)[:, None],
                               axis=1)[:, 0]
    return pool.at[bidx, pos % bs].set(new[:, 0].astype(pool.dtype))


def _paged_write_multi(pool, new, block_table, pos, n_write):
    """Scatter ``new`` (B, T, ...) into one layer's block ``pool``: row t
    holds context position ``pos_b + t``.  Rows with ``t >= n_write_b``
    (draft slack beyond a request's write budget, or idle batch rows with
    ``n_write = 0``) are redirected to the reserved null block 0 — never
    gathered unmasked, so collisions there are harmless."""
    bs, nb = pool.shape[1], block_table.shape[1]
    T = new.shape[1]
    idx = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # (B,T)
    col = jnp.clip(idx // bs, 0, nb - 1)
    bidx = jnp.take_along_axis(block_table, col, axis=1)
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < n_write[:, None]
    bidx = jnp.where(live, bidx, 0)
    return pool.at[bidx, idx % bs].set(new.astype(pool.dtype))


def _paged_write_chunk(pool, new, block_table, start, end):
    """Scatter a B=1 prefill chunk ``new`` (1, C, ...) into one layer's
    block ``pool`` (N, bs, ...): row ``i`` holds context position
    ``start + i``.  Rows at positions ≥ ``end`` (shape-bucket padding)
    are redirected to the reserved null block 0 — they can never clobber
    a real block, and the null block's garbage is never gathered
    unmasked."""
    bs = pool.shape[1]
    C = new.shape[1]
    idx = start + jnp.arange(C)
    col = jnp.clip(idx // bs, 0, block_table.shape[1] - 1)
    bidx = jnp.where(idx < end, block_table[0, col], 0)
    return pool.at[bidx, idx % bs].set(new[0].astype(pool.dtype))


# --------------------------------------------------------------------------
# KV-cache write: ring-buffer update of the sequence-sharded cache
# --------------------------------------------------------------------------

def _cache_write(cache, new, pos, rt: Runtime):
    """Write ``new`` (B,1,...) into the S-sharded ``cache`` (B,S,...) at
    per-request ring-buffer slot ``pos[b] % S`` (``pos``: (B,) int32, or a
    scalar that broadcasts). Done in a small shard_map: only the owner
    shard of each request's slot scatters (no gather of the cache)."""
    par = rt.par
    seq_axes = par.seq_axes
    n = 1
    for a in seq_axes:
        n *= mesh_axis_size(rt.mesh, a)
    S_loc = cache.shape[1] // n
    bspec = tuple(par.batch_axes) if par.batch_axes else None
    seq = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    nd = cache.ndim
    cspec = P(bspec, seq, *([None] * (nd - 2)))
    rspec = P(bspec, None, *([None] * (nd - 2)))
    pos = jnp.broadcast_to(jnp.asarray(pos), (cache.shape[0],))

    def upd(c, x, pv):
        idx = jnp.int32(0)
        for ax in seq_axes:
            idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
        slot = pv % (n * S_loc)                       # (B,)
        owner = slot // S_loc
        local = slot % S_loc
        hit = ((jnp.arange(S_loc)[None, :] == local[:, None])
               & (owner == idx)[:, None])             # (B, S_loc)
        hit = hit.reshape(hit.shape + (1,) * (c.ndim - 2))
        return jnp.where(hit, x.astype(c.dtype), c)   # x (B,1,...) bcasts

    fn = jax.shard_map(upd, mesh=rt.mesh, in_specs=(cspec, rspec,
                                                    P(bspec)),
                       out_specs=cspec, check_vma=False)
    return fn(cache, new, pos)


# ==========================================================================
# Whisper-style encoder–decoder (audio backbone; conv frontend is a stub —
# batch["frames"] are precomputed frame embeddings) [arXiv:2212.04356]
# ==========================================================================

class EncDecLM:
    """Encoder runs replicated over the sequence axis (n_frames ≪ decoder
    seq — DESIGN.md §5); decoder self-attention uses DISTFLASHATTN; decoder
    cross-attention attends the replicated encoder output locally (zero
    ring communication). Both attention sites sit at remat-aware
    checkpoint boundaries."""

    def __init__(self, cfg: ModelConfig, rt: Runtime):
        self.cfg = cfg
        self.rt = rt
        self.dtype = jnp.dtype(cfg.dtype)

    # ---------------------------------------------------------------- init
    def _cross_params(self, key):
        cfg = self.cfg
        a = cfg.attn
        d, hd = cfg.d_model, a.head_dim
        ks = jax.random.split(key, 4)
        return {"wq": L.dense_init(ks[0], d, a.n_heads * hd, self.dtype),
                "wk": L.dense_init(ks[1], d, a.n_heads * hd, self.dtype),
                "wv": L.dense_init(ks[2], d, a.n_heads * hd, self.dtype),
                "wo": L.dense_init(ks[3], a.n_heads * hd, d, self.dtype),
                "ln": jnp.ones((d,), self.dtype)}

    def init(self, rng):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(rng, 6)
        return {
            "embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model, dt),
            "enc_layers": _stack(ks[1], cfg.n_enc_layers, lambda k: {
                "attn": L.attn_params(k, cfg, dt),
                "mlp": L.mlp_params(jax.random.fold_in(k, 1), cfg.d_model,
                                    cfg.d_ff, dt)}),
            "dec_layers": _stack(ks[2], cfg.n_layers, lambda k: {
                "attn": L.attn_params(k, cfg, dt),
                "cross": self._cross_params(jax.random.fold_in(k, 1)),
                "mlp": L.mlp_params(jax.random.fold_in(k, 2), cfg.d_model,
                                    cfg.d_ff, dt)}),
            "ln_enc": jnp.ones((cfg.d_model,), dt),
            "ln_f": jnp.ones((cfg.d_model,), dt),
        }

    # ------------------------------------------------------------- encoder
    def encode(self, p, frames):
        cfg, rt = self.cfg, self.rt
        h = frames.astype(self.dtype)
        h = constrain(h, rt.mesh, act_spec(rt.par, seq_sharded=False))
        T = h.shape[1]
        cos, sin = L.rope_tables(jnp.arange(T), cfg.attn.head_dim,
                                 cfg.attn.rope_theta)

        def layer(lp, h):
            q, k, v = L.attn_qkv(lp["attn"], h, cfg, cos, sin)
            o, _ = chunk_attn(q, k, v, mask=mk.full(), impl=rt.impl)
            h2 = L.attn_out(lp["attn"], h, o, cfg)
            return L.mlp_apply(lp["mlp"], h2, cfg.norm_eps)

        def body(h, lp):
            return jax.checkpoint(layer)(lp, h), None
        h, _ = xscan(body, h, p["enc_layers"])
        return L.rms_norm(h, p["ln_enc"], cfg.norm_eps)

    # ----------------------------------------------------- decoder layers
    def _dec_layer(self):
        """Two chained remat-aware sub-layers: self-attn, then cross+MLP.
        x = (h, enc, cos, sin)."""
        cfg, rt = self.cfg, self.rt
        spec = _attn_spec(cfg, rt, causal=True)
        a = cfg.attn

        def pre_self(lp, x):
            h, enc, cos, sin = x
            return L.attn_qkv(lp["attn"], h, cfg, cos, sin)

        def self_fwd(qkv):
            return dist_attn_fwd(*qkv, mesh=rt.mesh, spec=spec,
                                 batch_axes=rt.par.batch_axes)

        def self_bwd(qkv, o, lse, do):
            return dist_attn_bwd(*qkv, o, lse, do, mesh=rt.mesh, spec=spec,
                                 batch_axes=rt.par.batch_axes)

        def post_self(lp, x, o):
            h, enc, cos, sin = x
            return (L.attn_out(lp["attn"], h, o, cfg), enc, cos, sin)

        def pre_cross(lp, x):
            h, enc = x[0], x[1]
            B, T, _ = h.shape
            F = enc.shape[1]
            c = lp["cross"]
            hn = L.rms_norm(h, c["ln"], cfg.norm_eps)
            q = (hn @ c["wq"]).reshape(B, T, a.n_heads, a.head_dim)
            k = (enc @ c["wk"]).reshape(B, F, a.n_heads, a.head_dim)
            v = (enc @ c["wv"]).reshape(B, F, a.n_heads, a.head_dim)
            return q, k, v

        def cross_fwd(qkv):
            return chunk_attn(*qkv, mask=mk.full(), impl=rt.impl)

        def cross_bwd(qkv, o, lse, do):
            from repro.core.attention import chunk_attn_bwd
            return chunk_attn_bwd(*qkv, o, lse, do, mask=mk.full(),
                                  impl=rt.impl)

        def post_cross(lp, x, o):
            h, enc = x[0], x[1]
            B, T, _ = h.shape
            h2 = h + (o.reshape(B, T, -1) @ lp["cross"]["wo"]).astype(h.dtype)
            h3 = L.mlp_apply(lp["mlp"], h2, cfg.norm_eps)
            h3 = constrain(h3, rt.mesh, act_spec(rt.par))
            return (h3,) + tuple(x[1:])

        if rt.par.remat == "remat_aware":
            sub_a = remat_aware(pre_self, self_fwd, self_bwd, post_self)
            sub_b = remat_aware(pre_cross, cross_fwd, cross_bwd, post_cross)
            return lambda lp, x: sub_b(lp, sub_a(lp, x))

        def plain(lp, x):
            o, _ = dist_flash_attn(*pre_self(lp, x), rt.mesh, spec,
                                   rt.par.batch_axes)
            x = post_self(lp, x, o)
            qkv = pre_cross(lp, x)
            o2, _ = chunk_attn(*qkv, mask=mk.full(), impl=rt.impl)
            return post_cross(lp, x, o2)
        return jax.checkpoint(plain) if rt.par.remat == "hf" else plain

    # ----------------------------------------------------------- training
    def loss(self, p, batch):
        cfg, rt = self.cfg, self.rt
        enc = self.encode(p, batch["frames"])
        toks = batch["tokens"]
        h = p["embed"][toks].astype(self.dtype)
        h = constrain(h, rt.mesh, act_spec(rt.par))
        T = h.shape[1]
        cos, sin = L.rope_tables(jnp.arange(T), cfg.attn.head_dim,
                                 cfg.attn.rope_theta)
        layer = self._dec_layer()

        def body(carry, lp):
            return layer(lp, carry), None
        (h, *_rest), _ = xscan(body, (h, enc, cos, sin), p["dec_layers"])
        logits = L.rms_norm(h, p["ln_f"], cfg.norm_eps) @ \
            p["embed"].T.astype(h.dtype)
        ce = L.cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce}

    # ---------------------------------------------------------- inference
    def prefill(self, p, batch):
        cfg, rt = self.cfg, self.rt
        enc = self.encode(p, batch["frames"])
        toks = batch["tokens"]
        h = p["embed"][toks].astype(self.dtype)
        h = constrain(h, rt.mesh, act_spec(rt.par))
        T = h.shape[1]
        a = cfg.attn
        cos, sin = L.rope_tables(jnp.arange(T), a.head_dim, a.rope_theta)
        spec = _attn_spec(cfg, rt, causal=True)

        def body(h, lp):
            q, k, v = L.attn_qkv(lp["attn"], h, cfg, cos, sin)
            o, _ = dist_attn_fwd(q, k, v, mesh=rt.mesh, spec=spec,
                                 batch_axes=rt.par.batch_axes)
            h2 = L.attn_out(lp["attn"], h, o, cfg)
            c = lp["cross"]
            B, F = enc.shape[0], enc.shape[1]
            hn = L.rms_norm(h2, c["ln"], cfg.norm_eps)
            qc = (hn @ c["wq"]).reshape(B, T, a.n_heads, a.head_dim)
            ek = (enc @ c["wk"]).reshape(B, F, a.n_heads, a.head_dim)
            ev = (enc @ c["wv"]).reshape(B, F, a.n_heads, a.head_dim)
            o2, _ = chunk_attn(qc, ek, ev, mask=mk.full(), impl=rt.impl)
            h3 = h2 + (o2.reshape(B, T, -1) @ c["wo"]).astype(h2.dtype)
            h4 = L.mlp_apply(lp["mlp"], h3, cfg.norm_eps)
            return h4, (k, v, ek, ev)
        h, (ck, cv, ek, ev) = xscan(body, h, p["dec_layers"])
        logits = L.rms_norm(h[:, -1:], p["ln_f"], cfg.norm_eps) @ \
            p["embed"].T.astype(h.dtype)
        return logits, {"k": ck, "v": cv, "ek": ek, "ev": ev}

    def decode(self, p, cache, batch):
        cfg, rt = self.cfg, self.rt
        a = cfg.attn
        tok = batch["token"]
        pos = _norm_pos(batch["pos"], tok.shape[0])
        h = p["embed"][tok].astype(self.dtype)
        cos, sin = _decode_rope(pos, a.head_dim, a.rope_theta)

        def body(h, xs):
            lp, ck, cv, ek, ev = xs
            B = h.shape[0]
            q, k, v = L.attn_qkv(lp["attn"], h, cfg, cos, sin)
            o = dist_decode_attn(q, ck, cv, k, v, mesh=rt.mesh,
                                 seq_axes=rt.par.seq_axes,
                                 batch_axes=rt.par.batch_axes,
                                 mask=_decode_mask(a.window), pos=pos)
            ck = _cache_write(ck, k, pos, rt)
            cv = _cache_write(cv, v, pos, rt)
            h2 = L.attn_out(lp["attn"], h, o, cfg)
            c = lp["cross"]
            hn = L.rms_norm(h2, c["ln"], cfg.norm_eps)
            qc = (hn @ c["wq"]).reshape(B, 1, a.n_heads, a.head_dim)
            o2, _ = chunk_attn(qc, ek, ev, mask=mk.full(), impl=rt.impl)
            h3 = h2 + (o2.reshape(B, 1, -1) @ c["wo"]).astype(h2.dtype)
            h4 = L.mlp_apply(lp["mlp"], h3, cfg.norm_eps)
            return h4, (ck, cv)
        h, (ck, cv) = xscan(body, h, (p["dec_layers"], cache["k"],
                                         cache["v"], cache["ek"],
                                         cache["ev"]))
        logits = L.rms_norm(h, p["ln_f"], cfg.norm_eps) @ \
            p["embed"].T.astype(h.dtype)
        return logits, {"k": ck, "v": cv, "ek": cache["ek"],
                        "ev": cache["ev"]}


def build_model(cfg: ModelConfig, rt: Runtime):
    if cfg.arch_type == "audio":
        return EncDecLM(cfg, rt)
    return DecoderLM(cfg, rt)
