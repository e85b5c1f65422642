"""Three-term roofline from a compiled dry-run artifact.

    compute_s    = HLO_FLOPs / peak_FLOPs            (per chip)
    memory_s     = HLO_bytes / HBM_bw                (per chip)
    collective_s = collective_link_bytes / ICI_bw    (per chip)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (the SPMD
module is the per-device program, so these are already per chip).
Collective bytes are parsed from the optimized HLO text: for each
collective op we estimate the *per-device link traffic* from the result
shape and replica-group size (ring-algorithm estimates):

    collective-permute : R            (one send + one recv of the result)
    all-gather         : R·(n−1)/n    (R = gathered result)
    all-reduce         : 2·R·(n−1)/n
    reduce-scatter     : R·(n−1)      (R = scattered result; input = n·R)
    all-to-all         : R·(n−1)/n

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
Ring ``ppermute`` steps in DISTFLASHATTN are neighbor exchanges (1 hop);
the balanced schedule's distance-t result send costs t hops on a physical
ring — we report the 1-hop number and note the worst-case hop multiplier
separately (hop_weighted uses the source-target distance when available).
"""
from __future__ import annotations

import re
from dataclasses import dataclass

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+?))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict
    total_bytes: float
    hop_weighted_bytes: float
    op_counts: dict


def collective_stats(hlo_text: str) -> CollectiveStats:
    by_kind: dict = {}
    counts: dict = {}
    hopw = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        rtype, kind = m.group(1), m.group(2)
        r = _shape_bytes(rtype)
        if kind in ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all"):
            g = _GROUPS_RE.search(line)
            n = len(g.group(1).split(",")) if g else 2
        else:
            n = 2
        if kind == "collective-permute":
            moved = r
            pm = _PAIRS_RE.search(line)
            if pm:
                pairs = [tuple(map(int, p.split(",")))
                         for p in pm.group(1).strip("{}").split("},{")]
                dmax = max(abs(a - b) for a, b in pairs) if pairs else 1
                hopw += r * dmax
            else:
                hopw += r
        elif kind == "all-gather":
            moved = r * (n - 1) / max(n, 1)
            hopw += moved
        elif kind == "all-reduce":
            moved = 2 * r * (n - 1) / max(n, 1)
            hopw += moved
        elif kind == "reduce-scatter":
            moved = r * (n - 1)
            hopw += moved
        else:  # all-to-all
            moved = r * (n - 1) / max(n, 1)
            hopw += moved
        by_kind[kind] = by_kind.get(kind, 0.0) + moved
        counts[kind] = counts.get(kind, 0) + 1
    return CollectiveStats(by_kind, sum(by_kind.values()), hopw, counts)


def model_flops(cfg, shape, *, chips: int) -> float:
    """MODEL_FLOPS per chip: 6·N_active·tokens (train) / 2·N·tokens
    (prefill) / 2·N·batch (decode, one token)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tok = shape.global_batch * shape.seq_len
        total = 6.0 * n * tok
    elif shape.kind == "prefill":
        tok = shape.global_batch * shape.seq_len
        total = 2.0 * n * tok
    else:
        total = 2.0 * n * shape.global_batch
    return total / chips


def a2a_bytes(nbytes: float, k: int) -> float:
    """Per-device link bytes of a tiled all-to-all over a k-device axis:
    each device keeps 1/k of its payload local and ships the rest —
    factor (k − 1)/k.  Used by core/schedule.plan2d_cost for the head-
    scatter traffic of 2D (seq×head) factorizations."""
    return nbytes * (k - 1) / max(k, 1)


def allgather_bytes(nbytes: float, k: int) -> float:
    """Per-device link bytes of a tiled all-gather over a k-device axis
    (ring algorithm): every device receives the other k − 1 shards —
    factor (k − 1).  ``nbytes`` is one device's shard."""
    return nbytes * (k - 1)


def schedule_cost_terms(*, flops, comm_bytes):
    """Two-term time model for a static schedule-plan cost
    (core/schedule.PlanCost): kernel FLOPs against peak compute, hop-
    weighted ring-link bytes against per-link ICI bandwidth.  This is what
    ``DistAttnSpec(schedule="auto")`` ranks candidate schedules by — HBM
    traffic is schedule-invariant at this granularity (every schedule
    streams the same chunks) so the memory term is omitted."""
    ct = flops / PEAK_FLOPS
    kt = comm_bytes / ICI_BW
    return {"compute_s": ct, "collective_s": kt,
            "bound": "compute" if ct >= kt else "collective",
            "step_s_lower_bound": max(ct, kt)}


def roofline_terms(flops, bytes_accessed, coll_bytes):
    ct = flops / PEAK_FLOPS
    mt = bytes_accessed / HBM_BW
    kt = coll_bytes / ICI_BW
    dom = max((ct, "compute"), (mt, "memory"), (kt, "collective"))
    return {"compute_s": ct, "memory_s": mt, "collective_s": kt,
            "bound": dom[1],
            "step_s_lower_bound": max(ct, mt, kt)}


# --------------------------------------------------------------------------
# Analytic attention-kernel costs (per chip).
#
# The CPU dry-run lowers the pure-jnp reference attention, which materializes
# O(T²) score tiles — faithful in FLOPs but wildly pessimistic in HBM bytes
# vs. the Pallas kernel (which keeps tiles in VMEM). For the kernel-adjusted
# roofline we measure the model with a null-attention stub (all surrounding
# ops + ring collectives intact) and add the kernel's ideal costs computed
# here from first principles.
# --------------------------------------------------------------------------

def _site(flops_fwd, flops_bwd, bytes_fwd, bytes_bwd, train):
    if train:
        return flops_fwd + flops_bwd, bytes_fwd + bytes_bwd
    return flops_fwd, bytes_fwd


def _self_attn_site(*, B_loc, T_glob, P, H, hd_qk, hd_v, Hkv, window,
                    causal, train, bpe=2):
    """One sequence-sharded self-attention site, per chip."""
    if causal:
        w = min(window, T_glob) if window else T_glob
        pairs = B_loc * T_glob * (w / 2 if not window else w) / P
        steps = (P // 2 + 1) if not window else \
            min(P, max(1, -(-w // max(T_glob // P, 1))) + 1)
    else:
        pairs = B_loc * T_glob * T_glob / P
        steps = P
    T_loc = T_glob // P
    f_fwd = 2 * pairs * H * (hd_qk + hd_v)
    f_bwd = 2 * pairs * H * (3 * hd_qk + 2 * hd_v)
    kv_chunk = B_loc * T_loc * Hkv * (hd_qk + hd_v) * bpe
    q_bytes = B_loc * T_loc * H * hd_qk * bpe
    o_bytes = B_loc * T_loc * H * hd_v * bpe
    b_fwd = q_bytes + o_bytes + steps * kv_chunk
    b_bwd = 2 * q_bytes + 2 * o_bytes + 2 * steps * kv_chunk
    return _site(f_fwd, f_bwd, b_fwd, b_bwd, train)


def _decode_attn_site(*, B, S, seq_shards, H, hd_qk, hd_v, Hkv, window,
                      bpe=2):
    w = min(window, S) if window else S
    pairs = B * w / seq_shards
    flops = 2 * pairs * H * (hd_qk + hd_v)
    bytes_ = B * (w / seq_shards) * Hkv * (hd_qk + hd_v) * bpe
    return flops, bytes_


def attention_analytic(cfg, shape, *, seq_shards, batch_shards):
    """Total analytic kernel (flops, bytes) per chip for all attention
    sites of one (arch × shape)."""
    a = cfg.attn
    if a is None:
        return 0.0, 0.0
    train = shape.kind == "train"
    B_loc = max(shape.global_batch // batch_shards, 1)
    P = seq_shards
    win = a.window
    is_mla = a.is_mla
    hd_qk = (a.qk_nope_head_dim + a.qk_rope_head_dim) if is_mla else a.head_dim
    hd_v = (a.v_head_dim or a.head_dim) if is_mla else a.head_dim
    Hkv = a.n_heads if is_mla else a.n_kv_heads
    fl = by = 0.0

    if shape.kind in ("train", "prefill"):
        T = shape.seq_len
        n_self = cfg.n_layers + (cfg.mtp_depth or 0)
        if cfg.arch_type == "hybrid":
            n_self = cfg.n_layers // cfg.hybrid_period
        if cfg.arch_type == "ssm":
            n_self = 0
        if n_self:
            f, b = _self_attn_site(B_loc=B_loc, T_glob=T, P=P, H=a.n_heads,
                                   hd_qk=hd_qk, hd_v=hd_v, Hkv=Hkv,
                                   window=win, causal=True, train=train)
            fl += n_self * f
            by += n_self * b
        if cfg.arch_type == "audio":
            F = cfg.n_audio_frames
            # encoder self (bidirectional, replicated over the seq axis)
            f, b = _self_attn_site(B_loc=B_loc, T_glob=F, P=1, H=a.n_heads,
                                   hd_qk=hd_qk, hd_v=hd_v, Hkv=a.n_heads,
                                   window=0, causal=False, train=train)
            fl += cfg.n_enc_layers * f
            by += cfg.n_enc_layers * b
            # decoder cross-attention: q sharded, enc kv replicated
            pairs = B_loc * (T // P) * F
            f_fwd = 2 * pairs * a.n_heads * 2 * a.head_dim
            b_fwd = B_loc * F * a.n_heads * a.head_dim * 2 * 2
            fl += cfg.n_layers * (f_fwd * (2.5 if train else 1.0))
            by += cfg.n_layers * (b_fwd * (2.0 if train else 1.0))
        return fl, by

    # decode
    S = shape.seq_len
    B = shape.global_batch
    n_self = cfg.n_layers + (cfg.mtp_depth or 0)
    if cfg.arch_type == "hybrid":
        n_self = cfg.n_layers // cfg.hybrid_period
    if cfg.arch_type == "ssm":
        n_self = 0
    if is_mla:  # absorbed-latent decode: single 576-dim latent head cache
        hd_qk_d = a.kv_lora_rank + a.qk_rope_head_dim
        hd_v_d = a.kv_lora_rank
        f, b = _decode_attn_site(B=B, S=S, seq_shards=seq_shards * batch_shards
                                 if shape.global_batch == 1 else seq_shards,
                                 H=a.n_heads, hd_qk=hd_qk_d, hd_v=hd_v_d,
                                 Hkv=1, window=win)
    else:
        f, b = _decode_attn_site(B=B, S=S, seq_shards=seq_shards,
                                 H=a.n_heads, hd_qk=hd_qk, hd_v=hd_v,
                                 Hkv=Hkv, window=win)
    fl += n_self * f
    by += n_self * b
    if cfg.arch_type == "audio":
        F = cfg.n_audio_frames
        fl += cfg.n_layers * 2 * B * F * a.n_heads * 2 * a.head_dim
        by += cfg.n_layers * B * F * a.n_heads * a.head_dim * 2 * 2
    return fl, by
