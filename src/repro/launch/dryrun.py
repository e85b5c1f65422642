"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes and extract memory/cost/collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k [--multi-pod] [--schedule balanced] [--out f.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # driver loop

Success of ``.lower().compile()`` for a pair proves the sharding config is
coherent (no mismatched collectives, divisibility holes, or unsupported
layouts); the printed analyses feed EXPERIMENTS.md §Dry-run and §Roofline.
The CLI runs on 512 placeholder host devices, which stand in for the
production 2×16×16 multi-pod mesh; importing this module changes nothing.
"""
import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import roofline as R
from repro.core.config import (ARCH_IDS, SHAPES, TrainConfig, get_config,
                               get_shape)
from repro.data.pipeline import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config, param_shardings
from repro.train.step import make_train_step

LONG_CTX_WINDOW = 8192   # paper Appendix-F sliding window for long_500k


def prepare(arch: str, shape_name: str, mesh, *, schedule="balanced",
            remat="remat_aware", impl=None, latent_ring=False):
    """Build (step_fn, arg_structs, in_shardings) for one pair."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape_name == "long_500k" and cfg.uses_attention:
        # sub-quadratic requirement: Appendix-F sliding window for the
        # attention families; SSM/hybrid are naturally O(1)-state
        cfg = cfg.replace(attn=dataclasses.replace(cfg.attn,
                                                   window=LONG_CTX_WINDOW))
    par = make_parallel_config(mesh, shape, schedule=schedule, remat=remat)
    rt = Runtime(mesh=mesh, par=par, impl=impl, latent_ring=latent_ring)
    model = build_model(cfg, rt)

    p_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = param_shardings(p_struct, mesh, par)
    batch_struct, batch_spec = input_specs(cfg, shape, par, mesh)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_spec,
                            is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "train":
        tc = TrainConfig()
        opt_struct = jax.eval_shape(adamw.init, p_struct)
        opt_sh = adamw.AdamWState(step=NamedSharding(mesh, P()), m=p_sh,
                                  v=jax.tree.map(lambda s: s, p_sh))
        step = make_train_step(model, tc)
        args = (p_struct, opt_struct, batch_struct)
        shardings = (p_sh, opt_sh, batch_sh)
    elif shape.kind == "prefill":
        step = lambda p, b: model.prefill(p, b)[0]
        args = (p_struct, batch_struct)
        shardings = (p_sh, batch_sh)
    else:  # decode
        cache_struct = batch_struct.pop("cache")
        cache_sh = batch_sh.pop("cache")
        step = lambda p, c, b: model.decode(p, c, b)
        args = (p_struct, cache_struct, batch_struct)
        shardings = (p_sh, cache_sh, batch_sh)
    return cfg, shape, step, args, shardings


def _knob_points(cfg):
    """Scan trip-count knobs per arch family for cost extrapolation.

    XLA's cost_analysis counts a ``while`` (scan) body ONCE, so FLOPs /
    bytes / collective counts of an L-layer scanned model are reported as
    if L=1. Layers are homogeneous, so every cost is an affine function of
    the scan trip counts; we compile 2–3 reduced-depth variants, fit the
    affine model exactly, and evaluate it at the true depth. The full-depth
    compile is still performed for memory_analysis + compile success.

    Returns (dims, points, builder): ``dims`` the true knob values, each
    point a knob tuple, ``builder(knobs) -> cfg``.
    """
    at = cfg.arch_type
    if at == "moe":
        nd = cfg.moe.n_dense_layers
        dims = (nd, cfg.n_layers - nd)
        pts = [(2, 2), (3, 2), (2, 3)]

        def build(k):
            return cfg.replace(
                n_layers=k[0] + k[1],
                moe=dataclasses.replace(cfg.moe, n_dense_layers=k[0]))
        return dims, pts, build
    if at == "hybrid":
        period = cfg.hybrid_period
        G = cfg.n_layers // period
        dims = (G, G * period)           # cost = o + G·c_shared + GP·c_ssm
        pts = [(2, 4), (3, 6), (2, 6)]   # (G, G·period) with period 2, 2, 3

        def build(k):
            g, gp = k
            return cfg.replace(n_layers=gp, hybrid_period=gp // g)
        return dims, pts, build
    if at == "audio":
        dims = (cfg.n_enc_layers, cfg.n_layers)
        pts = [(2, 2), (3, 2), (2, 3)]

        def build(k):
            return cfg.replace(n_enc_layers=k[0], n_layers=k[1])
        return dims, pts, build
    dims = (cfg.n_layers,)
    pts = [(2,), (3,)]

    def build(k):
        return cfg.replace(n_layers=k[0])
    return dims, pts, build


def _measure(cfg, shape, mesh, schedule, remat, impl=None,
             latent_ring=False):
    """(flops, bytes, collective_bytes, hop_bytes) for one concrete cfg,
    compiled with UNROLLED layer scans so cost_analysis sees every layer.
    ``impl="null"`` swaps the attention math for an O(T) stub (collectives
    and all surrounding ops intact) to isolate the kernel's contribution."""
    from repro.models.transformer import set_scan_unroll
    set_scan_unroll(True)
    try:
        return _measure_inner(cfg, shape, mesh, schedule, remat, impl,
                              latent_ring)
    finally:
        set_scan_unroll(False)


def _measure_inner(cfg, shape, mesh, schedule, remat, impl,
                   latent_ring=False):
    par = make_parallel_config(mesh, shape, schedule=schedule, remat=remat)
    rt = Runtime(mesh=mesh, par=par, impl=impl, latent_ring=latent_ring)
    model = build_model(cfg, rt)
    p_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = param_shardings(p_struct, mesh, par)
    batch_struct, batch_spec = input_specs(cfg, shape, par, mesh)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_spec,
                            is_leaf=lambda x: isinstance(x, P))
    if shape.kind == "train":
        step = make_train_step(model, TrainConfig())
        opt_struct = jax.eval_shape(adamw.init, p_struct)
        opt_sh = adamw.AdamWState(step=NamedSharding(mesh, P()), m=p_sh,
                                  v=jax.tree.map(lambda s: s, p_sh))
        args, shd = (p_struct, opt_struct, batch_struct), \
            (p_sh, opt_sh, batch_sh)
    elif shape.kind == "prefill":
        step = lambda p, b: model.prefill(p, b)[0]
        args, shd = (p_struct, batch_struct), (p_sh, batch_sh)
    else:
        cache_struct = batch_struct.pop("cache")
        cache_sh = batch_sh.pop("cache")
        step = lambda p, c, b: model.decode(p, c, b)
        args, shd = (p_struct, cache_struct, batch_struct), \
            (p_sh, cache_sh, batch_sh)
    compiled = jax.jit(step, in_shardings=shd).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = R.collective_stats(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            coll.total_bytes, coll.hop_weighted_bytes, coll)


def extrapolate_costs(cfg, shape, mesh, schedule, remat, impl=None,
                      latent_ring=False):
    """Affine fit of (flops, bytes, coll, hop) over the scan knobs."""
    import numpy as np
    dims, pts, build = _knob_points(cfg)
    rows, ys = [], []
    last_coll = None
    for k in pts:
        f, b, c, h, coll = _measure(build(k), shape, mesh, schedule, remat,
                                    impl, latent_ring)
        rows.append([1.0] + list(k))
        ys.append([f, b, c, h])
        last_coll = coll
    A = np.array(rows)
    Y = np.array(ys)
    coef, *_ = np.linalg.lstsq(A, Y, rcond=None)
    target = np.array([1.0] + list(dims))
    f, b, c, h = (target @ coef).tolist()
    return {"flops": max(f, 0.0), "bytes": max(b, 0.0),
            "coll_bytes": max(c, 0.0), "hop_bytes": max(h, 0.0),
            "per_knob": coef.tolist(), "knob_dims": list(dims),
            "coll_kinds_at_smallest": last_coll.bytes_by_kind}


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            schedule="balanced", remat="remat_aware",
            latent_ring=False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cfg, shape, step, args, shardings = prepare(
        arch, shape_name, mesh, schedule=schedule, remat=remat,
        latent_ring=latent_ring)
    t0 = time.time()
    lowered = jax.jit(step, in_shardings=shardings).lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = R.collective_stats(compiled.as_text())
    if multi_pod:
        # the multi-pod pass proves the 512-chip sharding lowers+compiles
        # and reports memory; the roofline table is single-pod (§Roofline)
        return {
            "arch": arch, "shape": shape_name, "schedule": schedule,
            "remat": remat, "multi_pod": True, "chips": chips,
            "kind": shape.kind, "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "peak_device_bytes": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      + mem.output_size_in_bytes
                                      - mem.alias_size_in_bytes),
            },
            "collective_op_counts_scan_body_once": coll.op_counts,
            "compiled_ok": True,
        }
    # scan-aware extrapolated costs (see _knob_points). NOTE: cfg here
    # already carries the long_500k window override from prepare().
    ext = extrapolate_costs(cfg, shape, mesh, schedule, remat,
                            latent_ring=latent_ring)
    flops = ext["flops"]
    bytes_acc = ext["bytes"]
    # kernel-adjusted terms: null-attention measurement + analytic Pallas
    # kernel costs (the ref path materializes O(T²) scores on CPU, which a
    # TPU flash kernel never writes to HBM — see roofline.py)
    par = make_parallel_config(mesh, shape, schedule=schedule, remat=remat)
    seq_shards = 1
    for ax in par.seq_axes:
        seq_shards *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
    batch_shards = 1
    for ax in par.batch_axes:
        batch_shards *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
    if cfg.uses_attention:
        ext_null = extrapolate_costs(cfg, shape, mesh, schedule, remat,
                                     impl="null", latent_ring=latent_ring)
        an_f, an_b = R.attention_analytic(cfg, shape, seq_shards=seq_shards,
                                          batch_shards=batch_shards)
        adj_flops = ext_null["flops"] + an_f
        adj_bytes = ext_null["bytes"] + an_b
        adj_coll = ext_null["coll_bytes"]
    else:
        an_f = an_b = 0.0
        adj_flops, adj_bytes, adj_coll = flops, bytes_acc, ext["coll_bytes"]
    mf = R.model_flops(cfg, shape, chips=chips)
    terms = R.roofline_terms(flops, bytes_acc, ext["coll_bytes"])
    terms_adj = R.roofline_terms(adj_flops, adj_bytes, adj_coll)
    rec = {
        "arch": arch, "shape": shape_name, "schedule": schedule,
        "remat": remat, "multi_pod": multi_pod, "chips": chips,
        "kind": shape.kind,
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_device_bytes": (mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  + mem.output_size_in_bytes
                                  - mem.alias_size_in_bytes),
        },
        "hlo_flops": flops,
        "hlo_bytes": bytes_acc,
        "hlo_flops_scan_body_once": float(cost.get("flops", 0.0)),
        "collectives": {
            "total_bytes": ext["coll_bytes"],
            "hop_weighted_bytes": ext["hop_bytes"],
            "by_kind_scan_body_once": coll.bytes_by_kind,
            "op_counts_scan_body_once": coll.op_counts,
        },
        "extrapolation": {"knob_dims": ext["knob_dims"],
                          "per_knob_coeffs": ext["per_knob"]},
        "model_flops_per_chip": mf,
        "useful_flops_ratio": (mf / flops) if flops else None,
        "attention_analytic": {"flops": an_f, "bytes": an_b},
        "roofline_as_lowered": terms,
        "roofline": terms_adj,
        "adjusted": {"flops": adj_flops, "bytes": adj_bytes,
                     "coll_bytes": adj_coll,
                     "useful_flops_ratio": (mf / adj_flops)
                     if adj_flops else None},
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ("llama-7b", "llama-gqa",
                                                  "llama-33h", "llama-16h"))
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--schedule", default="balanced",
                    choices=("balanced", "ring", "rsa", "zigzag",
                             "ulysses"))
    ap.add_argument("--remat", default="remat_aware",
                    choices=("remat_aware", "hf", "none"))
    ap.add_argument("--latent-ring", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) in subprocesses")
    ap.add_argument("--results-dir", default="results/dryrun")
    args = ap.parse_args(argv)

    if args.all:
        return run_all(args)

    rec = run_one(args.arch, args.shape, multi_pod=args.multi_pod,
                  schedule=args.schedule, remat=args.remat,
                  latent_ring=args.latent_ring)
    js = json.dumps(rec, indent=1)
    print(js)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)
    return 0


def run_all(args):
    os.makedirs(args.results_dir, exist_ok=True)
    fails = []
    for multi_pod in (False, True):
        for arch in ARCH_IDS:
            for shape in SHAPES:
                tag = f"{'pod2' if multi_pod else 'pod1'}_{arch}_{shape}"
                out = os.path.join(args.results_dir, tag + ".json")
                if os.path.exists(out):
                    print(f"[skip] {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", out,
                       "--schedule", args.schedule, "--remat", args.remat]
                if multi_pod:
                    cmd.append("--multi-pod")
                print(f"[run ] {tag}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    fails.append(tag)
                    print(f"[FAIL] {tag}\n{r.stderr[-2000:]}")
    print(f"done; {len(fails)} failures: {fails}")
    return 1 if fails else 0


if __name__ == "__main__":
    # before the first backend initialization, which locks the device count
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
