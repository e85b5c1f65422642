"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics with the profiler off;
``--trace 1`` runs the same work with the profiler on over the window and
reports the per-layer metrics instead. Either way the run ends by comparing
what the timed path produced with the plain reference (``correct``).

The last line of standard output is one JSON object; nothing else is
printed there. Progress, the compiles counted inside the window and the
numbers compared (each beside its limit) go to standard error. Without a
TPU, or on a device kind missing from ``bench/peaks.json``, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness as H  # noqa: E402

sys.path.insert(0, str(H.SRC))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_ctx(args) -> H.Ctx:
    spec = H.load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        raise H.BenchError(f"no cell {args.workload!r} in BENCHMARK.json")
    wl = H.load_workload(args.workload)
    wl.setdefault("chips", cells[args.workload]["chips"])
    cfg = H.load_config(cells[args.workload]["config"])
    return H.Ctx(spec=spec, workload=wl, config=cfg, seed=args.seed,
                 seconds=args.seconds, trace=bool(args.trace),
                 t_start=T_START)


def result_line(ctx: H.Ctx, res: H.Result) -> dict:
    units = H.end_to_end_units(ctx.spec)
    if ctx.trace:
        metrics = H.per_layer_metrics(ctx)
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res.metrics.items()}
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": ctx.counts["memory_peak_bytes"]}
    if ctx.trace:
        device["busy_s"] = ctx.trace_summary.busy_s
        device["window_s"] = ctx.trace_summary.window_s
    line = {"correct": res.correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": device}
    if ctx.trace and res.breakdown:
        line["breakdown"] = res.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in res.checks}
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        ctx = make_ctx(args)
        H.enable_compile_cache()
        ctx.devices = H.require_chips(int(ctx.workload["chips"]))
        ctx.peaks = H.peaks_for(ctx.devices[0].device_kind)
        ctx.compiles = H.CompileCounter()
        driver = H.load_module("drivers", ctx.workload["driver"])
        res = driver.run(ctx)
        line = result_line(ctx, res)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"compiles inside the window: {ctx.compiles.in_window} "
          f"(whole run {ctx.compiles.total})", file=sys.stderr, flush=True)
    for c in res.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
