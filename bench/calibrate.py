"""Readings that the limits of ``correct`` are set from, on the chip, at a
cell's own size, in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 12 [--control 3]
        [--fault 3] [--out calib.jsonl]

For each seed it prints one JSON line of the numbers ``correct`` compares:

* ``program``: the timed path against the reference (the lower reading
  is the largest of these over the seeds);
* ``control`` (first ``--control`` seeds): the reference computed in the
  nearest precision below the configuration's (float8 for bfloat16), put
  in the program's place (the upper reading is the smallest of these);
* ``fault`` (first ``--fault`` seeds): the program with half of
  each batch's tokens left out of the loss, the mean taken over the rest.

A step that leaves its state unchanged reads 1 on ``update_leaf`` by
construction and needs no run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness as H  # noqa: E402

sys.path.insert(0, str(H.SRC))


def emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def half_labels(rows):
    """The same rows with the second half of every row's labels left out."""
    out = []
    for r in rows:
        r = dict(r)
        lab = r["labels"].copy()
        lab[:, lab.shape[1] // 2:] = -100
        r["labels"] = lab
        out.append(r)
    return out


def raw(r):
    """A reading as plain lists (per step, per leaf in sorted path order)."""
    return {"losses": list(r.losses), "gnorms": list(r.gnorms),
            "grad_leaf": [float(x) for x in r.grad_leaf],
            "change_leaf": [float(x) for x in r.change_leaf],
            "grad_diff_leaf": [float(x) for x in r.grad_diff_leaf]
            if r.grad_diff_leaf is not None else None}


def train(ctx, args):
    import checks
    import traffic
    import weights
    import jax
    from repro.optim import adamw
    drv = H.load_module("drivers", "train")
    tr, p_sh, b_sh, _ = drv.build(ctx)
    o = ctx.workload["optimizer"]
    init = jax.jit(adamw.init, out_shardings=jax.tree.map(
        lambda x: x.sharding, tr.opt))
    for i in range(args.seeds):
        ctx.seed = args.seed_base + i
        rows = traffic.train_rows(ctx.workload["traffic"], tr.cfg.vocab,
                                  ctx.seed, drv.N_CHECK)
        runs = {"program": rows}
        if i < args.fault:
            runs["fault"] = half_labels(rows)
        got = {}
        for name, rs in runs.items():
            tr.params = weights.make(ctx.config, H.jax_key(ctx.seed),
                                     shardings=p_sh)
            tr.opt = init(tr.params)
            got[name] = drv.first_steps(tr, drv.put(rs, b_sh), ctx.config,
                                        p_sh, ctx.seed, o["beta1"])
        tr.params = tr.opt = None
        gc.collect()
        want = drv.reference_reading(ctx, rows)
        if i < args.control:
            got["control"] = drv.reference_reading(ctx, rows,
                                                   precision="fp8")
        rec = {"seed": ctx.seed, "reference": raw(want)}
        ref_first = want.first
        for name, g in got.items():
            want.first = ref_first
            checks.grad_diff(g, want)
            rec[name] = checks.train_numbers(g, want)
            rec[name + "_raw"] = raw(g)
        emit(args.out, rec)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", type=int, default=3)
    ap.add_argument("--seed-base", type=int, default=7_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import run
    ctx = run.make_ctx(argparse.Namespace(
        workload=args.workload, seed=0, seconds=0.0, trace=0))
    H.enable_compile_cache()
    ctx.devices = H.require_chips(int(ctx.workload["chips"]))
    ctx.peaks = H.peaks_for(ctx.devices[0].device_kind)
    ctx.compiles = H.CompileCounter()
    if ctx.workload["driver"] != "train":
        raise H.BenchError(f"no calibration for driver "
                           f"{ctx.workload['driver']!r}")
    train(ctx, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
