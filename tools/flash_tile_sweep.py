"""Time the flash kernels on a TPU at candidate tile shapes.

    python tools/flash_tile_sweep.py [--seq 8192] [--q-seq N] [--iters 5]
        [--mask causal|unmasked] [--tiles 512x512,1024x1024] [--out FILE]

For each attention width (smollm-360m's 15/5 heads at head_dim 64, and
qwen3-8b's 32/8 at 128) and each candidate ``(block_q, block_kv)``, one
bf16 forward and backward at batch 1 is compiled, warmed up and
profiled over ``--iters`` calls. The device time of each kernel's events
(``flash_fwd``, ``flash_bwd.dq``, ``flash_bwd.dkv``) is summed from the
trace and printed per call in ms, one JSON line per candidate. The mask
is causal (a training chunk on its own diagonal) or none (``unmasked``:
the sequence-parallel step's off-diagonal 8192² chunks). ``--q-seq``
gives the queries a length of their own (default ``--seq``): 4096 against
8192 keys is the half pair each of two devices computes in the balanced
plan's split last step. The tile rule of ``flash_tiles`` was chosen from
these readings (PERF.md). Fails without a TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import re
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import devtrace  # noqa: E402
from repro.core import mask as mk  # noqa: E402
from repro.kernels import ops  # noqa: E402

WIDTHS = {"hd64": (15, 5, 64), "hd128": (32, 8, 128)}
TILES = [(128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
         (512, 1024), (1024, 512), (1024, 1024)]
KERNELS = ("flash_fwd", "flash_bwd.dq", "flash_bwd.dkv")
MASKS = {"causal": mk.causal(), "unmasked": mk.MaskSpec()}


def kernel_ms(fn, args, iters):
    """{kernel: device ms per call} of ``iters`` profiled calls."""
    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp(prefix="tile-sweep-")
    jax.profiler.start_trace(d)
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    evs = devtrace.events_from_xspace(path)["devices"]
    tot = dict.fromkeys(KERNELS, 0)
    for dev in evs.values():
        for name, _, _, dur in dev:
            k = re.sub(r"\.\d+$", "", name)
            if k in tot:
                tot[k] += dur
    return {k: v / 1e6 / iters for k, v in tot.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--q-seq", type=int, default=None)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--mask", choices=sorted(MASKS), default="causal")
    ap.add_argument("--tiles", default=None,
                    help="comma-separated QxKV candidates (default: all)")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    tiles = TILES if a.tiles is None else [
        tuple(int(x) for x in t.split("x")) for t in a.tiles.split(",")]
    if jax.default_backend() != "tpu":
        sys.exit("flash_tile_sweep: no TPU")
    mask, T, rows = MASKS[a.mask], a.seq, []
    Tq = T if a.q_seq is None else a.q_seq
    for width, (hq, hkv, d) in WIDTHS.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, do = (jax.random.normal(k, (1, Tq, hq, d), jnp.bfloat16)
                 for k in ks[:2])
        k, v = (jax.random.normal(kk, (1, T, hkv, d), jnp.bfloat16)
                for kk in ks[2:])
        for bq, bk in tiles:
            def fwd_bwd(q, k, v, do, bq=bq, bk=bk):
                o, lse = ops.flash_fwd(q, k, v, mask=mask, block_q=bq,
                                       block_kv=bk)
                return ops.flash_bwd(q, k, v, o, lse, do, mask=mask,
                                     block_q=bq, block_kv=bk)
            fn = jax.jit(fwd_bwd)
            try:
                ms = kernel_ms(fn, (q, k, v, do), a.iters)
            except Exception as e:  # noqa: BLE001 - report and go on
                ms = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            row = {"width": width, "mask": a.mask, "seq": T, "q_seq": Tq,
                   "block_q": bq, "block_kv": bk, **ms}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
