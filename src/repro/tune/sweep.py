"""Offline sweep harness: measure → pick winners → tuning-table rows.

Three sweeps, one per tuning surface (driven by ``tools/autotune.py``):

  * :func:`sweep_kernels` — kernel tile shapes.  For every (backend,
    mask kind, head_dim, seq bucket) the candidate ``block_q``×``block_kv``
    tiles race round-robin (:func:`repro.tune.timing.timeit_round_robin`,
    the same interleaved-median clock ``benchmarks/kernel_bench.py``
    uses) and the fastest tile becomes the table row.
  * :func:`sweep_schedules` — distributed-schedule wall time.  A
    subprocess with ``--xla_force_host_platform_device_count=8`` times
    every capable schedule per (mask, seq) on the host mesh — the same
    harness as ``benchmarks/run.py bench_schedules_wall`` — and each row
    keeps the full per-schedule wall map so ``tune/calibrate.py`` can fit
    cost-model coefficients against it.
  * :func:`sweep_paged` — paged-decode ``block_size`` per kv layout via
    ``benchmarks/serving_bench.run_trace`` microtraces with the pool
    token capacity held ~constant across candidate block sizes.

Everything lands in one table document (see :mod:`repro.tune.table`);
``--smoke`` shrinks shapes/iters to CI scale (seconds, not minutes).
"""
from __future__ import annotations

import os
import platform as _platform
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

from repro.tune.table import SCHEMA_VERSION
from repro.tune.timing import timeit_round_robin

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                          "..", "..", ".."))


def host_info() -> dict:
    import jax
    return dict(platform=jax.default_backend(),
                jax=jax.__version__,
                devices=jax.device_count(),
                machine=_platform.machine(),
                python=_platform.python_version())


def new_table_data() -> dict:
    return dict(schema_version=SCHEMA_VERSION,
                generated_by="tools/autotune.py",
                host=host_info(),
                kernel=[], schedule=[], paged=[])


# --------------------------------------------------------------------------
# (a) kernel tile shapes
# --------------------------------------------------------------------------

def _kernel_masks(T: int) -> Dict[str, object]:
    from repro.core import mask as mk
    return {
        "causal": mk.causal(),
        "sliding_window": mk.sliding_window(max(T // 4, 1)),
        "document": mk.document(boundaries=mk.doc_boundaries(T, 4)),
        "full": mk.full(),
    }


def _tile_candidates(backend: str, T: int,
                     blocks: Sequence[int]) -> List[tuple]:
    """(block_q, block_kv) grid.  chunked-lax ignores block_q (its scan
    has a single whole-chunk q block), so only block_kv varies there —
    no point timing the same kernel N times."""
    bs = [b for b in blocks if b <= T] or [T]
    if backend == "chunked-lax":
        return [(bs[-1], bk) for bk in bs]
    return [(bq, bk) for bq in bs for bk in bs]


def _kernel_runner(backend, op, q, k, v, do, mask, bq, bk):
    import jax
    from repro.kernels import ops
    from repro.kernels.chunked import chunked_bwd, chunked_fwd
    if backend == "pallas-interpret":
        if op == "fwd":
            def run():
                o, _ = ops.flash_fwd(q, k, v, mask=mask, block_q=bq,
                                     block_kv=bk, interpret=True)
                jax.block_until_ready(o)
            return run
        o, lse = ops.flash_fwd(q, k, v, mask=mask, interpret=True)

        def run():
            g = ops.flash_bwd(q, k, v, o, lse, do, mask=mask, block_q=bq,
                              block_kv=bk, interpret=True)
            jax.block_until_ready(g)
        return run
    if op == "fwd":
        fn = jax.jit(lambda q, k, v: chunked_fwd(q, k, v, mask=mask,
                                                 block_kv=bk))

        def run():
            jax.block_until_ready(fn(q, k, v))
        return run
    o, lse = chunked_fwd(q, k, v, mask=mask)
    fn = jax.jit(lambda q, k, v, o, lse, do: chunked_bwd(
        q, k, v, o, lse, do, mask=mask, block_kv=bk))

    def run():
        jax.block_until_ready(fn(q, k, v, o, lse, do))
    return run


def sweep_kernels(data: dict, *, smoke: bool = False,
                  log=print) -> None:
    """Race candidate tiles per (backend, mask_kind, head_dim, seq);
    append winner rows to ``data['kernel']``."""
    import jax
    import jax.numpy as jnp
    plat = jax.default_backend()
    if smoke:
        grid = [("chunked-lax", 128, 32), ("pallas-interpret", 64, 32)]
        blocks, iters, H = (16, 32, 64), 2, 2
    else:
        grid = [("chunked-lax", 256, 64), ("chunked-lax", 512, 64),
                ("chunked-lax", 1024, 64),
                ("pallas-interpret", 128, 32), ("pallas-interpret", 256, 32)]
        blocks, iters, H = (32, 64, 128, 256), 3, 4
    for backend, T, D in grid:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (jax.random.normal(kk, (1, T, H, D), jnp.float32)
                       for kk in ks)
        for mask_kind, m in _kernel_masks(T).items():
            for op in ("fwd", "bwd"):
                cands = _tile_candidates(backend, T, blocks)
                fns = [_kernel_runner(backend, op, q, k, v, do, m, bq, bk)
                       for bq, bk in cands]
                med = timeit_round_robin(fns, iters)
                best = min(range(len(cands)), key=lambda i: med[i])
                bq, bk = cands[best]
                data["kernel"].append(dict(
                    backend=backend, platform=plat, mask_kind=mask_kind,
                    head_dim=D, seq=T, op=op, block_q=bq, block_kv=bk,
                    wall_us=round(med[best], 1),
                    sweep={f"{a}x{b}": round(u, 1)
                           for (a, b), u in zip(cands, med)}))
                log(f"kernel {backend:16s} {mask_kind:15s} T={T:5d} "
                    f"D={D} {op}: best {bq}x{bk} "
                    f"({med[best] / 1e3:.1f}ms)")


# --------------------------------------------------------------------------
# (b) distributed-schedule wall time (8-device host mesh, subprocess)
# --------------------------------------------------------------------------

_SCHED_CODE = """
import time, statistics, numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd, zigzag_perm
SEQS = {seqs!r}
SCHEDS = {scheds!r}
REGIMES = {regimes!r}
ITERS = {iters}
mesh = make_mesh((1, 8), ("data", "model"))
B, H, D = 1, 8, 64
def timeit(f, *a):
    jax.block_until_ready(f(*a))
    ts = []
    for _ in range(ITERS):
        t0 = time.perf_counter(); jax.block_until_ready(f(*a))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6
for N in SEQS:
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, N, H, D), jnp.float32) for kk in ks)
    bnd = mk.doc_boundaries(N, 8)
    seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, bnd), (B, 1)))
    perm = zigzag_perm(N, 8)
    win = N // 8
    specs = dict(causal=(mk.causal(), False),
                 document=(mk.document(), True),
                 sliding_window=(mk.sliding_window(win), False))
    for sched in SCHEDS:
        qq, kk_, vv, ss = (q[:, perm], k[:, perm], v[:, perm],
                           seg[:, perm]) if sched == "zigzag" else (q, k, v,
                                                                    seg)
        for regime in REGIMES:
            m, needs_seg = specs[regime]
            if sched == "rsa" and regime == "sliding_window":
                continue
            spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched,
                                mask=m)
            if needs_seg:
                f = jax.jit(lambda a, b, c, s, _spec=spec: dist_attn_fwd(
                    a, b, c, mesh=mesh, spec=_spec, batch_axes=None,
                    segments=s)[0])
                us = timeit(f, qq, kk_, vv, ss)
            else:
                f = jax.jit(lambda a, b, c, _spec=spec: dist_attn_fwd(
                    a, b, c, mesh=mesh, spec=_spec, batch_axes=None)[0])
                us = timeit(f, qq, kk_, vv)
            print(f"RESULT {{regime}} {{N}} {{win}} {{sched}} {{us:.0f}}",
                  flush=True)
"""


def sweep_schedules(data: dict, *, smoke: bool = False, log=print,
                    seqs: Optional[Sequence[int]] = None) -> None:
    """Measure per-schedule forward wall on the 8-device host mesh and
    append one row per (mask_kind, seq) with the full wall map."""
    if smoke:
        seqs = tuple(seqs or (256,))
        scheds = ("ring", "balanced", "ulysses")
        regimes = ("causal", "sliding_window")
        iters = 2
    else:
        seqs = tuple(seqs or (1024, 2048))
        scheds = ("ring", "balanced", "zigzag", "ulysses", "rsa")
        regimes = ("causal", "document", "sliding_window")
        iters = 3
    code = _SCHED_CODE.format(seqs=tuple(seqs), scheds=tuple(scheds),
                              regimes=tuple(regimes), iters=iters)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"schedule sweep subprocess failed:\n"
                           f"{r.stderr[-2000:]}")
    rows: Dict[tuple, dict] = {}
    for line in r.stdout.splitlines():
        if not line.startswith("RESULT"):
            continue
        _, regime, N, win, sched, us = line.split()
        key = (regime, int(N))
        row = rows.setdefault(key, dict(
            mask_kind=regime, P=8, seq=int(N), B=1, Hq=8, Hkv=8, Dqk=64,
            bpe=4, window=int(win) if regime == "sliding_window" else None,
            dynamic_seg=regime == "document", best=None, wall_us={}))
        row["wall_us"][sched] = float(us)
    for key in sorted(rows):
        row = rows[key]
        row["best"] = min(row["wall_us"], key=row["wall_us"].get)
        data["schedule"].append(row)
        log(f"schedule {row['mask_kind']:15s} seq={row['seq']:5d}: "
            f"best {row['best']} " + " ".join(
                f"{s}={u / 1e3:.0f}ms"
                for s, u in sorted(row["wall_us"].items())))


# --------------------------------------------------------------------------
# (c) paged-decode block size
# --------------------------------------------------------------------------

def _cache_layout(arch: str) -> str:
    """kv layout label of this arch's paged cache ("mha"/"gqa"/"mla")."""
    from repro.core.config import get_config, smoke_config
    from repro.serve.cache import PagedKVCache
    cfg = smoke_config(get_config(arch))
    return PagedKVCache.create(cfg, block_size=4, n_blocks=2,
                               max_reqs=1).layout


def sweep_paged(data: dict, *, smoke: bool = False, log=print) -> None:
    """Race paged block sizes per kv layout on a serving microtrace; the
    pool's token capacity is held ~constant so candidates differ only in
    granularity (alloc pressure, pad waste), not total memory."""
    if _REPO_ROOT not in sys.path:       # benchmarks/ is repo-root relative
        sys.path.insert(0, _REPO_ROOT)
    from benchmarks.serving_bench import run_trace
    if smoke:
        archs = ("smollm-360m",)
        sizes = (8, 16)
        kw = dict(n_requests=3, max_batch=2, prompt_lens=(8, 12),
                  budgets=(3, 5), mean_gap=1, seed=0)
    else:
        archs = ("smollm-360m", "deepseek-v2-lite-16b")
        sizes = (4, 8, 16, 32)
        kw = dict(n_requests=8, max_batch=4, prompt_lens=(16, 24, 32),
                  budgets=(6, 10, 14), mean_gap=1, seed=0)
    tokens = 17 * 8                       # default pool capacity of the trace
    for arch in archs:
        layout = _cache_layout(arch)
        meas = {}
        for bs in sizes:
            res = run_trace(arch=arch, block_size=bs,
                            n_blocks=max(tokens // bs, 4) + 1, **kw)
            meas[bs] = float(res["tokens_per_s"])
            log(f"paged {arch} ({layout}) block_size={bs}: "
                f"{meas[bs]:.1f} tok/s")
        best = max(meas, key=lambda b: (meas[b], -b))
        data["paged"].append(dict(
            layout=layout, sharding="none", arch=arch, block_size=best,
            tokens_per_s=round(meas[best], 2),
            sweep={str(b): round(t, 2) for b, t in sorted(meas.items())}))
        log(f"paged {arch} ({layout}): best block_size={best}")


# --------------------------------------------------------------------------

def run_sweep(*, smoke: bool = False, parts=("kernel", "schedule", "paged"),
              seqs: Optional[Sequence[int]] = None, log=print) -> dict:
    """Run the requested sweeps into a fresh table document."""
    data = new_table_data()
    if "kernel" in parts:
        sweep_kernels(data, smoke=smoke, log=log)
    if "schedule" in parts:
        sweep_schedules(data, smoke=smoke, log=log, seqs=seqs)
    if "paged" in parts:
        sweep_paged(data, smoke=smoke, log=log)
    return data
