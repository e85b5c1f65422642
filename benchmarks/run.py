"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Wall-clock rows are measured
on this host (CPU; 8 forced host devices in subprocess benches) as the
median over iterations (robust to CPU timing noise); derived rows are
analytic or HLO-derived quantities that reproduce the paper's comparisons
where real multi-GPU wall time is unavailable. ``--json OUT`` additionally
writes the rows to a machine-readable JSON file.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,table5] [--json F]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.schedule_sim import (balanced_schedule, coverage_ok,
                                     expected_speedup, idle_fraction,
                                     ring_schedule)
from repro.launch.mesh import make_mesh

ROWS = []


def row(name, us, derived=""):
    ROWS.append((name, us, derived))
    print(f"{name},{us},{derived}", flush=True)


def _timeit(fn, iters=5):
    fn()  # warmup/compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6  # median-of-iters: noise-robust


# ---------------------------------------------------------------- figure 4

def bench_fig4_load_balance():
    """Paper Fig. 1/4 + Eq. 2: idle fractions and expected speedups of ring
    vs balanced scheduling, from the schedule simulator (coverage-proved)."""
    for P in (4, 7, 8, 16, 32):
        rp, rb = ring_schedule(P)
        bp, bb = balanced_schedule(P)
        assert coverage_ok(rp, P) and coverage_ok(bp, P), P
        row(f"fig4/ring_idle_P{P}", 0, f"{idle_fraction(rb, P):.4f}")
        row(f"fig4/balanced_idle_P{P}", 0, f"{idle_fraction(bb, P):.4f}")
        row(f"fig4/ring_speedup_P{P}", 0, f"{expected_speedup(rb, P):.2f}")
        row(f"fig4/balanced_speedup_P{P}", 0,
            f"{expected_speedup(bb, P):.2f}")
    # paper's Eq.2 closed forms (even P)
    for P in (8, 16):
        row(f"fig4/eq2_theory_P{P}", 0, f"{1 / (2 * P):.4f}")


# ---------------------------------------------------------------- table 5

def bench_table5_checkpointing():
    """Remat-aware vs HF checkpointing: wall-clock per train step (tiny
    LLaMA-family model on CPU) + backward-pass HLO FLOPs ratio."""
    from repro.core.config import (TrainConfig, get_config, smoke_config,
                                   ShapeSpec)
    from repro.data.pipeline import SyntheticTokens
    from repro.models.transformer import Runtime, build_model
    from repro.optim import adamw
    from repro.parallel.sharding import make_parallel_config
    from repro.train.step import make_train_step

    cfg = smoke_config(get_config("llama-7b")).replace(n_layers=4)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("b5", 512, 2, "train")
    results = {}
    for remat in ("none", "hf", "remat_aware"):
        par = make_parallel_config(mesh, shape, remat=remat)
        model = build_model(cfg, Runtime(mesh=mesh, par=par))
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init(params)
        batch = SyntheticTokens(cfg, shape, par, mesh).batch(0)
        step = jax.jit(make_train_step(model, TrainConfig()))
        flops = step.lower(params, opt, batch).compile().cost_analysis(
        ).get("flops", 0)

        def run(step=step, params=params, opt=opt, batch=batch):
            jax.block_until_ready(step(params, opt, batch))

        us = _timeit(run, iters=3)
        results[remat] = (us, flops)
        row(f"table5/train_step_{remat}", f"{us:.0f}", f"flops={flops:.3e}")
    hf_us, hf_f = results["hf"]
    ra_us, ra_f = results["remat_aware"]
    row("table5/speedup_remat_aware_vs_hf", 0, f"{hf_us / ra_us:.3f}x")
    row("table5/flops_ratio_hf_over_remat_aware", 0, f"{hf_f / ra_f:.3f}")


# ---------------------------------------------------------------- table 3

def bench_table3_rsa():
    """RSA vs DISTFLASHATTN: peak attention memory (compiled temp bytes)
    and wall time, 8 host devices, seq-parallel attention only."""
    code = """
import time, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
mesh = make_mesh((1,8), ("data","model"))
B,N,H,D = 1,4096,8,64
ks = jax.random.split(jax.random.PRNGKey(0),3)
q,k,v = (jax.random.normal(kk,(B,N,H,D),jnp.float32) for kk in ks)
for sched in ("rsa","balanced"):
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=mk.causal())
    f = jax.jit(lambda q,k,v: dist_attn_fwd(q,k,v,mesh=mesh,spec=spec,batch_axes=None)[0])
    co = f.lower(q,k,v).compile()
    mem = co.memory_analysis().temp_size_in_bytes
    jax.block_until_ready(f(q,k,v))
    t0=time.perf_counter()
    for _ in range(3): jax.block_until_ready(f(q,k,v))
    us=(time.perf_counter()-t0)/3*1e6
    print(f"RESULT {sched} {us:.0f} {mem}")
"""
    out = _subproc(code)
    vals = {}
    for line in out.splitlines():
        if line.startswith("RESULT"):
            _, sched, us, mem = line.split()
            vals[sched] = (float(us), int(mem))
            row(f"table3/attn_fwd_{sched}_seq4k_8dev", f"{float(us):.0f}",
                f"temp_bytes={mem}")
    if len(vals) == 2:
        row("table3/rsa_temp_bytes_ratio", 0,
            f"{vals['rsa'][1] / max(vals['balanced'][1], 1):.2f}x")
        row("table3/rsa_time_ratio", 0,
            f"{vals['rsa'][0] / max(vals['balanced'][0], 1):.2f}x")


# ---------------------------------------------------------------- table 4

def bench_table4_ulysses():
    """DISTFLASHATTN vs DeepSpeed-Ulysses: collective bytes per attention
    layer from compiled HLO (8 host devices) + head-divisibility failures."""
    code = """
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd
from repro.analysis.roofline import collective_stats
mesh = make_mesh((1,8), ("data","model"))
B,N,D = 1,4096,64
for name, H, Hkv, sched in [("balanced_mha",8,8,"balanced"),
                            ("ulysses_mha",8,8,"ulysses"),
                            ("balanced_gqa",8,2,"balanced")]:
    ks = jax.random.split(jax.random.PRNGKey(0),3)
    q = jax.random.normal(ks[0],(B,N,H,D)); k = jax.random.normal(ks[1],(B,N,Hkv,D)); v = jax.random.normal(ks[2],(B,N,Hkv,D))
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=mk.causal())
    f = jax.jit(lambda q,k,v: dist_attn_fwd(q,k,v,mesh=mesh,spec=spec,batch_axes=None)[0])
    txt = f.lower(q,k,v).compile().as_text()
    st = collective_stats(txt)
    print(f"RESULT {name} coll_bytes={st.total_bytes:.0f}")
# irregular heads: ulysses must fail, balanced must work (paper 4.2/4.6)
q = jax.random.normal(jax.random.PRNGKey(0),(B,N,33,32))
spec = DistAttnSpec(axis="model", axis_size=8, schedule="ulysses", mask=mk.causal())
try:
    dist_attn_fwd(q,q,q,mesh=mesh,spec=spec,batch_axes=None)
    print("RESULT ulysses_33h ok")
except ValueError:
    print("RESULT ulysses_33h infeasible_head_padding_required")
spec = DistAttnSpec(axis="model", axis_size=8, schedule="balanced", mask=mk.causal())
o,_ = jax.jit(lambda q: dist_attn_fwd(q,q,q,mesh=mesh,spec=spec,batch_axes=None))(q)
print("RESULT balanced_33h ok_no_padding")
"""
    for line in _subproc(code).splitlines():
        if line.startswith("RESULT"):
            parts = line.split()
            row(f"table4/{parts[1]}", 0, " ".join(parts[2:]))


# ------------------------------------------------- schedule-level tracking

def bench_schedules_plans():
    """Tracked static schedule-plan rows (BENCH_schedules.json): per
    schedule × mask regime, the plan's executed/total ring steps, kernel
    calls, and the cost-model predictions that drive schedule="auto" —
    pure python, no devices.  The windowed rows are the step-skipping
    acceptance surface: windowed balanced/zigzag must execute strictly
    fewer steps than their causal plans."""
    from repro.core import mask as mkm
    from repro.core import schedule as spm

    B, N, P, H, D = 1, 2048, 8, 8, 64
    Tl = N // P
    bnd = mkm.doc_boundaries(N, 8)
    regimes = [
        ("causal", mkm.causal(), False),
        ("windowed", mkm.sliding_window(N // 8), False),
        ("document", mkm.document(boundaries=bnd), False),
        ("doc_dynamic", mkm.document(), True),
    ]
    for rname, m, dyn in regimes:
        for sched in ("ring", "balanced", "zigzag"):
            if not spm.plan_capable(sched, m):
                continue
            plan = spm.build_plan(sched, m, P, Tl)
            cost = plan.cost(B=B, Hq=H, Hkv=H, Dqk=D, Dv=D, bpe=4,
                             dynamic_seg=dyn)
            t = cost.time_estimate()
            row(f"schedules/plan_{sched}_{rname}", 0,
                f"steps={plan.exec_steps}/{plan.total_steps} "
                f"calls={plan.kernel_calls} "
                f"pred_compute_s={t['compute_s']:.3e} "
                f"pred_collective_s={t['collective_s']:.3e} "
                f"pred_bound={t['bound']}")
        auto = spm.choose_schedule(m, P, Tl=Tl, B=B, Hq=H, Hkv=H, Dqk=D,
                                   Dv=D, bpe=4, dynamic_seg=dyn)
        row(f"schedules/auto_{rname}", 0, f"resolved={auto}")


def bench_schedules_wall():
    """Tracked schedule-level benchmark (BENCH_schedules.json): forward
    wall-clock of each sequence-parallel schedule on 8 host devices, for
    the dense causal mask, a packed (document) batch, the windowed regime
    (plan step skipping — new ring steps matrix), and schedule="auto" —
    so the perf trajectory covers the schedules, not just the kernels."""
    code = """
import time, statistics, numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd, zigzag_perm
mesh = make_mesh((1,8), ("data","model"))
B,N,H,D = 1,2048,8,64
ks = jax.random.split(jax.random.PRNGKey(0),3)
q,k,v = (jax.random.normal(kk,(B,N,H,D),jnp.float32) for kk in ks)
bnd = mk.doc_boundaries(N, 8)
seg = jnp.asarray(np.tile(mk.segments_from_boundaries(N, bnd), (B,1)))
perm = zigzag_perm(N, 8)
win = mk.sliding_window(N // 8)
def timeit(f, *a):
    jax.block_until_ready(f(*a))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); jax.block_until_ready(f(*a))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6
for sched in ("auto","ring","balanced","zigzag","ulysses","rsa"):
    qq, kk_, vv, ss = (q[:,perm],k[:,perm],v[:,perm],seg[:,perm]) \\
        if sched == "zigzag" else (q,k,v,seg)
    spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=mk.causal())
    f = jax.jit(lambda a,b,c: dist_attn_fwd(a,b,c,mesh=mesh,spec=spec,batch_axes=None)[0])
    us = timeit(f, qq, kk_, vv)
    print(f"RESULT {sched}/causal {us:.0f}")
    specd = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=mk.document())
    fd = jax.jit(lambda a,b,c,s: dist_attn_fwd(a,b,c,mesh=mesh,spec=specd,batch_axes=None,segments=s)[0])
    usd = timeit(fd, qq, kk_, vv, ss)
    print(f"RESULT {sched}/document {usd:.0f}")
    if sched != "rsa":   # rsa has no sliding-window path
        specw = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=win)
        fw = jax.jit(lambda a,b,c: dist_attn_fwd(a,b,c,mesh=mesh,spec=specw,batch_axes=None)[0])
        usw = timeit(fw, qq, kk_, vv)
        print(f"RESULT {sched}/windowed {usw:.0f}")
"""
    for line in _subproc(code).splitlines():
        if line.startswith("RESULT"):
            _, name, us = line.split()
            row(f"schedules/attn_fwd_{name}_seq2k_8dev", f"{float(us):.0f}",
                "wall us, CPU host mesh")


def bench_schedules_plans2d():
    """Tracked 2D (seq×head) factored-plan rows (BENCH_schedules.json):
    for the GQA regime the 1D schedules serve poorly (Hq=8, Hkv=2 — the
    bespoke ulysses is infeasible), the factorized chooser's pick per
    mask regime, the analytic cost of every (r, u) factorization, and the
    measured acceptance walls: the chosen r>1∧u>1 factorization vs the
    pure-ring (r=8) and head-parallel (r=1, u=8) extremes on 8 host
    devices."""
    from repro.core import mask as mkm
    from repro.core import schedule as spm

    B, N, P, Hq, Hkv, D = 1, 2048, 8, 8, 2, 64
    Tl = N // P
    bnd = mkm.doc_boundaries(N, 8)
    regimes = [
        ("causal", mkm.causal(), False),
        ("windowed", mkm.sliding_window(N // 8), False),
        ("document", mkm.document(boundaries=bnd), False),
    ]
    picks = {}
    for rname, m, dyn in regimes:
        name, r, u = spm.choose_schedule(m, P, Tl=Tl, B=B, Hq=Hq,
                                         Hkv=Hkv, Dqk=D, Dv=D, bpe=4,
                                         dynamic_seg=dyn, factorize=True)
        picks[rname] = (m, name, r, u)
        row(f"plans2d/auto_{rname}_gqa8x2", 0, f"resolved={name}@r{r}u{u}")
        for rr, uu in spm.factorizations(P):
            for sched in ("ring", "balanced"):
                if uu == 1:
                    if not spm.plan_capable(sched, m):
                        continue
                    cost = spm.plan_cost(
                        spm.build_plan(sched, m, P, Tl), B=B, Hq=Hq,
                        Hkv=Hkv, Dqk=D, Dv=D, bpe=4, dynamic_seg=dyn)
                else:
                    if not spm.plan2d_capable(sched, m, r=rr, u=uu,
                                              Hq=Hq, Hkv=Hkv):
                        continue
                    cost = spm.plan2d_cost(
                        spm.build_plan2d(sched, m, rr, uu, Tl, Hq=Hq,
                                         Hkv=Hkv), B=B, Dqk=D, Dv=D,
                        bpe=4, dynamic_seg=dyn)
                t = cost.time_estimate()
                row(f"plans2d/cost_{sched}_r{rr}u{uu}_{rname}", 0,
                    f"pred_total_s={t['step_s_lower_bound']:.3e} "
                    f"pred_bound={t['bound']}")

    # measured acceptance walls: regimes whose pick is a genuine 2D
    # factorization (r > 1 and u > 1) race against both 1D extremes.
    # fwd + grads — the horizon the chooser ranked on (include_bwd=True)
    for rname, (m, name, r, u) in picks.items():
        if r == 1 or u == 1:
            continue
        code = f"""
import time, statistics, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import mask as mk
from repro.core.dist_attention import DistAttnSpec, Mesh2DSpec, dist_flash_attn
B,N,Hq,Hkv,D = {B},{N},{Hq},{Hkv},{D}
ks = jax.random.split(jax.random.PRNGKey(0),3)
q = jax.random.normal(ks[0],(B,N,Hq,D),jnp.float32)
k = jax.random.normal(ks[1],(B,N,Hkv,D),jnp.float32)
v = jax.random.normal(ks[2],(B,N,Hkv,D),jnp.float32)
m = mk.{m!r}
def timeit(f,*a):
    jax.block_until_ready(f(*a)); ts=[]
    for _ in range(5):
        t0=time.perf_counter(); jax.block_until_ready(f(*a))
        ts.append(time.perf_counter()-t0)
    return statistics.median(ts)*1e6
for label, sched, r, u in (("chosen",{name!r},{r},{u}),
                           ("pure_ring","ring",8,1),
                           ("head_parallel","ring",1,8)):
    if u == 1:
        mesh = make_mesh((1,8), ("data","model"))
        spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched, mask=m)
    else:
        mesh = make_mesh((1,r,u), ("data","seq","head"))
        spec = DistAttnSpec(axis="seq", axis_size=8, schedule=sched,
                            mask=m, mesh2d=Mesh2DSpec(r=r,u=u))
    def loss(a,b,c,mesh=mesh,spec=spec):
        o,_ = dist_flash_attn(a,b,c,mesh,spec,batch_axes=None)
        return jnp.sum(o*o)
    f = jax.jit(jax.value_and_grad(loss, argnums=(0,1,2)))
    print(f"RESULT {{label}} {{timeit(f,q,k,v):.0f}}")
"""
        walls = {}
        for line in _subproc(code).splitlines():
            if line.startswith("RESULT"):
                _, label, us = line.split()
                walls[label] = float(us)
                row(f"plans2d/attn_step_{label}_{rname}_gqa8x2_seq2k_8dev",
                    f"{float(us):.0f}", "fwd+bwd wall us, CPU host mesh")
        if len(walls) == 3:
            row(f"plans2d/accept_{rname}", 0,
                f"chosen={name}@r{r}u{u} "
                f"beats_ring={'yes' if walls['chosen'] < walls['pure_ring'] else 'NO'} "
                f"beats_head_parallel="
                f"{'yes' if walls['chosen'] < walls['head_parallel'] else 'NO'}")


# --------------------------------------------------------------- autotune

def bench_autotune_ab():
    """Tuning-table A/B (tracked): derived rows only — no timing — so the
    tracked file is deterministic across CI hosts.  For every schedule row
    in the active table, resolve ``schedule="auto"`` through the consumer
    chain (table hit → calibrated coeffs → roofline) and record whether it
    returns the measured winner; then replay the calibration fit stored in
    the table (per-regime roofline pick vs calibrated pick vs measured
    best, Spearman of each cost model against wall time)."""
    from repro.core.schedule import choose_schedule, plan_capable
    from repro.tune.calibrate import mask_for_kind
    from repro.tune.table import active_table
    tab = active_table()
    if tab is None:
        row("autotune/table", 0, "none active (run tools/autotune.py sweep)")
        return
    row("autotune/table", 0, os.path.basename(tab.path or "<in-memory>"))
    n_match = n_rows = 0
    for r in tab.schedule_rows():
        seq, P = r["seq"], r["P"]
        m = mask_for_kind(r["mask_kind"], T=seq, window=r.get("window"))
        Hq = r.get("Hq", 8)
        Hkv = r.get("Hkv") or Hq
        pick = choose_schedule(m, P, Tl=seq // P, B=r.get("B", 1),
                               Hq=Hq, Hkv=Hkv, Dqk=r.get("Dqk", 64),
                               bpe=r.get("bpe", 4),
                               dynamic_seg=bool(r.get("dynamic_seg")),
                               include_bwd=False)
        # auto's candidate set excludes zigzag (needs the caller's layout
        # permutation) — judge the pick against the fastest *capable*
        # schedule, and report the global winner alongside
        names = [n for n in ("balanced", "ring") if plan_capable(n, m)]
        if Hq % P == 0 and Hkv % P == 0:
            names.append("ulysses")
        best_cap = tab.best_schedule(mask_kind=r["mask_kind"], P=P, seq=seq,
                                     candidates=names)
        ok = pick == best_cap
        n_rows += 1
        n_match += ok
        row(f"autotune/auto_{r['mask_kind']}_P{P}_seq{seq}", 0,
            f"auto={pick} best_capable={best_cap} global_best={r['best']} "
            f"match={'yes' if ok else 'NO'}")
    row("autotune/auto_match", 0, f"{n_match}/{n_rows}")
    fit = tab.data.get("calibration", {}).get("fit")
    if not fit:
        row("autotune/calibration", 0, "absent")
        return
    for reg in fit.get("regimes", []):
        row(f"autotune/costmodel_{reg['mask_kind']}_P{reg['P']}"
            f"_seq{reg['seq']}", 0,
            f"measured_best={reg['measured_best']} "
            f"calibrated_pick={reg['calibrated_pick']} "
            f"roofline_pick={reg['roofline_pick']}")
    row("autotune/spearman_calibrated", 0, f"{fit['spearman']:.4f}")
    row("autotune/spearman_roofline", 0,
        f"{fit['spearman_roofline']:.4f}")
    row("autotune/best_match_calibrated", 0, fit["best_match"])
    row("autotune/best_match_roofline", 0, fit["best_match_roofline"])
    from benchmarks.kernel_bench import tuned_tile_rows
    tiles = tuned_tile_rows()
    for t in tiles["rows"]:
        row(f"autotune/tiles_{t['backend']}_{t['mask_kind']}_seq{t['seq']}"
            f"_{t['op']}", 0,
            f"resolved={t['resolved'][0]}x{t['resolved'][1]} "
            f"measured_best={t['measured_best'][0]}x{t['measured_best'][1]} "
            f"match={'yes' if t['match'] else 'NO'}")
    if tiles["rows"]:
        row("autotune/tiles_all_match", 0,
            "yes" if tiles["all_match"] else "NO")


# ------------------------------------------------------------- appendix D

def bench_appendixD_comm_volume():
    """Analytic communication volume (paper App. D): DISTFLASHATTN 3Nd vs
    Megatron-LM 14Nd (with remat recompute)."""
    d = 4096
    row("appD/distflashattn_comm_per_token", 0, f"{3 * d * 2}B (3Nd bf16)")
    row("appD/megatron_remat_comm_per_token", 0, f"{14 * d * 2}B (14Nd)")
    row("appD/reduction", 0, f"{14 / 3:.2f}x")


# ---------------------------------------------------------------- table 2

def bench_table2_max_seqlen():
    """Max per-device sequence model (paper Table 2): LLaMA-nH ladder on
    16×A100-40G. Sequence parallelism scales to all 16 devices regardless
    of head count; Megatron TP is capped at `heads` (+ DP which does not
    reduce per-sequence memory)."""
    HBM = 40e9
    devs = 16
    for name, d, L, heads in [("16H", 2048, 64, 16), ("8H", 2048, 64, 8),
                              ("4H", 2048, 64, 4), ("2H", 2048, 64, 2)]:
        act_per_tok_layer = 2 * 2 * d + 4        # saved (x, o, lse) bf16
        peak_layer = 18 * d * 2                   # live working set, 1 layer
        per_tok = act_per_tok_layer * L + peak_layer * 4
        ours = devs * (HBM * 0.6) / per_tok
        tp = min(heads, devs)
        meg = tp * (HBM * 0.6) / per_tok
        row(f"table2/ours_max_seq_{name}", 0, f"{int(ours // 1024)}K")
        row(f"table2/megatron_tp_dp_max_seq_{name}", 0,
            f"{int(meg // 1024)}K")
        row(f"table2/ratio_{name}", 0, f"{ours / meg:.1f}x")


# --------------------------------------------------------------- roofline

def bench_roofline_table():
    """§Roofline: dump the dry-run table (if results/dryrun exists)."""
    files = sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "..", "results", "dryrun",
        "pod1_*.json")))
    for f in files:
        d = json.load(open(f))
        r = d.get("roofline", {})
        adj = d.get("adjusted", {})
        ur = adj.get("useful_flops_ratio")
        row(f"roofline/{d['arch']}/{d['shape']}", 0,
            f"bound={r.get('bound')} "
            f"step_lb={r.get('step_s_lower_bound', 0):.4f}s "
            f"C={r.get('compute_s', 0):.4f} M={r.get('memory_s', 0):.4f} "
            f"K={r.get('collective_s', 0):.4f} "
            f"useful={ur:.3f}" if ur else "pending")


def _subproc(code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1800)
    if r.returncode != 0:
        print(r.stderr[-2000:], file=sys.stderr)
    return r.stdout


BENCHES = {
    "fig4": bench_fig4_load_balance,
    "table5": bench_table5_checkpointing,
    "table3": bench_table3_rsa,
    "table4": bench_table4_ulysses,
    "table2": bench_table2_max_seqlen,
    "appD": bench_appendixD_comm_volume,
    "plans": bench_schedules_plans,
    "plans2d": bench_schedules_plans2d,
    "schedules": bench_schedules_wall,
    "autotune": bench_autotune_ab,
    "roofline": bench_roofline_table,
}

# the subset tracked in BENCH_schedules.json (CI smoke + in-repo history):
# deterministic derived rows + static plan/step-count/cost rows + the
# schedule-level wall rows + the tuning-table A/B resolution rows
TRACKED = ("fig4", "appD", "table2", "plans", "plans2d", "schedules",
           "autotune")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names, or 'tracked' for "
                         "the BENCH_schedules.json subset")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows to a machine-readable JSON file")
    args = ap.parse_args()
    if args.only == "tracked":
        names = list(TRACKED)
    else:
        names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    if args.json:
        rows = [dict(name=n, us_per_call=us, derived=d)
                for n, us, d in ROWS]
        with open(args.json, "w") as f:
            json.dump(dict(version=1, generated_by="benchmarks/run.py",
                           benches=names, rows=rows), f, indent=1)
            f.write("\n")
        print(f"wrote {os.path.abspath(args.json)}", file=sys.stderr)


if __name__ == "__main__":
    main()
