"""Training cells: the program's jitted train step (``Trainer.step``) over
the seed's weights and token rows.

Set-up builds the one ``Trainer`` (model, sharded weights from the seed,
AdamW state, the jitted step) and drives it through its first steps on
rows that all differ; those steps compile and warm the step, and their
losses, first gradient and weight change are what the reference checks.
The same object then runs the window: one step after another, each
waited for as the program's own loop waits (``launch/train.py``), until
``--seconds`` have passed. Afterwards the program's state is freed and the
reference follows the same first steps.
"""
from __future__ import annotations

import time

import numpy as np

import checks
import devtrace as TR
import flops
import harness as H
import traffic
import weights

N_CHECK = 3          # steps the reference follows
N_POOL = 8           # further distinct batches the window cycles through


def build(ctx: H.Ctx):
    """The Trainer of this cell with the seed's weights, its parameter and
    batch shardings, and the token rows of the checked steps and the
    window."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.config import ShapeSpec, TrainConfig
    from repro.data.pipeline import input_specs
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer
    from repro.models.transformer import Runtime, build_model
    from repro.optim import adamw
    from repro.parallel.sharding import make_parallel_config, param_shardings
    from repro.train.step import jit_train_step

    c, t, o = ctx.config, ctx.workload["traffic"], ctx.workload["optimizer"]
    cfg = H.program_config(c)
    S = t["seq_shards"]
    mesh = make_mesh((len(ctx.devices) // S, S), ("data", "model"),
                     devices=ctx.devices)
    shape = ShapeSpec(ctx.workload["name"], t["seq"], t["batch"], "train",
                      docs=t.get("docs", 1))
    par = make_parallel_config(mesh, shape, schedule=t["schedule"],
                               remat=t["remat"])
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    tc = TrainConfig(**o)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    weights.check_layout(abstract, c)
    p_sh = param_shardings(abstract, mesh, par)
    params = weights.make(c, H.jax_key(ctx.seed), shardings=p_sh)
    rep = NamedSharding(mesh, P())
    opt = jax.jit(adamw.init, out_shardings=adamw.AdamWState(
        step=rep, m=p_sh, v=p_sh))(params)
    _, bspec = input_specs(cfg, shape, par, mesh)
    b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspec,
                        is_leaf=lambda x: isinstance(x, P))
    tr = Trainer(cfg, mesh, model, params, opt,
                 jit_train_step(model, tc, p_sh, b_sh), None)
    rows = traffic.train_rows(t, cfg.vocab, ctx.seed, N_CHECK + N_POOL)
    return tr, p_sh, b_sh, rows


def put(rows, b_sh):
    import jax
    return [{k: jax.device_put(v, b_sh[k]) for k, v in r.items()}
            for r in rows]


def first_steps(tr, batches, c, p_sh, seed, beta1):
    """Drive the Trainer through ``len(batches)`` steps. Returns the
    program's readings: losses and pre-clip gradient norms per step, the
    per-leaf norms of the first gradient as the optimizer holds it (its
    first moment after one step, over 1 - beta1), and the per-leaf norms
    of the weights' change over all the steps."""
    losses, gnorms = [], []
    g1 = first = None
    for i, b in enumerate(batches):
        tr.params, tr.opt, m = tr.step(tr.params, tr.opt, b)
        losses.append(float(m["loss"]))
        gnorms.append(float(m["gnorm"]))
        if i == 0:
            g1 = np.asarray(weights.leaf_norms(tr.opt.m)) / (1 - beta1)
            # the first gradient itself, kept on the host for the
            # element-wise comparison once the reference has run
            first = {k: np.asarray(v, np.float32) / (1 - beta1)
                     for k, v in weights.flatten(tr.opt.m).items()}
    p0 = weights.make(c, H.jax_key(seed), shardings=p_sh)
    change = np.asarray(weights.diff_norms(tr.params, p0))
    del p0
    return checks.TrainReading(losses, gnorms, g1, change, first)


def reference_reading(ctx: H.Ctx, rows, precision="fp32"):
    """The reference's readings over the same first steps, from the seed's
    weights made again; on several chips its work is split over them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    import reference
    mesh = None
    put_on = SingleDeviceSharding(ctx.devices[0])
    if len(ctx.devices) > 1:
        mesh = jax.sharding.Mesh(np.array(ctx.devices), ("s",))
        put_on = NamedSharding(mesh, PartitionSpec())
    p = weights.make(ctx.config, H.jax_key(ctx.seed),
                     shardings=SingleDeviceSharding(ctx.devices[0]))
    bs = [{k: jax.device_put(v, put_on) for k, v in r.items()} for r in rows]
    losses, gnorms, first, p3 = reference.train_steps(
        ctx.config, ctx.workload["optimizer"], p, bs, precision, mesh)
    g1 = np.asarray(weights.leaf_norms(first))
    p0 = weights.make(ctx.config, H.jax_key(ctx.seed),
                      shardings=jax.tree.map(lambda x: x.sharding, p3))
    change = np.asarray(weights.diff_norms(p3, p0))
    del p0, p3
    return checks.TrainReading(losses, gnorms, g1, change, first)


def run(ctx: H.Ctx) -> H.Result:
    import jax
    t = ctx.workload["traffic"]
    tr, p_sh, b_sh, rows = build(ctx)
    batches = put(rows, b_sh)
    got = first_steps(tr, batches[:N_CHECK], ctx.config, p_sh, ctx.seed,
                      ctx.workload["optimizer"]["beta1"])
    pool = batches[N_CHECK:]
    tokens_per_step = t["batch"] * t["seq"]
    pool_pairs = [sum(flops.causal_pairs(
        None if "segment_ids" not in r else r["segment_ids"][b], t["seq"])
        for b in range(t["batch"])) for r in rows[N_CHECK:]]

    capture = TR.Capture() if ctx.trace else None
    ctx.compiles.open()
    if capture:
        capture.start()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    n = failed = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.batch"):
            b = pool[n % len(pool)]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            tr.params, tr.opt, m = tr.step(tr.params, tr.opt, b)
        with jax.profiler.TraceAnnotation("bench.sync"):
            failed += int(m["skipped_nonfinite"])
        n += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    t1 = time.perf_counter()
    if capture:
        capture.stop()
    ctx.compiles.close()
    peak = H.peak_memory(ctx.devices)
    ctx.counts.update(
        memory_peak_bytes=peak, steps=n, window_s=t1 - t0,
        tokens_per_step=tokens_per_step,
        attn_pairs_per_step=sum(pool_pairs[i % len(pool)]
                                for i in range(n)) / n)
    ctx.note(f"train: {n} steps in {t1 - t0:.3f} s, set-up {setup_s:.3f} s,"
             f" peak {peak} B")
    del tr, batches, pool, m
    if capture:
        ctx.trace_summary = capture.reduce(ctx.devices)

    t_ref = time.perf_counter()
    want = reference_reading(ctx, rows[:N_CHECK])
    checks.grad_diff(got, want)
    ctx.note(f"train: reference followed {N_CHECK} steps in "
             f"{time.perf_counter() - t_ref:.3f} s")
    return H.Result(
        attempted=n, failed=failed,
        metrics={"train_tok_s": n * tokens_per_step / (t1 - t0),
                 "train_peak_hbm_gib": peak / H.GIB, "setup_s": setup_s},
        checks=checks.train_checks(got, want, ctx.workload["limits"]),
        breakdown=ctx.trace_summary.breakdown() if capture else None)
