"""Tracked serving benchmark: the paged continuous-batching engine under a
seeded synthetic arrival trace.

Requests arrive by a deterministic pseudo-Poisson process (seeded numpy
RNG) with mixed prompt lengths, generation budgets, and temperatures; the
engine is stepped until drained while per-token wall times are recorded.

Reported (CSV rows like benchmarks/run.py, JSON via ``--json``):

  * serving/tokens_per_s           — end-to-end decode throughput
  * serving/p50|p99_token_ms       — per-token latency percentiles
    (token wall-time = its engine-step duration; TTFT separately)
  * serving/ttft_p50_ms            — median time-to-first-token
  * serving/steps, preemptions, occupancy — scheduler behavior
  * serving/pred_*                 — analytic paged-decode roofline terms
    (analysis/roofline.paged_decode_terms) at the trace's mean context
  * serving/shared_prefix_*        — the shared-system-prompt A/B: the
    same staggered trace of requests sharing one long prefix, run with
    the prefix cache off (cold) and on (cached) — cache-hit rate, median
    TTFT (steps and ms), peak pool blocks in use, and tokens/s for both
    regimes, plus the analytic cold/warm TTFT lower bounds
    (analysis/roofline.prefix_cache_terms)
  * serving/chaos_*                — the degraded-mode A/B: the same
    arrival trace with a seeded fault storm off (calm) and on (storm),
    through an engine with admission control + always-on auditing —
    tokens/s, shed rate, quarantine count, p99 TTFT, and the storm's
    throughput retention
  * serving/spec_*                 — the speculative-decoding A/B: the
    same arrival trace with speculation off and on (ModelDraft sharing
    the target's params — the acceptance ceiling regime) — tokens/step,
    acceptance rate, tokens/s, p99 TTFT, plus the analytic
    expected-tokens/step and speedup bounds
    (analysis/roofline.speculative_terms); byte-identical streams across
    the two regimes are asserted, not assumed

Results are written to ``BENCH_serving.json`` (repo root by default) so
the serving-perf trajectory is tracked in-repo; CI runs
``python -m benchmarks.serving_bench --smoke`` and uploads the file.

    PYTHONPATH=src python -m benchmarks.serving_bench [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import numpy as np

from repro.launch.mesh import make_mesh

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_serving.json")

ROWS = []


def row(name, us, derived=""):
    ROWS.append(dict(name=name, us_per_call=us, derived=derived))
    print(f"{name},{us},{derived}", flush=True)


def _trace(rng, n_requests, prompt_lens, budgets, mean_gap):
    """Seeded arrival trace: (arrive_step, prompt_len, n_new, temperature)."""
    t = 0
    out = []
    for i in range(n_requests):
        t += int(rng.poisson(mean_gap))
        out.append((t, int(rng.choice(prompt_lens)),
                    int(rng.choice(budgets)),
                    float(rng.choice([0.0, 0.0, 0.8]))))
    return out


def run_trace(*, arch="smollm-360m", n_requests=8, max_batch=4,
              block_size=8, n_blocks=17, prompt_lens=(16, 24, 32),
              budgets=(6, 10, 14), mean_gap=1, seed=0):
    # 16 usable blocks against bursty arrivals and long budgets: the
    # tracked trace exercises queueing AND pool-pressure preemption
    from repro.analysis import roofline as R
    from repro.core.config import ShapeSpec, get_config, smoke_config
    from repro.data.pipeline import SyntheticTokens
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    from repro.serve.engine import Engine

    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("bench", max(prompt_lens), max(4, n_requests),
                      "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])

    rng = np.random.default_rng(seed)
    trace = _trace(rng, n_requests, prompt_lens, budgets, mean_gap)
    eng = Engine(model, params, max_batch=max_batch, block_size=block_size,
                 n_blocks=n_blocks)

    # warmup outside timing: every chunk shape the trace can reach
    # (prompts AND preemption re-prefills, which land at arbitrary context
    # lengths) plus the jitted decode step — so the tracked latencies
    # measure serving, not XLA compilation
    max_ctx = max(prompt_lens) + max(budgets)
    eng.warm_prefill(max_ctx)
    w = eng.submit(prompts[0][:prompt_lens[0]], max_new_tokens=2)
    eng.run()
    del eng.requests[w]
    warm_steps = eng.sched.step_count
    warm_preempt = eng.sched.n_preemptions

    submit_t, first_t = {}, {}
    token_ms = []
    occupancy = []
    pending = sorted(trace, key=lambda x: x[0])
    step = 0
    i = 0
    rids = []
    t_start = time.perf_counter()
    while pending[len(rids):] or not eng.sched.idle:
        while len(rids) < len(pending) and pending[len(rids)][0] <= step:
            _, plen, n_new, temp = pending[len(rids)]
            r = eng.submit(prompts[i % len(prompts)][:plen],
                           max_new_tokens=n_new, temperature=temp, seed=i)
            submit_t[r] = time.perf_counter()
            rids.append(r)
            i += 1
        t0 = time.perf_counter()
        events = eng.step()
        dt_ms = (time.perf_counter() - t0) * 1e3
        n_tok = sum(len(v) for v in events.values())
        occupancy.append(len(eng.sched.running))
        for r, toks in events.items():
            if r not in first_t and toks:
                first_t[r] = time.perf_counter()
            token_ms.extend([dt_ms / max(n_tok, 1)] * len(toks))
        step += 1
        if step > 100_000:
            raise RuntimeError("trace did not drain")
    wall = time.perf_counter() - t_start

    total_tokens = sum(len(eng.requests[r].emitted) for r in rids)
    ttft = sorted((first_t[r] - submit_t[r]) * 1e3
                  for r in rids if r in first_t)
    mean_ctx = int(np.mean([len(eng.requests[r].prompt)
                            + len(eng.requests[r].emitted) for r in rids]))
    stats = eng.stats()
    return {
        "arch": cfg.name,
        "n_requests": n_requests,
        "total_tokens": total_tokens,
        "wall_s": wall,
        "tokens_per_s": total_tokens / wall,
        "p50_token_ms": statistics.median(token_ms),
        "p99_token_ms": (sorted(token_ms)[max(0, int(0.99 * len(token_ms))
                                              - 1)]),
        "ttft_p50_ms": ttft[len(ttft) // 2],
        "steps": stats["steps"] - warm_steps,          # trace only, not warmup
        "preemptions": stats["n_preemptions"] - warm_preempt,
        "mean_occupancy": float(np.mean(occupancy)),
        "mean_context": mean_ctx,
        "pred": R.paged_decode_terms(cfg, batch=max_batch,
                                     mean_len=mean_ctx,
                                     block_size=block_size, bpe=4),
    }


def run_shared_prefix(*, arch="smollm-360m", n_requests=6, prefix_len=48,
                      tail_len=7, budget=4, gap=4, max_batch=4,
                      block_size=8, n_blocks=96, chunk_tokens=8, seed=0):
    """Shared-system-prompt A/B: ``n_requests`` staggered requests share
    one ``prefix_len``-token prefix (distinct short tails).  The same
    trace runs twice — prefix cache off (every request re-prefills and
    re-stores the prefix) and on (later arrivals share the first
    request's blocks) — measuring cache-hit rate, TTFT, peak blocks in
    use, and throughput."""
    from repro.analysis import roofline as R
    from repro.core.config import ShapeSpec, get_config, smoke_config
    from repro.data.pipeline import SyntheticTokens
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    from repro.serve.engine import Engine

    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("bench", prefix_len + tail_len, 4, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    rows = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
    system = rows[0][:prefix_len]
    reqs = [np.concatenate([system, rows[1 + i % 3][:tail_len]])
            for i in range(n_requests)]

    def drive(prefix_cache):
        eng = Engine(model, params, max_batch=max_batch,
                     block_size=block_size, n_blocks=n_blocks,
                     prefill_chunk_tokens=chunk_tokens,
                     prefix_cache=prefix_cache)
        eng.warm_prefill(prefix_len + tail_len + budget)
        # compile the decode step too (a 2-token prompt registers no full
        # block, so the cached run's stats stay clean), then zero the
        # counters so hit-rate reflects the measured trace only
        w = eng.submit(rows[1][:3], max_new_tokens=2)
        eng.run()
        del eng.requests[w]
        for k in eng.cache.counters:
            eng.cache.counters[k] = 0
        submit_t, submit_step, first_t, first_step = {}, {}, {}, {}
        peak_blocks = 0
        t_start = time.perf_counter()
        step = 0
        rids = []
        while len(rids) < len(reqs) or not eng.sched.idle:
            if len(rids) < len(reqs) and step >= gap * len(rids):
                r = eng.submit(reqs[len(rids)], max_new_tokens=budget)
                submit_t[r], submit_step[r] = time.perf_counter(), step
                rids.append(r)
            events = eng.step()
            for r, toks in events.items():
                if r not in first_t and toks:
                    first_t[r] = time.perf_counter()
                    first_step[r] = step
            peak_blocks = max(peak_blocks, eng.cache.allocator.n_usable
                              - eng.cache.allocator.n_free)
            step += 1
            if step > 100_000:
                raise RuntimeError("shared-prefix trace did not drain")
        wall = time.perf_counter() - t_start
        total = sum(len(eng.requests[r].emitted) for r in rids)
        ttft_ms = sorted((first_t[r] - submit_t[r]) * 1e3 for r in rids)
        ttft_steps = sorted(first_step[r] - submit_step[r] for r in rids)
        n_prefill = sum(len(q) - 1 for q in reqs)
        return {
            "ttft_p50_ms": ttft_ms[len(ttft_ms) // 2],
            "ttft_p50_steps": ttft_steps[len(ttft_steps) // 2],
            "peak_blocks": peak_blocks,
            "tokens_per_s": total / wall,
            "hit_rate": eng.stats()["hit_tokens"] / n_prefill,
            "forks": eng.stats()["forks"],
            "dedup_swaps": eng.stats()["dedup_swaps"],
            "stored_prefix_copies": (eng.stats()["cache_blocks"]
                                     if prefix_cache else None),
        }

    cold = drive(False)
    cached = drive(True)
    hit = cached["hit_rate"]
    return {
        "n_requests": n_requests, "prefix_len": prefix_len,
        "tail_len": tail_len, "chunk_tokens": chunk_tokens,
        "cold": cold, "cached": cached,
        "cache_hit_rate": hit,
        "ttft_reduction": 1 - cached["ttft_p50_ms"] / cold["ttft_p50_ms"],
        "peak_blocks_reduction": 1 - cached["peak_blocks"]
                                     / cold["peak_blocks"],
        "pred": R.prefix_cache_terms(cfg, prompt_len=prefix_len + tail_len,
                                     hit_rate=hit,
                                     chunk_tokens=chunk_tokens, bpe=4),
    }


def run_chaos(*, arch="smollm-360m", n_requests=8, max_batch=4,
              block_size=8, n_blocks=24, prompt_lens=(16, 24),
              budgets=(4, 6), mean_gap=1, chaos_seed=1234,
              storm_steps=24, storm_rate=0.5, seed=0):
    """Degraded-mode A/B: the same seeded arrival trace driven twice —
    fault storm off, then on (``FaultInjector.seeded(chaos_seed)``) —
    through an engine with admission control + auditing enabled.
    Reports per-regime tokens/s, shed rate, quarantine count, and p99
    TTFT: the cost of surviving the storm."""
    from repro.core.config import ShapeSpec, get_config, smoke_config
    from repro.data.pipeline import SyntheticTokens
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    from repro.serve.engine import Engine
    from repro.serve.faults import FaultInjector

    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("bench", max(prompt_lens), max(4, n_requests),
                      "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
    trace = _trace(np.random.default_rng(seed), n_requests, prompt_lens,
                   budgets, mean_gap)

    def drive(faulty):
        eng = Engine(model, params, max_batch=max_batch,
                     block_size=block_size, n_blocks=n_blocks,
                     prefill_chunk_tokens=8, max_queue=2 * max_batch,
                     max_retries=6, audit=True)
        eng.warm_prefill(max(prompt_lens) + max(budgets))
        w = eng.submit(prompts[0][:prompt_lens[0]], max_new_tokens=2)
        eng.run()
        del eng.requests[w]
        if faulty:
            # timeline starts after warmup: the storm hits the trace
            eng.install_faults(FaultInjector.seeded(
                chaos_seed, n_steps=storm_steps, rate=storm_rate))
        submit_t, first_t = {}, {}
        pending = sorted(trace, key=lambda x: x[0])
        rids = []
        step, i = 0, 0
        t_start = time.perf_counter()
        while len(rids) < len(pending) or not eng.sched.idle:
            while len(rids) < len(pending) and pending[len(rids)][0] <= step:
                _, plen, n_new, temp = pending[len(rids)]
                r = eng.submit(prompts[i % len(prompts)][:plen],
                               max_new_tokens=n_new, temperature=temp,
                               seed=i)
                submit_t[r] = time.perf_counter()
                rids.append(r)
                i += 1
            for r, toks in eng.step().items():
                if r not in first_t and toks:
                    first_t[r] = time.perf_counter()
            step += 1
            if step > 100_000:
                raise RuntimeError("chaos trace did not drain")
        wall = time.perf_counter() - t_start
        eng.release_faults()
        eng.cache.allocator.check_conservation()   # survives the storm
        s = eng.stats()
        total = sum(len(eng.requests[r].emitted) for r in rids)
        ttft = sorted((first_t[r] - submit_t[r]) * 1e3
                      for r in rids if r in first_t)
        return {
            "tokens_per_s": total / wall,
            "total_tokens": total,
            "shed": s["shed"],
            "shed_rate": s["shed"] / n_requests,
            "quarantined": s["quarantined"],
            "expired": s["expired"],
            "failed": s["failed"],
            "retried": s["retried"],
            "watchdog_trips": s["watchdog_trips"],
            "preemptions": s["n_preemptions"],
            "ttft_p99_ms": (ttft[max(0, int(0.99 * len(ttft)) - 1)]
                            if ttft else None),
            "terminal_states": {
                st: sum(1 for r in rids if eng.requests[r].state == st)
                for st in ("finished", "rejected", "expired", "failed")},
            "faults_applied": dict(eng.injector.counts) if faulty else None,
        }

    calm = drive(False)
    storm = drive(True)
    return {"chaos_seed": chaos_seed, "storm_steps": storm_steps,
            "storm_rate": storm_rate, "n_requests": n_requests,
            "calm": calm, "storm": storm,
            "throughput_retention": (storm["tokens_per_s"]
                                     / calm["tokens_per_s"])}


def run_speculative(*, arch="smollm-360m", n_requests=6, max_batch=4,
                    block_size=8, n_blocks=48, prompt_lens=(16, 24),
                    budgets=(6, 8), mean_gap=1, depth=4, seed=0):
    """Speculative-decoding A/B: the same seeded arrival trace driven
    twice — spec off (vanilla one-token decode) and spec on (a
    ``ModelDraft`` sharing the target's params: the acceptance ceiling
    regime, limited only by draft-side chunked-prefill numerics) —
    measuring tokens/step, acceptance rate, tokens/s, and p99 TTFT.  The
    determinism contract is asserted, not assumed: both regimes must emit
    byte-identical streams."""
    from repro.analysis import roofline as R
    from repro.core.config import ShapeSpec, get_config, smoke_config
    from repro.data.pipeline import SyntheticTokens
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    from repro.serve.engine import Engine
    from repro.serve.speculative import ModelDraft, SpecConfig

    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("bench", max(prompt_lens), max(4, n_requests),
                      "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(
        SyntheticTokens(cfg, shape, par, mesh).batch(0)["tokens"])
    trace = _trace(np.random.default_rng(seed), n_requests, prompt_lens,
                   budgets, mean_gap)

    def drive(spec_on):
        spec = draft = None
        if spec_on:
            spec = SpecConfig(depth=depth, mode="model",
                              draft_arch=cfg.name)
            draft = ModelDraft(model, params, block_size=block_size,
                               n_blocks=64, max_batch=max_batch)
        eng = Engine(model, params, max_batch=max_batch,
                     block_size=block_size, n_blocks=n_blocks,
                     spec=spec, draft=draft)
        eng.warm_prefill(max(prompt_lens) + max(budgets))
        w = eng.submit(prompts[0][:prompt_lens[0]], max_new_tokens=2)
        eng.run()
        del eng.requests[w]
        if draft is not None:
            draft.release(w)
        warm_steps = eng.sched.step_count
        warm_counters = dict(eng.counters)
        submit_t, first_t = {}, {}
        pending = sorted(trace, key=lambda x: x[0])
        rids = []
        step, i = 0, 0
        t_start = time.perf_counter()
        while len(rids) < len(pending) or not eng.sched.idle:
            while len(rids) < len(pending) and pending[len(rids)][0] <= step:
                _, plen, n_new, temp = pending[len(rids)]
                r = eng.submit(prompts[i % len(prompts)][:plen],
                               max_new_tokens=n_new, temperature=temp,
                               seed=i)
                submit_t[r] = time.perf_counter()
                rids.append(r)
                i += 1
            for r, toks in eng.step().items():
                if r not in first_t and toks:
                    first_t[r] = time.perf_counter()
            step += 1
            if step > 100_000:
                raise RuntimeError("speculative trace did not drain")
        wall = time.perf_counter() - t_start
        s = eng.stats()
        total = sum(len(eng.requests[r].emitted) for r in rids)
        steps = s["steps"] - warm_steps
        ttft = sorted((first_t[r] - submit_t[r]) * 1e3
                      for r in rids if r in first_t)
        proposed = s["spec_proposed"] - warm_counters.get("spec_proposed", 0)
        accepted = s["spec_accepted"] - warm_counters.get("spec_accepted", 0)
        return {
            "tokens_per_s": total / wall,
            "total_tokens": total,
            "steps": steps,
            "tokens_per_step": total / max(steps, 1),
            "ttft_p99_ms": ttft[max(0, int(0.99 * len(ttft)) - 1)],
            "spec_proposed": proposed,
            "spec_accepted": accepted,
            "acceptance": accepted / max(proposed, 1),
            "rollbacks": s["spec_rollbacks"]
            - warm_counters.get("spec_rollbacks", 0),
        }, {r: [int(t) for t in eng.requests[r].emitted] for r in rids}

    off, streams_off = drive(False)
    on, streams_on = drive(True)
    identical = list(streams_off.values()) == list(streams_on.values())
    if not identical:
        raise AssertionError("speculative streams diverged from vanilla")
    mean_ctx = int(np.mean([p + b for _, p, b, _ in trace]))
    return {
        "depth": depth, "n_requests": n_requests,
        "draft": "target-params (ceiling regime)",
        "off": off, "on": on,
        "streams_identical": identical,
        "tokens_per_step_gain": on["tokens_per_step"]
        / max(off["tokens_per_step"], 1e-12),
        "pred": R.speculative_terms(cfg, batch=max_batch,
                                    mean_len=mean_ctx, depth=depth,
                                    acceptance=on["acceptance"],
                                    block_size=block_size, bpe=4),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (fewer, shorter requests)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)

    kw, spkw, chkw, spec_kw = {}, {}, {}, {}
    if args.smoke:
        kw = dict(n_requests=5, prompt_lens=(16, 24), budgets=(3, 4),
                  n_blocks=24)   # small pool: exercises queueing on CI
        spkw = dict(n_requests=4, prefix_len=32, n_blocks=64)
        chkw = dict(n_requests=5, budgets=(3, 4), storm_steps=16)
        spec_kw = dict(n_requests=4, budgets=(4, 6), depth=3)
    res = run_trace(**kw)
    sp = run_shared_prefix(**spkw)
    res["shared_prefix"] = sp
    ch = run_chaos(**chkw)
    res["chaos"] = ch
    spc = run_speculative(**spec_kw)
    res["speculative"] = spc

    row("serving/tokens_per_s", 0, f"{res['tokens_per_s']:.2f}")
    row("serving/p50_token_ms", f"{res['p50_token_ms'] * 1e3:.0f}",
        f"{res['p50_token_ms']:.1f}ms")
    row("serving/p99_token_ms", f"{res['p99_token_ms'] * 1e3:.0f}",
        f"{res['p99_token_ms']:.1f}ms")
    row("serving/ttft_p50_ms", f"{res['ttft_p50_ms'] * 1e3:.0f}",
        f"{res['ttft_p50_ms']:.1f}ms")
    row("serving/trace", 0,
        f"requests={res['n_requests']} tokens={res['total_tokens']} "
        f"steps={res['steps']} preemptions={res['preemptions']} "
        f"occupancy={res['mean_occupancy']:.2f}")
    p = res["pred"]
    row("serving/pred_roofline", 0,
        f"bound={p['bound']} tok_s_bound={p['tok_s_bound']:.0f} "
        f"block_waste={p['block_waste']:.2f} "
        f"step_lb={p['step_s_lower_bound']:.2e}s "
        f"(mean_ctx={res['mean_context']})")
    row("serving/shared_prefix_hit_rate", 0,
        f"{sp['cache_hit_rate']:.2f} (forks={sp['cached']['forks']} "
        f"dedup_swaps={sp['cached']['dedup_swaps']})")
    row("serving/shared_prefix_ttft_ms",
        f"{sp['cached']['ttft_p50_ms'] * 1e3:.0f}",
        f"cached={sp['cached']['ttft_p50_ms']:.1f}ms "
        f"cold={sp['cold']['ttft_p50_ms']:.1f}ms "
        f"(-{sp['ttft_reduction'] * 100:.0f}%; steps "
        f"{sp['cached']['ttft_p50_steps']} vs {sp['cold']['ttft_p50_steps']})")
    row("serving/shared_prefix_peak_blocks", 0,
        f"cached={sp['cached']['peak_blocks']} "
        f"cold={sp['cold']['peak_blocks']} "
        f"(-{sp['peak_blocks_reduction'] * 100:.0f}%)")
    sps = sp["pred"]
    row("serving/shared_prefix_pred", 0,
        f"prefill_flops_saved={sps['prefill_flops_saved_frac']:.2f} "
        f"ttft_lb_cold={sps['ttft_s_lower_bound_cold']:.2e}s "
        f"ttft_lb_cached={sps['ttft_s_lower_bound_cached']:.2e}s")
    for regime in ("calm", "storm"):
        c = ch[regime]
        ttft = (f"{c['ttft_p99_ms']:.1f}ms" if c["ttft_p99_ms"] is not None
                else "n/a")
        row(f"serving/chaos_{regime}", 0,
            f"tok_s={c['tokens_per_s']:.2f} shed_rate={c['shed_rate']:.2f} "
            f"quarantined={c['quarantined']} expired={c['expired']} "
            f"retried={c['retried']} watchdog_trips={c['watchdog_trips']} "
            f"p99_ttft={ttft}")
    row("serving/chaos_retention", 0,
        f"{ch['throughput_retention']:.2f} of calm tokens/s under a "
        f"rate={ch['storm_rate']} seed={ch['chaos_seed']} fault storm")
    for regime in ("off", "on"):
        c = spc[regime]
        row(f"serving/spec_{regime}", 0,
            f"tok_s={c['tokens_per_s']:.2f} "
            f"tok_step={c['tokens_per_step']:.2f} "
            f"p99_ttft={c['ttft_p99_ms']:.1f}ms"
            + (f" acceptance={c['acceptance']:.2f} "
               f"proposed={c['spec_proposed']} "
               f"rollbacks={c['rollbacks']}" if regime == "on" else ""))
    sppred = spc["pred"]
    row("serving/spec_ab", 0,
        f"depth={spc['depth']} tok_step_gain="
        f"{spc['tokens_per_step_gain']:.2f} "
        f"streams_identical={spc['streams_identical']} "
        f"pred_E_tok_step={sppred['expected_tokens_per_step']:.2f} "
        f"pred_speedup_bound={sppred['speedup_bound']:.2f}")

    out = dict(version=1, generated_by="benchmarks/serving_bench.py",
               smoke=bool(args.smoke), result=res, rows=ROWS)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(args.out)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
