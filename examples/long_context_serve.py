"""Long-context serving example: continuous batching over a paged KV cache
(staggered arrivals, per-request lengths), full-attention vs the paper's
Appendix-F sliding-window variant, over 8 (forced host) devices — plus the
legacy fixed-slot dense-cache engine for an A/B of the same prompts, and a
shared-system-prompt pass showing the content-addressed prefix cache
(identical prefixes stored once, chunked prefill skipping cached blocks).

With ``--chaos-seed N`` the continuous-batching pass runs under a seeded
fault storm (serve/faults.py: pool squeezes, NaN logits, dropped steps,
preemption storms …) with deadlines, admission control, and always-on
invariant auditing — demonstrating that every request still reaches a
definite terminal status and fault-free streams are untouched.

    python examples/long_context_serve.py          # sets its own XLA_FLAGS
    python examples/long_context_serve.py --prefill-chunk-tokens 128
    python examples/long_context_serve.py --no-prefix-cache
    python examples/long_context_serve.py --chaos-seed 7
    python examples/long_context_serve.py --spec-depth 4 --self-spec
    python examples/long_context_serve.py --spec-depth 4 \
        --draft-config smollm-360m
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.config import ShapeSpec, get_config, smoke_config  # noqa
import dataclasses  # noqa: E402
from repro.data.pipeline import SyntheticTokens  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.transformer import Runtime, build_model  # noqa: E402
from repro.parallel.sharding import make_parallel_config  # noqa: E402
from repro.serve.engine import Engine, FixedSlotEngine  # noqa: E402


def run(window: int, *, chunk_tokens: int = 256, prefix_cache: bool = True,
        chaos_seed: int = None, spec_depth: int = 0, self_spec: bool = False,
        draft_config: str = None):
    cfg = smoke_config(get_config("qwen3-8b"))
    if window:
        cfg = cfg.replace(attn=dataclasses.replace(cfg.attn, window=window))
    mesh = make_mesh((2, 4), ("data", "model"))   # 4-way seq parallel
    shape = ShapeSpec("lc", 1024, 4, "prefill")       # 1K-token prompts
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticTokens(cfg, shape, par, mesh).batch(0)
    prompts = np.asarray(batch["tokens"])

    # --- speculative decoding: self-speculation (prompt-lookup n-grams)
    # or a paired draft model with its own paged cache.  The acceptance
    # rule keeps the emitted streams token-identical to vanilla decode —
    # the draft is purely a tokens/step knob
    spec = draft = None
    if spec_depth > 0:
        from repro.serve.speculative import ModelDraft, SpecConfig
        if self_spec or draft_config is None:
            spec = SpecConfig(depth=spec_depth, mode="ngram")
        else:
            d_cfg = smoke_config(get_config(draft_config))
            d_model = build_model(d_cfg, Runtime(mesh=mesh, par=par))
            d_params = d_model.init(jax.random.PRNGKey(7))
            spec = SpecConfig(depth=spec_depth, mode="model",
                              draft_arch=d_cfg.name)
            draft = ModelDraft(d_model, d_params, block_size=64,
                               n_blocks=96, max_batch=4)

    # --- continuous batching: requests arrive over time, with different
    # budgets, into a paged pool (mixed in-flight lengths per step).
    # Under --chaos-seed the same pass runs chaos-hardened: bounded queue,
    # deadlines, retries, quarantine, per-step invariant audit
    faults = None
    if chaos_seed is not None:
        from repro.serve.faults import FaultInjector
        faults = FaultInjector.seeded(chaos_seed, n_steps=24, rate=0.5)
    eng = Engine(model, params, max_batch=4, block_size=64, n_blocks=80,
                 prefill_chunk_tokens=chunk_tokens,
                 prefix_cache=prefix_cache,
                 max_queue=8, audit=chaos_seed is not None, faults=faults,
                 spec=spec, draft=draft)
    t0 = time.time()
    rids = []
    for i in range(prompts.shape[0]):
        rids.append(eng.submit(prompts[i], max_new_tokens=4 + 2 * i,
                               deadline_steps=200 if chaos_seed is not None
                               else None))
        eng.step()                     # staggered: admit + decode as we go
    out = eng.run()
    dt = time.time() - t0
    tag = f"window={window}" if window else "full attention"
    total = sum(len(out[r]) for r in rids)
    print(f"[{tag:>16}] paged: 4×1024-token prompts, staggered, "
          f"{total} tokens in {dt:.2f}s over {eng.stats()['steps']} steps; "
          f"req0: {[int(t) for t in out[rids[0]]]}")
    if spec is not None:
        s = eng.stats()
        print(f"[{tag:>16}] speculative({spec.mode}, depth={spec.depth}): "
              f"proposed={s['spec_proposed']} accepted={s['spec_accepted']} "
              f"rollbacks={s['spec_rollbacks']} "
              f"acceptance={s['spec_acceptance']:.2f} — emitted streams "
              f"identical to vanilla decode by construction")
    if chaos_seed is not None:
        s = eng.stats()
        states = {r: eng.requests[r].state for r in rids}
        print(f"[{tag:>16}] chaos(seed={chaos_seed}): "
              f"faults={s['faults']} terminal={states} "
              f"shed={s['shed']} retried={s['retried']} "
              f"quarantined={s['quarantined']} expired={s['expired']} "
              f"watchdog_trips={s['watchdog_trips']} "
              f"audit_passes={s['audit_passes']}")
        eng.cache.allocator.check_conservation()
        print(f"[{tag:>16}] chaos: every request terminal, allocator "
              f"conservation holds after the storm")

    # --- shared system prompt: the same 1024-token prefix, four different
    # user turns.  With the prefix cache the first request prefills the
    # prefix once; the other three *share* its blocks (chunked prefill
    # starts at the first uncached position) and the engine stores the
    # prefix exactly once
    if prefix_cache:
        system = prompts[0]
        turns = [np.concatenate([system, prompts[1][:64 * (i + 1)]])
                 for i in range(4)]
        t0 = time.time()
        rs = [eng.submit(p, max_new_tokens=4) for p in turns]
        eng.run()
        dt = time.time() - t0
        pc = eng.stats()["prefix_cache"]
        print(f"[{tag:>16}] shared system prompt: 4 turns × "
              f"{len(system)}-token prefix in {dt:.2f}s; "
              f"hit_tokens={pc['hit_tokens']} "
              f"stored_blocks={eng.stats()['cache_blocks']} "
              f"forks={eng.stats()['forks']} "
              f"dedup_swaps={eng.stats()['dedup_swaps']}")

    # --- fixed-slot dense oracle on the same prompts (uniform budget;
    # 1024 + 6 is NOT a multiple of the 4 seq shards — the padded cache
    # rounds itself up)
    t0 = time.time()
    toks, _ = FixedSlotEngine(model, params).generate(batch, n_tokens=6)
    dt = time.time() - t0
    agree = all(int(a) == int(b)
                for a, b in zip(np.asarray(toks)[0], out[rids[0]][:4]))
    print(f"[{tag:>16}] fixed-slot oracle: decode 6 tok: {dt:.2f}s; "
          f"first-request streams agree: {agree}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--prefill-chunk-tokens", type=int, default=256,
                    help="chunked-prefill budget per engine step "
                         "(0 = whole-prompt prefill)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-addressed prefix sharing")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="run the continuous-batching pass under a seeded "
                         "fault storm (deterministic; same seed, same "
                         "storm) with auditing + deadlines enabled")
    ap.add_argument("--spec-depth", type=int, default=0,
                    help="speculative draft depth (0 = vanilla decode)")
    ap.add_argument("--self-spec", action="store_true",
                    help="n-gram prompt-lookup self-speculation")
    ap.add_argument("--draft-config", default=None,
                    help="draft arch id (e.g. smollm-360m) for model-based "
                         "speculation; omit for self-speculation")
    args = ap.parse_args()
    kw = dict(chunk_tokens=args.prefill_chunk_tokens,
              prefix_cache=not args.no_prefix_cache,
              chaos_seed=args.chaos_seed, spec_depth=args.spec_depth,
              self_spec=args.self_spec, draft_config=args.draft_config)
    run(window=0, **kw)
    run(window=256, **kw)   # Appendix-F sliding window: prefill ring
    #                         truncated, paged decode masks beyond the
    #                         window per request — and the paged engine
    #                         *reclaims* blocks wholly below the window
