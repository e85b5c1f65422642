"""Serving driver: continuous-batching paged-KV serving of synthetic
prompts (default), or the legacy fixed-slot dense-cache engine
(``--fixed-slot``) for A/B comparison.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --prompt-len 128 --gen 16 --batch 4 [--window 64] \
        [--block-size 16 --n-blocks 128] [--fixed-slot] \
        [--spec-depth 4 [--self-spec | --draft-config smollm-360m]]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.config import ShapeSpec, get_config, smoke_config
import dataclasses
from repro.data.pipeline import SyntheticTokens
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.transformer import Runtime, build_model
from repro.parallel.sharding import make_parallel_config
from repro.serve.engine import Engine, FixedSlotEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default="local")
    ap.add_argument("--seq-shards", type=int, default=1)
    ap.add_argument("--fixed-slot", action="store_true",
                    help="legacy dense-cache engine instead of paged")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged block size (0 = the cache default, 16)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="paged pool size (0 = sized to the workload)")
    ap.add_argument("--spec-depth", type=int, default=0,
                    help="speculative draft depth (0 = vanilla decode)")
    ap.add_argument("--self-spec", action="store_true",
                    help="n-gram prompt-lookup self-speculation (no draft "
                         "model)")
    ap.add_argument("--draft-config", default=None,
                    help="draft arch id for model-based speculation "
                         "(default: configs/spec_pairs.py pairing)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.window:
        cfg = cfg.replace(attn=dataclasses.replace(cfg.attn,
                                                   window=args.window))
    mesh = make_local_mesh(seq=args.seq_shards) if args.mesh == "local" \
        else make_production_mesh(multi_pod="multipod" in args.mesh)
    shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticTokens(cfg, shape, par, mesh).batch(0)

    if not args.block_size:
        from repro.serve.cache import PagedKVCache
        args.block_size = PagedKVCache.default_block_size(
            cfg.attn, mesh, par.seq_axis)

    if args.fixed_slot:
        eng = FixedSlotEngine(model, params)
        t0 = time.time()
        toks, _ = eng.generate(batch, args.gen, rng=jax.random.PRNGKey(1),
                               temperature=args.temperature)
        dt = time.time() - t0
        tag = "fixed-slot"
    else:
        spec = draft = None
        if args.spec_depth > 0:
            from repro.serve.speculative import ModelDraft, SpecConfig
            if args.self_spec:
                spec = SpecConfig(depth=args.spec_depth, mode="ngram")
            else:
                from repro.configs.spec_pairs import draft_arch_for
                d_arch = args.draft_config or draft_arch_for(cfg.name)
                if d_arch is None:
                    raise SystemExit(
                        f"no draft pairing for {cfg.name!r}; pass "
                        f"--draft-config or --self-spec")
                d_cfg = get_config(d_arch)
                if args.smoke:
                    d_cfg = smoke_config(d_cfg)
                d_model = build_model(d_cfg, Runtime(mesh=mesh, par=par))
                d_params = d_model.init(jax.random.PRNGKey(7))
                spec = SpecConfig(depth=args.spec_depth, mode="model",
                                  draft_arch=d_cfg.name)
                draft = ModelDraft(d_model, d_params,
                                   block_size=args.block_size,
                                   max_batch=args.batch)
        blocks_per_req = -(-(args.prompt_len + args.gen
                             + args.spec_depth) // args.block_size)
        n_blocks = args.n_blocks or args.batch * blocks_per_req + 2
        eng = Engine(model, params, max_batch=args.batch,
                     block_size=args.block_size, n_blocks=n_blocks,
                     spec=spec, draft=draft)
        t0 = time.time()
        toks = eng.generate(batch, args.gen, rng=jax.random.PRNGKey(1),
                            temperature=args.temperature)
        dt = time.time() - t0
        tag = (f"paged bs={args.block_size} pool={n_blocks} "
               f"steps={eng.stats()['steps']} "
               f"preempt={eng.stats()['n_preemptions']}")
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"generated={args.gen} tokens in {dt:.2f}s "
          f"({args.gen * args.batch / dt:.1f} tok/s) [{tag}]")
    if not args.fixed_slot:
        s = eng.stats()
        print("robustness: "
              f"shed={s['shed']} retried={s['retried']} "
              f"quarantined={s['quarantined']} expired={s['expired']} "
              f"failed={s['failed']} watchdog_trips={s['watchdog_trips']} "
              f"audit_passes={s['audit_passes']}")
        if args.spec_depth > 0:
            mode = "ngram" if args.self_spec else "model"
            print("speculative: "
                  f"mode={mode} depth={args.spec_depth} "
                  f"proposed={s['spec_proposed']} "
                  f"accepted={s['spec_accepted']} "
                  f"rejected={s['spec_rejected']} "
                  f"rollbacks={s['spec_rollbacks']} "
                  f"acceptance={s['spec_acceptance']:.2f}")
    print("sampled token ids (first request):",
          [int(t) for t in toks[0][:16]])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
