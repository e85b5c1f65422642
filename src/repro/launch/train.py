"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --smoke --steps 50 --seq 256 --batch 4 [--schedule balanced] \
        [--remat remat_aware] [--ckpt-dir ckpts/run0]

Uses whatever devices exist (tests/CPU: a (1,1) or (data,model) local mesh;
on real hardware pass --mesh production). The step is jit-compiled with
explicit FSDP in/out shardings and donated params/optimizer.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable, List, Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.config import (ModelConfig, ShapeSpec, TrainConfig,
                               get_config, smoke_config)
from repro.data.pipeline import SyntheticTokens, input_specs
from repro.io import checkpoint as ckpt_io
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.transformer import Runtime, build_model
from repro.optim import adamw
from repro.parallel.sharding import make_parallel_config
from repro.train.step import init_sharded, jit_train_step


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default="balanced",
                    choices=("balanced", "ring", "ulysses"))
    ap.add_argument("--remat", default="remat_aware",
                    choices=("remat_aware", "hf", "none"))
    ap.add_argument("--mesh", default="local",
                    choices=("local", "production", "production-multipod"))
    ap.add_argument("--seq-shards", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Trainer:
    """Everything one training run holds: the model, its sharded params and
    optimizer state, the jitted step and the data stream."""
    cfg: ModelConfig
    mesh: Mesh
    model: object
    params: dict
    opt: adamw.AdamWState
    step: Callable
    data: SyntheticTokens


def setup(args: argparse.Namespace, mesh: Optional[Mesh] = None) -> Trainer:
    """Build the run ``args`` describe, on ``mesh`` (default: the mesh
    ``--mesh``/``--seq-shards`` select). Params come from PRNGKey(0), so
    two meshes start from the same values."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if mesh is None:
        mesh = (make_local_mesh(seq=args.seq_shards) if args.mesh == "local"
                else make_production_mesh(
                    multi_pod=args.mesh.endswith("multipod")))
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    par = make_parallel_config(mesh, shape, schedule=args.schedule,
                               remat=args.remat)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    tc = TrainConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                     total_steps=args.steps)
    params, opt, p_sh = init_sharded(model, tc, jax.random.PRNGKey(0))
    _, batch_spec = input_specs(cfg, shape, par, mesh)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_spec,
                            is_leaf=lambda x: isinstance(x, P))
    return Trainer(cfg, mesh, model, params, opt,
                   jit_train_step(model, tc, p_sh, batch_sh),
                   SyntheticTokens(cfg, shape, par, mesh))


def train(args: argparse.Namespace) -> List[dict]:
    """Run ``args.steps`` steps and return one record per step: loss,
    grad norm, lr, whether the update was skipped, and the step's wall
    seconds (dispatch to the device finishing it; step 0 includes the
    compile)."""
    enable_compile_cache()
    tr = setup(args)
    mesh = tr.mesh
    print(f"arch={tr.cfg.name} params≈{tr.cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"schedule={args.schedule} remat={args.remat}")

    records = []
    n_skipped = 0
    for i in range(args.steps):
        batch = tr.data.batch(i)
        ts = time.perf_counter()
        tr.params, tr.opt, m = tr.step(tr.params, tr.opt, batch)
        skipped = int(m["skipped_nonfinite"])      # waits for the step
        records.append({"step": i, "loss": float(m["loss"]),
                        "gnorm": float(m["gnorm"]), "lr": float(m["lr"]),
                        "skipped": bool(skipped),
                        "seconds": time.perf_counter() - ts})
        if skipped:
            # log the first skip loudly, then just count — a burst of bad
            # steps must not flood the log
            if n_skipped == 0:
                print(f"step {i:5d} non-finite loss/grads — optimizer "
                      f"update skipped (params untouched); further skips "
                      f"counted silently", flush=True)
            n_skipped += 1
        if i % args.log_every == 0 or i == args.steps - 1:
            r = records[-1]
            # steps 1 onward only: step 0's seconds hold the compile
            warm = sum(rec["seconds"] for rec in records[1:])
            tok_s = (f"{i * args.batch * args.seq / warm:.0f}" if warm > 0
                     else "-")
            print(f"step {i:5d} loss {r['loss']:.4f} lr {r['lr']:.2e} "
                  f"gnorm {r['gnorm']:.2f} tok/s {tok_s}"
                  + (f" skipped {n_skipped}" if n_skipped else ""),
                  flush=True)
        if args.ckpt_dir and args.ckpt_every and \
                (i + 1) % args.ckpt_every == 0:
            ckpt_io.save(args.ckpt_dir, {"params": tr.params}, step=i + 1)
    if args.ckpt_dir:
        ckpt_io.save(args.ckpt_dir, {"params": tr.params}, step=args.steps)
        print(f"saved checkpoint to {args.ckpt_dir}")
    return records


def main(argv=None):
    train(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
