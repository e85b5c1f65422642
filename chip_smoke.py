"""Smoke run of the main path on a TPU.

    python chip_smoke.py               # one chip: train phase + serve phase
    python chip_smoke.py --four-chips  # four chips: sequence-parallel step

Everything runs in this one process, through the program's own entry
points, at the full published width of smollm-360m (32 layers, d_model 960,
15/5 heads, head_dim 64, vocab 49152, bf16) with random weights from
PRNGKey(0) and the seeded synthetic token stream.

* Train phase: ``repro.launch.train`` takes 5 steps at seq 4096, batch 1,
  remat_aware, balanced schedule. Fails on a non-finite loss or when step
  1's loss is not below step 0's.
* Kernel check: the compiled flash fwd/bwd against the float32 oracle on
  a small input (causal, and document masking with segment IDs).
* Serve phase: the paged ``Engine`` answers 4 requests (prompt 512, 16
  greedy tokens). Each request's first decode-step logits are compared with
  a float32 forward of the same params on the ``ref`` oracle at ``highest``
  matmul precision: max |error| must stay within ``LOGIT_TOL`` of the
  reference's largest |logit| (bf16 carries 8 mantissa bits; a reduced-
  depth CPU probe of the same comparison measured about 1.2%).
* Four-chip phase: one training step at seq 16384 sequence-parallel over 4
  chips (``--seq-shards 4``, balanced schedule), against the same step,
  params and batch on a one-device mesh. Loss and global grad norm must
  agree within ``FOUR_CHIP_TOL`` (relative), and the four-chip batch must
  live on all four devices.

The attention backend resolved at every site is recorded while the steps
are traced; flash fwd, flash bwd and paged decode must all be ``pallas``.
The chunked-prefill site carries a traced position offset, which the
compiled kernels do not take, and runs on ``chunked-lax`` (exact and
compiled). Exits non-zero, printing no result line, when no TPU is found or
any phase fails. The last line of stdout is the result JSON.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
LOGIT_TOL = 5e-2          # max |logit error| / max |reference logit|
KERNEL_TOL = 2e-2         # the same ratio for the flash kernels' outputs
FOUR_CHIP_TOL = 2e-2      # relative |Δ| of loss and grad norm, bf16 sums
ORACLE = "ref"            # the plain float32 jnp reference backend
# backend resolution call site -> attention site it serves
SITES = {"chunk_attn": "flash fwd", "chunk_attn_bwd": "flash bwd",
         "paged_decode_attn": "paged decode"}
MUST_BE_PALLAS = ("flash fwd", "flash bwd", "paged decode")


@contextlib.contextmanager
def recording_backends(seen):
    """While active, every ``registry.resolve`` call (made as a step is
    traced) is recorded in ``seen`` as {site: {backend names}}."""
    from repro.kernels import registry
    resolve = registry.resolve

    def recording(*args, **kw):
        be = resolve(*args, **kw)
        site = SITES.get(sys._getframe(1).f_code.co_name, "other")
        if kw.get("dynamic_offsets"):
            site = "prefill chunk (traced offset)"
        seen.setdefault(site, set()).add(be.name)
        return be
    registry.resolve = recording
    try:
        yield
    finally:
        registry.resolve = resolve


def check_backends(seen, sites):
    line = {s: sorted(seen.get(s, ())) for s in sorted(seen)}
    print("attention backends:", json.dumps(line), flush=True)
    for s in sites:
        if seen.get(s) != {"pallas"}:
            raise RuntimeError(f"{s} resolved to {sorted(seen.get(s, ()))},"
                               f" not pallas")


def train_phase(jax):
    from repro.launch import train
    args = train.parse_args(["--arch", ARCH, "--steps", "5", "--seq", "4096",
                             "--batch", "1", "--remat", "remat_aware",
                             "--schedule", "balanced", "--log-every", "1"])
    recs = train.train(args)
    for r in recs:
        print(f"train step {r['step']} loss {r['loss']!r} gnorm "
              f"{r['gnorm']!r} seconds {r['seconds']!r}", flush=True)
    losses = [r["loss"] for r in recs]
    if not all(map(_finite, losses)) or any(r["skipped"] for r in recs):
        raise RuntimeError(f"non-finite training step: {losses}")
    if not losses[1] < losses[0]:
        raise RuntimeError(f"step 1 loss {losses[1]} not below step 0 "
                           f"loss {losses[0]}")
    warm = [r["seconds"] for r in recs[1:]]
    peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    print(f"train: step seconds after warm-up {warm!r} median "
          f"{statistics.median(warm)!r}; tokens/s "
          f"{4096 / statistics.median(warm)!r}; peak_bytes_in_use {peak}",
          flush=True)


def kernel_check(jax):
    """The compiled flash kernels against the float32 oracle on a small
    input: causal, and document masking with dynamic segment IDs."""
    import jax.numpy as jnp
    from repro.core import mask as mk
    from repro.core.attention import chunk_attn, chunk_attn_bwd
    B, T, Hq, Hkv, D = 2, 1024, 15, 5, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, do = (jax.random.normal(k, (B, T, Hq, D), jnp.bfloat16)
             for k in ks[::3])
    k, v = (jax.random.normal(k, (B, T, Hkv, D), jnp.bfloat16)
            for k in ks[1:3])
    seg = jnp.repeat(jnp.arange(4, dtype=jnp.int32)[None], T // 4,
                     axis=1).reshape(1, T).repeat(B, axis=0)

    def run(impl, m, segs, *xs):
        kw = dict(mask=m, impl=impl, q_segments=segs, kv_segments=segs)
        o, lse = chunk_attn(*xs[:3], **kw)
        return (o,) + tuple(chunk_attn_bwd(*xs[:3], o, lse, xs[3], **kw))

    errs = {}
    for name, m, segs in (("causal", mk.causal(), None),
                          ("document", mk.document(), seg)):
        got = jax.jit(lambda *xs: run("pallas", m, segs, *xs))(q, k, v, do)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *xs: run(ORACLE, m, segs, *xs))(
                *(x.astype(jnp.float32) for x in (q, k, v, do)))
        errs[name] = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                            / jnp.max(jnp.abs(w))) for g, w in zip(got, want)]
    print(f"kernels: flash (o, dq, dk, dv) vs float32 oracle at "
          f"({B}, {T}, {Hq}/{Hkv}, {D}) bf16, max|err|/max|ref| {errs!r} "
          f"(tolerance {KERNEL_TOL})", flush=True)
    if max(max(e) for e in errs.values()) > KERNEL_TOL:
        raise RuntimeError(f"flash kernels off the reference: {errs}")


def serve_phase(jax, seen):
    import jax.numpy as jnp
    import numpy as np
    from repro.core.config import ShapeSpec, get_config
    from repro.data.pipeline import SyntheticTokens
    from repro.launch.mesh import make_local_mesh
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    from repro.serve.cache import PagedKVCache
    from repro.serve.engine import Engine

    n_req, prompt_len, gen = 4, 512, 16
    cfg = get_config(ARCH)
    mesh = make_local_mesh()
    shape = ShapeSpec("serve", prompt_len, n_req, "prefill")
    par = make_parallel_config(mesh, shape)
    model = build_model(cfg, Runtime(mesh=mesh, par=par))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(SyntheticTokens(cfg, shape, par, mesh)
                         .batch(0)["tokens"])
    bs = PagedKVCache.default_block_size(cfg.attn, mesh, par.seq_axis)
    # whole-prompt prefill: one prefill shape to compile, not one per chunk
    eng = Engine(model, params, max_batch=n_req, block_size=bs,
                 n_blocks=n_req * -(-(prompt_len + gen) // bs) + 2,
                 prefill_chunk_tokens=0)

    # the decode step's last-position logits, captured as it runs
    step_logits = []
    decode = eng.model.decode

    def capturing_decode(p, cache, batch):
        logits, cache2 = decode(p, cache, batch)
        jax.debug.callback(lambda x: step_logits.append(np.asarray(x)),
                           logits[:, -1].astype(jnp.float32))
        return logits, cache2
    eng.model.decode = capturing_decode

    rids = [eng.submit(prompts[b], max_new_tokens=gen) for b in range(n_req)]
    first = {}
    with recording_backends(seen):
        while not eng.sched.idle:
            fresh = [r for r in rids if not eng.requests[r].emitted]
            n = len(step_logits)
            eng.step()
            for r in fresh:  # a request's first token came from this step
                req = eng.requests[r]
                if req.emitted and len(step_logits) == n + 1 \
                        and req.slot >= 0:
                    first[r] = step_logits[-1][req.slot]
    out = {r: list(eng.requests[r].emitted) for r in rids}
    if sorted(first) != sorted(rids) or any(len(t) != gen
                                            for t in out.values()):
        raise RuntimeError(f"engine did not answer every request: {out}")
    print(f"serve: {n_req} requests x {gen} tokens, block_size {bs}; "
          f"first request's tokens {out[rids[0]]}", flush=True)

    ref = build_model(cfg.replace(dtype="float32"),
                      Runtime(mesh=mesh, par=par, impl=ORACLE))
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(ref.prefill)(p32, {"tokens": jnp.asarray(prompts)})
    want = np.asarray(want)[:, -1]
    errs = []
    for b, r in enumerate(rids):
        err = float(np.max(np.abs(first[r] - want[b])))
        errs.append(err / float(np.max(np.abs(want[b]))))
    print(f"serve: first-decode logits vs float32 oracle, max|err|/max|ref| "
          f"per request {errs!r} (tolerance {LOGIT_TOL})", flush=True)
    if not all(e <= LOGIT_TOL for e in errs):
        raise RuntimeError(f"decode logits off the reference: {errs}")


def four_chip_phase(jax):
    from repro.launch import train
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    enable_compile_cache()
    args = train.parse_args(["--arch", ARCH, "--steps", "1", "--seq", "16384",
                             "--batch", "1", "--seq-shards", "4",
                             "--remat", "remat_aware",
                             "--schedule", "balanced"])
    results = {}
    # the one-device step needs most of a chip's memory: run it first
    for name, mesh in (("1 device", make_mesh((1, 1), ("data", "model"),
                                              devices=jax.devices()[:1])),
                       ("4 devices", None)):
        tr = train.setup(args, mesh=mesh)
        batch = tr.data.batch(0)
        where = {d.id for d in batch["tokens"].sharding.device_set}
        _, _, m = tr.step(tr.params, tr.opt, batch)
        results[name] = (float(m["loss"]), float(m["gnorm"]))
        print(f"four-chip: {name} mesh "
              f"{dict(zip(tr.mesh.axis_names, tr.mesh.devices.shape))} "
              f"schedule {tr.model.rt.par.schedule} over "
              f"{tr.model.rt.seq_size} shard(s); batch on devices "
              f"{sorted(where)}; loss {results[name][0]!r} gnorm "
              f"{results[name][1]!r}", flush=True)
        if name == "4 devices" and len(where) != 4:
            raise RuntimeError(f"sharded batch sits on devices {where}")
        del tr, batch, m
    (l4, g4), (l1, g1) = results["4 devices"], results["1 device"]
    rel = (abs(l4 - l1) / abs(l1), abs(g4 - g1) / abs(g1))
    print(f"four-chip: relative |Δ| loss {rel[0]!r}, grad norm {rel[1]!r} "
          f"(tolerance {FOUR_CHIP_TOL})", flush=True)
    if not all(_finite(x) for x in (l4, g4, l1, g1)) or \
            max(rel) > FOUR_CHIP_TOL:
        raise RuntimeError(f"4-device step disagrees with 1 device: {rel}")


def _finite(x):
    return x == x and abs(x) != float("inf")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sequence-parallel phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU found (JAX platform {devs[0].platform!r}); this "
              f"script runs only on a TPU", file=sys.stderr)
        return 1
    from repro.launch.cache import compile_cache_dir
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{compile_cache_dir()}", flush=True)

    seen = {}
    if args.four_chips:
        with recording_backends(seen):
            four_chip_phase(jax)
        check_backends(seen, ("flash fwd", "flash bwd"))
    else:
        with recording_backends(seen):
            train_phase(jax)
        kernel_check(jax)
        serve_phase(jax, seen)
        check_backends(seen, MUST_BE_PALLAS)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
