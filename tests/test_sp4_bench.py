"""The benchmark's four-chip training cell, ``qwen3-8b.sp4-32k``
(``bench/workloads/qwen3-8b.sp4-32k.json``), driven through the bench's
own driver for it (``bench/drivers/train_sp.py``) on four virtual CPU
devices: the qwen3-8b configuration cut to the bench tests' tiny sizes
(q/k norm, GQA and the untied head kept) at seq 512, split over the four
devices by the balanced schedule. One subprocess runs every case:

* the driver's ``correct`` (the program against ``bench/reference_sp.py``)
  is true for the sound program, and false with the ring's exchange left
  out (``schedule._shift`` as identity) or with the program's q/k norm
  gains ignored, so both sides implement q/k norm;
* the split reference follows ``bench/reference.py``: the same losses,
  gradient norms, first gradient and weights after its steps;
* every collective-permute of the compiled sequence-parallel step sits
  under a ``ring.*`` scope, and the one-chip step carries none."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {bench_tests!r}]
import jax
import jax.numpy as jnp
import numpy as np
import devtrace
import harness as H
import reference
import reference_sp
import scopes
import traffic
import weights
from conftest import TINY_MODEL
from repro.core import schedule
from repro.models import layers

cfg = dict(H.load_config("qwen3-8b"), **TINY_MODEL)
drv = H.load_module("drivers", "train_sp")


def ctx_for(chips):
    wl = H.load_workload("qwen3-8b.sp4-32k")
    wl["traffic"] = dict(wl["traffic"], seq=512, seq_shards=chips)
    wl["chips"] = chips
    ctx = H.Ctx(spec=H.load_spec(), workload=wl, config=cfg, seed=2**31 + 9,
                seconds=0.0, trace=False, t_start=time.perf_counter())
    ctx.devices = jax.devices()[:chips]
    ctx.peaks = H.peaks_for("TPU v5 lite")
    ctx.compiles = H.CompileCounter()
    return ctx


def drive():
    res = drv.run(ctx_for(4))
    return {{"correct": res.correct,
             "checks": {{c.name: c.value for c in res.checks}}}}


def split_reference_gap():
    # the largest relative gap, over the readings and the leaves, between
    # the split reference's three steps and the plain reference's
    ctx = ctx_for(4)
    mesh = jax.sharding.Mesh(np.array(ctx.devices), ("s",))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    rows = traffic.train_rows(ctx.workload["traffic"], cfg["vocab_size"],
                              ctx.seed, drv.N_CHECK)
    got = []
    for mod in (reference, reference_sp):
        p = weights.make(cfg, H.jax_key(ctx.seed))
        bs = [{{k: jax.device_put(v, rep) for k, v in r.items()}}
              for r in rows]
        losses, gnorms, first, p3 = mod.train_steps(
            cfg, ctx.workload["optimizer"], p, bs, "fp32", mesh)
        got.append([np.asarray(losses), np.asarray(gnorms),
                    *(np.asarray(x, np.float32) for x in
                      jax.tree.leaves(first) + jax.tree.leaves(p3))])
    return max(float(np.max(np.abs(a - b)) / max(np.max(np.abs(a)), 1e-30))
               for a, b in zip(*got))


def ring_scopes(chips):
    # {{collective-permute instruction: its ring.* scopes}}, and how many
    # instructions of any kind carry a ring.* scope
    text = scopes.step_text(ctx_for(chips))
    table = scopes.instructions(text)
    ring = {{n: [s for s in scopes.segments(op) if s.startswith("ring.")]
             for n, (op, _) in table.items()}}
    perms = {{}}
    for line in text.splitlines():
        name, opcode = devtrace.parse_hlo(line.strip().removeprefix("ROOT "))
        if opcode.startswith("collective-permute"):
            perms[name] = ring[name]
    return {{"permutes": perms, "ring_ops": sum(map(bool, ring.values()))}}


out = {{"sound": drive()}}
shift = schedule._shift
schedule._shift = lambda x, axis, shift, size: x
out["no_exchange"] = drive()
schedule._shift = shift
norm = layers.head_rms_norm
layers.head_rms_norm = lambda x, w, eps=1e-5: norm(x, jnp.ones_like(w), eps)
out["qk_norm_ignored"] = drive()
layers.head_rms_norm = norm
out["split_reference_gap"] = split_reference_gap()
out["sp4"] = ring_scopes(4)
out["one_chip"] = ring_scopes(1)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sp4(subproc):
    bench = ROOT / "bench"
    code = SCRIPT.format(bench=str(bench), src=str(ROOT / "src"),
                         bench_tests=str(bench / "tests"))
    return json.loads(subproc(code, devices=4).strip().splitlines()[-1])


@pytest.mark.parametrize("case, want", [
    ("sound", True),
    ("no_exchange", False),
    ("qk_norm_ignored", False),
])
def test_the_cell_is_correct_only_for_the_sound_program(sp4, case, want):
    assert sp4[case]["correct"] is want, sp4[case]


def test_the_split_reference_follows_the_plain_reference(sp4):
    assert sp4["split_reference_gap"] < 1e-5, sp4["split_reference_gap"]


def test_every_collective_permute_of_the_sp4_step_sits_in_a_ring_scope(sp4):
    perms = sp4["sp4"]["permutes"]
    assert perms
    assert all(perms.values()), {n: s for n, s in perms.items() if not s}
    assert {s for segs in perms.values() for s in segs} == {
        "ring.fwd", "ring.bwd", "ring.home"}


def test_the_one_chip_step_has_no_ring_scope(sp4):
    assert sp4["one_chip"] == {"permutes": {}, "ring_ops": 0}
