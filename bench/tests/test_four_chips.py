"""The sequence-parallel training path over four devices (virtual CPU
devices here), at a size a test can hold, with the qwen3-8b configuration
cut to two small layers: a sound run is correct, and with the ring's
exchange between chips left out it is not. The reference splits its own
work over the same four devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import jax
import harness as H
from conftest import TINY_MODEL
cfg = dict(H.load_config("qwen3-8b"), **TINY_MODEL)
wl = H.load_workload("qwen3-8b.train-sp4-32k")
wl["traffic"] = dict(wl["traffic"], seq=512)
wl["chips"] = 4
out = {{}}
for name in ("sound", "no_exchange"):
    if name == "no_exchange":
        from repro.core import schedule
        schedule._shift = lambda x, axis, shift, size: x
    ctx = H.Ctx(spec=H.load_spec(), workload=wl, config=cfg, seed=2**31 + 9,
                seconds=0.5, trace=False, t_start=time.perf_counter())
    ctx.devices = jax.devices()[:4]
    ctx.peaks = H.peaks_for("TPU v5 lite")
    ctx.compiles = H.CompileCounter()
    res = H.load_module("drivers", "train").run(ctx)
    out[name] = [res.correct, {{c.name: c.value for c in res.checks}}]
print(json.dumps(out))
"""


def test_sequence_parallel_train_checks_out_and_catches_a_missing_exchange():
    here = Path(__file__).resolve().parent
    bench = here.parent
    code = SCRIPT.format(bench=str(bench), src=str(bench.parent / "src"),
                         tests=str(here))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["sound"][0], got
    assert not got["no_exchange"][0], got
