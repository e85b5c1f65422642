"""Tests of the benchmark itself, on the CPU: ``pytest bench/tests``.

``tiny_ctx`` builds a run's context for a cell at a size a test can hold
(same files and code, a two-layer model of the cell's kind, short rows),
skipping the harness's look for a chip, so a test can drive the rest of a
run."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                  vocab_size=512)


def tiny_ctx(cell: str, seed: int = 2**31 + 11, seconds: float = 1.0):
    import jax
    import harness as H
    spec = H.load_spec()
    entry = {w["name"]: w for w in spec["workloads"]}[cell]
    wl = H.load_workload(cell)
    wl["chips"] = 1
    wl["traffic"].update(seq=256, seq_shards=1)
    cfg = dict(H.load_config(entry["config"]), **TINY_MODEL)
    ctx = H.Ctx(spec=spec, workload=wl, config=cfg, seed=seed,
                seconds=seconds, trace=False, t_start=time.perf_counter())
    ctx.devices = jax.devices()[:1]
    ctx.peaks = H.peaks_for("TPU v5 lite")
    ctx.compiles = H.CompileCounter()
    return ctx


@pytest.fixture
def make_ctx():
    return tiny_ctx
