"""What every cell of the benchmark shares: finding a cell's files by name,
the device checks, the compile cache, seeds, and the result line.

Everything here is found from the names in ``BENCHMARK.json``:

* ``bench/workloads/<cell>.json``: the cell's driver kind, configuration,
  chips and traffic parameters;
* ``bench/configs/<config>.json``: the model as it is run;
* ``bench/drivers/<kind>.py``: ``run(ctx) -> Result``;
* ``bench/metrics/<metric>.py``: ``read(ctx) -> dict | None``;
* ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
GIB = float(1 << 30)


class BenchError(RuntimeError):
    """A run that cannot produce a result: no chip, unknown device, a
    missing file. ``run.py`` prints it and exits non-zero."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_workload(name: str) -> dict:
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    wl["name"] = name
    return wl


def load_config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '.' and '-')."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json; add its published peaks")
    return table["devices"][device_kind]


def seed_words(seed: int):
    """Two 31-bit words of a seed of any size (a run's seed may not fit
    32 signed bits)."""
    s = int(seed) % (1 << 62)
    return s & 0x7FFFFFFF, s >> 31


def jax_key(seed: int, salt: int = 0):
    import jax
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(lo), hi), salt)


def np_rng(seed: int, salt: int = 0):
    import numpy as np
    return np.random.default_rng([*seed_words(seed), salt])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in the checkout's fixed
    ``.jax_cache`` (the directory the program itself uses), or where
    ``JAX_COMPILATION_CACHE_DIR`` points. Every program is cached, however
    small, so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compilations; ``window()`` brackets the measured
    window so that a compile inside it shows."""

    def __init__(self):
        import jax
        self.total = 0
        self.in_window = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1
            if self._open:
                self.in_window += 1

    def open(self) -> None:
        self._open = True

    def close(self) -> None:
        self._open = False


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``."""
    from repro.core.config import AttnConfig, ModelConfig
    from weights import dims
    m = dims(c)
    return ModelConfig(
        name=c["arch"], arch_type="dense", n_layers=m["L"], d_model=m["d"],
        d_ff=m["F"], vocab=m["V"],
        attn=AttnConfig(n_heads=m["Hq"], n_kv_heads=m["Hkv"],
                        head_dim=m["hd"], qk_norm=m["qk_norm"],
                        rope_theta=m["theta"]),
        tie_embeddings=m["tied"], norm_eps=m["eps"], dtype=m["dtype"])


def require_chips(n: int):
    """The devices of a run: ``n`` TPU chips, or a BenchError."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < n:
        raise BenchError(f"cell needs {n} chips, found {len(devs)}")
    return devs[:n]


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclasses.dataclass
class Ctx:
    """One run: what the driver is given, and what it leaves for the
    metric readers."""
    spec: dict
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list = dataclasses.field(default_factory=list)
    peaks: dict = dataclasses.field(default_factory=dict)
    compiles: Optional[CompileCounter] = None
    # filled by the driver for the metric readers
    counts: dict = dataclasses.field(default_factory=dict)
    trace_summary: object = None

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def note(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit (passes at value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    metrics: Dict[str, float]          # end-to-end metrics (host clock)
    checks: List[Check]
    breakdown: Optional[dict] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def per_layer_metrics(ctx: Ctx) -> Dict[str, dict]:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell
    (or lists no cells), read by its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    cell = ctx.workload["name"]
    for m in ctx.spec["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        got = load_module("metrics", m["name"]).read(ctx)
        if got is not None:
            out[m["name"]] = {"value": got["value"], "unit": m["unit"],
                              **{k: v for k, v in got.items()
                                 if k != "value"}}
    return out


def end_to_end_units(spec: dict) -> Dict[str, str]:
    return {m["name"]: m["unit"] for m in spec["end_to_end"]}
