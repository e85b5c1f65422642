"""The persisted tuning table: schema, validation, lookup, overrides.

One JSON document holds everything the sweeps measured on one host:

.. code-block:: text

    {
      "schema_version": 1,
      "generated_by": "tools/autotune.py",
      "host": {"platform": "cpu", "jax": "0.9.0", ...},
      "kernel":   [ {backend, platform, mask_kind, head_dim, seq, op,
                     block_q, block_kv, wall_us, sweep: {"64x64": us, ...}} ],
      "schedule": [ {mask_kind, P, seq, Hq, Hkv, Dqk, best,
                     wall_us: {schedule: us}} ],
      "paged":    [ {layout, sharding, block_size, tokens_per_s,
                     sweep: {"8": tok_s, ...}} ],
      "calibration": {coeffs: {s_per_flop, s_per_byte, s_per_hop, base_s},
                      fit: {rel_rms, spearman, spearman_roofline, ...}}
    }

Lookups are **nearest-bucket**: an exact match on the categorical keys
(backend, platform, mask kind, op / schedule P / paged layout) and the
closest measured bucket in log-space on the numeric ones (``seq``,
``head_dim``) — a table swept at 256 and 512 serves a 384-long call from
the 512 row and a 64-long call from the 256 row.  A missing table, a
schema-version mismatch, or a corrupt file degrade to ``None`` (callers
fall back to their built-in heuristics) with one logged warning per
process per path — tuning must never turn into a crash.

Resolution order for :func:`active_table` (cached per process):

  1. an explicit :func:`set_table` (tests, tools);
  2. ``REPRO_TUNE_TABLE=<path>`` env;
  3. the bundled per-platform default ``tables/default_<platform>.json``;
  4. ``None`` (heuristics).  ``REPRO_TUNE=off`` short-circuits to None.

Value overrides sit *between* explicit kwargs and the table:
``REPRO_TUNE_BLOCK_Q`` / ``REPRO_TUNE_BLOCK_KV`` (kernel tiles) and
``REPRO_TUNE_BLOCK_SIZE`` (paged cache) force a value without editing any
call site — see ``kernels/registry.block_tuning_kw`` and
``serve/cache.PagedKVCache.create`` for the full precedence chains.
"""
from __future__ import annotations

import json
import logging
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

log = logging.getLogger(__name__)

SCHEMA_VERSION = 1

# entry keys required per section (validation rejects rows missing any)
_REQUIRED = {
    "kernel": ("backend", "platform", "mask_kind", "head_dim", "seq", "op",
               "block_q", "block_kv"),
    "schedule": ("mask_kind", "P", "seq", "best", "wall_us"),
    "paged": ("layout", "sharding", "block_size"),
}


class TableError(ValueError):
    """Structured load/validation failure (path + reason)."""

    def __init__(self, path, reason):
        self.path, self.reason = path, reason
        super().__init__(f"tuning table {path!r}: {reason}")


def _log_dist(a: float, b: float) -> float:
    """Distance in log2 space (seq/head_dim buckets are powers-of-two-ish);
    guards zero/negative garbage from hand-edited tables."""
    a, b = max(float(a), 1.0), max(float(b), 1.0)
    return abs(math.log2(a) - math.log2(b))


class TuningTable:
    """In-memory view of one tuning-table document (see module docstring)."""

    def __init__(self, data: dict, path: Optional[str] = None):
        self.data = data
        self.path = path
        errs = self.validate(data)
        if errs:
            raise TableError(path or "<dict>", "; ".join(errs[:3]))

    # ------------------------------------------------------------ schema
    @staticmethod
    def validate(data) -> List[str]:
        """Schema errors ([] = valid).  Checked on load so a corrupt or
        future-versioned table degrades to heuristics instead of crashing
        some resolve() deep inside a jit trace."""
        errs = []
        if not isinstance(data, dict):
            return [f"document is {type(data).__name__}, expected object"]
        v = data.get("schema_version")
        if v != SCHEMA_VERSION:
            errs.append(f"schema_version {v!r} != supported {SCHEMA_VERSION}")
        for section, req in _REQUIRED.items():
            rows = data.get(section, [])
            if not isinstance(rows, list):
                errs.append(f"section {section!r} is not a list")
                continue
            for i, r in enumerate(rows):
                if not isinstance(r, dict):
                    errs.append(f"{section}[{i}] is not an object")
                    continue
                missing = [k for k in req if k not in r]
                if missing:
                    errs.append(f"{section}[{i}] missing {missing}")
        cal = data.get("calibration")
        if cal is not None:
            co = cal.get("coeffs") if isinstance(cal, dict) else None
            if not isinstance(co, dict) or not all(
                    isinstance(co.get(k), (int, float)) for k in
                    ("s_per_flop", "s_per_byte", "s_per_hop", "base_s")):
                errs.append("calibration.coeffs incomplete")
        return errs

    # -------------------------------------------------------- persistence
    @classmethod
    def load(cls, path: str) -> "TuningTable":
        """Parse + validate; raises :class:`TableError` on any problem."""
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise TableError(path, f"unreadable ({e})") from e
        return cls(data, path=path)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.data, f, indent=1, sort_keys=False)
            f.write("\n")
        self.path = path

    # ------------------------------------------------------------ lookups
    def best_blocks(self, *, backend: str, platform: str, mask_kind: str,
                    head_dim: int, seq: int,
                    op: str = "fwd") -> Optional[Tuple[int, int]]:
        """Winning ``(block_q, block_kv)`` for the nearest swept bucket:
        exact on (backend, platform, mask_kind, op), nearest in log space
        on (seq, head_dim).  None when no row matches the exact keys."""
        cands = [r for r in self.data.get("kernel", [])
                 if r["backend"] == backend and r["platform"] == platform
                 and r["mask_kind"] == mask_kind and r["op"] == op]
        if not cands:
            return None
        r = min(cands, key=lambda r: (_log_dist(r["seq"], seq)
                                      + _log_dist(r["head_dim"], head_dim),
                                      r["seq"], r["head_dim"]))
        return int(r["block_q"]), int(r["block_kv"])

    def best_schedule(self, *, mask_kind: str, P: int, seq: int,
                      candidates: Optional[Sequence[str]] = None,
                      ) -> Optional[str]:
        """Measured-fastest schedule at the nearest (mask_kind, P, seq)
        bucket, restricted to ``candidates`` (the capable set at this call
        site — the measured global best may be a schedule the caller can't
        run, e.g. zigzag without its layout permutation).  None when no
        row matches mask_kind × P or no candidate was measured."""
        rows = [r for r in self.data.get("schedule", [])
                if r["mask_kind"] == mask_kind and int(r["P"]) == int(P)]
        if not rows:
            return None
        r = min(rows, key=lambda r: (_log_dist(r["seq"], seq), r["seq"]))
        walls = {k: v for k, v in r["wall_us"].items()
                 if isinstance(v, (int, float))}
        if candidates is not None:
            walls = {k: v for k, v in walls.items() if k in candidates}
        if not walls:
            return None
        return min(walls, key=lambda k: (walls[k], k))

    def schedule_rows(self) -> List[dict]:
        return list(self.data.get("schedule", []))

    def best_block_size(self, *, layout: str,
                        sharding: str = "none") -> Optional[int]:
        """Paged-cache block size for (kv layout, pool sharding); falls
        back to the same layout under any sharding when the exact pair
        was not swept."""
        rows = [r for r in self.data.get("paged", [])
                if r["layout"] == layout]
        if not rows:
            return None
        exact = [r for r in rows if r["sharding"] == sharding]
        r = (exact or rows)[0]
        return int(r["block_size"])

    def coeffs(self) -> Optional[Dict[str, float]]:
        """Calibrated cost-model coefficients (None = table not
        calibrated; consumers fall back to the analytic roofline)."""
        cal = self.data.get("calibration")
        if not cal:
            return None
        return dict(cal["coeffs"])

    def fit(self) -> Optional[dict]:
        cal = self.data.get("calibration")
        return dict(cal.get("fit", {})) if cal else None


# ==========================================================================
# Process-wide active table
# ==========================================================================

_UNSET = object()
_ACTIVE = _UNSET                 # cache: TuningTable | None once resolved
_EXPLICIT = _UNSET               # set_table() override
_WARNED = set()                  # one degradation warning per path


def tables_dir() -> str:
    return os.path.join(os.path.dirname(__file__), "tables")


def bundled_default(platform: str) -> Optional[str]:
    p = os.path.join(tables_dir(), f"default_{platform}.json")
    return p if os.path.exists(p) else None


def _load_checked(path: str) -> Optional[TuningTable]:
    """Load-or-degrade: any failure (missing, corrupt, schema mismatch)
    logs one warning per process per path and returns None."""
    try:
        return TuningTable.load(path)
    except TableError as e:
        if path not in _WARNED:
            _WARNED.add(path)
            log.warning("ignoring tuning table %s (%s); falling back to "
                        "built-in heuristics", path, e.reason)
        return None


def set_table(table) -> None:
    """Force the active table: a :class:`TuningTable`, a path, or None
    (= heuristics).  Pass ``table=...UNSET...``?  No — call
    :func:`reset` to return to env/bundled resolution."""
    global _EXPLICIT, _ACTIVE
    if isinstance(table, str):
        table = _load_checked(table)
    _EXPLICIT = table
    _ACTIVE = _UNSET


def reset() -> None:
    """Drop the explicit override and the cached resolution (tests)."""
    global _EXPLICIT, _ACTIVE
    _EXPLICIT = _UNSET
    _ACTIVE = _UNSET


def active_table() -> Optional[TuningTable]:
    """The table consumers consult (see module docstring for the
    resolution order).  Cached; :func:`reset` after changing env vars."""
    global _ACTIVE
    if os.environ.get("REPRO_TUNE", "").lower() in ("off", "0", "false"):
        return None
    if _EXPLICIT is not _UNSET:
        return _EXPLICIT
    if _ACTIVE is _UNSET:
        path = os.environ.get("REPRO_TUNE_TABLE")
        if not path:
            try:
                import jax
                path = bundled_default(jax.default_backend())
            except Exception:        # pragma: no cover - jax always present
                path = None
        _ACTIVE = _load_checked(path) if path else None
    return _ACTIVE


def env_int(name: str) -> Optional[int]:
    """Int env override, or None when unset/garbage (garbage warns once)."""
    v = os.environ.get(name)
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        if name not in _WARNED:
            _WARNED.add(name)
            log.warning("ignoring non-integer %s=%r", name, v)
        return None
