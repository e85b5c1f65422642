"""The reduction from a profiler trace to the numbers the per-layer metrics
read: device busy time, idle gaps named by the benchmark's own host spans,
summed kernel time, and collective time that no compute hides.

A trace is first normalised to plain lists (``events_from_xspace``):

* ``devices``: {device id: [[name, opcode, start_ns, dur_ns], ...]}, the
  operations that ran on the device (a Pallas kernel's name is its
  call's, e.g. ``flash_fwd.7``);
* ``async``: the same for the device's asynchronous ops (copies,
  collectives), each spanning the time it was in flight;
* ``host``: [[name, start_ns, dur_ns], ...], the benchmark's spans
  (``jax.profiler.TraceAnnotation`` names starting ``bench.``).

Everything after that works on the lists alone, so the tests run it on a
small recorded trace kept beside them.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
HLO = re.compile(r"^%(\S+) = ")


def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of one line of HLO text, as a TPU trace
    names its ops: ``%flash_fwd.7 = (bf16[...], f32[...]) custom-call(...``
    gives ("flash_fwd.7", "custom-call")."""
    m = HLO.match(text)
    if not m:
        return text, ""
    rest = text[m.end():]
    if rest.startswith("("):                  # a tuple type
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = rest.strip().split("(", 1)[0]
    return m.group(1), op


def events_from_xspace(path: str, device_ids=None) -> dict:
    """Normalise one ``.xplane.pb`` file (see module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "async": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if device_ids is not None and int(m.group(1)) not in device_ids:
                continue
            for line in plane.lines:
                key = {OPS_LINE: "devices", ASYNC_LINE: "async"}.get(
                    line.name)
                if key is None:
                    continue
                evs = out[key].setdefault(m.group(1), [])
                for e in line.events:
                    evs.append([*parse_hlo(e.name), int(e.start_ns),
                                int(e.duration_ns)])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    return out


# ------------------------------------------------------------ interval sets

def union(intervals) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(ivs) -> int:
    return sum(e - s for s, e in ivs)


def clip(ivs, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def subtract(a, b):
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events):
    """The events that contain no other event of the same list (control
    flow ops on a device's ops line span their bodies' ops)."""
    evs = sorted(events, key=lambda x: (x[2], -x[3]))
    keep = [True] * len(evs)
    stack: List[int] = []
    for i, ev in enumerate(evs):
        s, e = ev[2], ev[2] + ev[3]
        while stack and evs[stack[-1]][2] + evs[stack[-1]][3] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2] + evs[stack[-1]][3]:
            keep[stack[-1]] = False
        stack.append(i)
    return [ev for ev, k in zip(evs, keep) if k]


# ----------------------------------------------------------------- summary

@dataclasses.dataclass
class Summary:
    events: dict
    lo: int                   # window, ns, from the first to the last span
    hi: int
    _ops: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def of(cls, events: dict) -> "Summary":
        host = events["host"]
        if not host:
            raise ValueError("the trace holds none of the benchmark's spans")
        lo = min(s for _, s, _ in host)
        hi = max(s + d for _, s, d in host)
        return cls(events, lo, hi)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, dev: str):
        """The device's leaf ops that overlap the window."""
        if dev not in self._ops:
            self._ops[dev] = leaves([e for e in self.events["devices"][dev]
                                     if e[2] + e[3] > self.lo
                                     and e[2] < self.hi])
        return self._ops[dev]

    def busy(self, dev: str):
        return union(clip([(e[2], e[2] + e[3]) for e in self.ops(dev)],
                          self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices in the trace."""
        devs = self.events["devices"]
        if not devs:
            return 0.0
        return sum(measure(self.busy(d)) for d in devs) / len(devs) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, pattern: str) -> Tuple[float, int]:
        """(seconds summed over every device, number of events) of the ops
        whose name matches ``pattern``, inside the window."""
        rx = re.compile(pattern)
        tot = n = 0
        for d in self.events["devices"]:
            for e in self.ops(d):
                if rx.match(e[0]):
                    s, t = max(e[2], self.lo), min(e[2] + e[3], self.hi)
                    tot += max(t - s, 0)
                    n += 1
        return tot / 1e9, n

    def exposed_collective_s(self, dev: str) -> float:
        """Seconds in which a collective is in flight on ``dev`` and no
        compute op runs there. A synchronous collective is in flight while
        it runs; an asynchronous one over its span on the async line."""
        coll, comp = [], []
        for e in self.ops(dev):
            (coll if COLLECTIVE.match(e[1]) else comp).append(
                (e[2], e[2] + e[3]))
        for e in self.events.get("async", {}).get(dev, []):
            if COLLECTIVE.match(e[1]):
                coll.append((e[2], e[2] + e[3]))
        inflight = union(clip(coll, self.lo, self.hi))
        return measure(subtract(inflight, union(clip(comp, self.lo,
                                                     self.hi)))) / 1e9

    def top_ops(self, k: int = 10):
        tot: Dict[str, float] = {}
        for d in self.events["devices"]:
            for e in self.ops(d):
                key = re.sub(r"\.\d+$", "", e[0])
                s, t = max(e[2], self.lo), min(e[2] + e[3], self.hi)
                tot[key] = tot.get(key, 0.0) + (t - s) / 1e9
        return sorted(([n, s] for n, s in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10):
        """The ``k`` longest idle gaps of any device, each named by the
        host span that overlaps it most."""
        host = self.events["host"]
        gaps = []
        for d in self.events["devices"]:
            for s, e in subtract([(self.lo, self.hi)], self.busy(d)):
                best, name = 0, "no span"
                for hn, hs, hd in host:
                    ov = min(e, hs + hd) - max(s, hs)
                    if ov > best:
                        best, name = ov, hn
                gaps.append([name, (e - s) / 1e9])
        return sorted(gaps, key=lambda x: -x[1])[:k]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


class Capture:
    """The profiler over a window, written to a temporary directory that
    is removed once the trace is read."""

    def __init__(self):
        self.dir: Optional[str] = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def events(self, devices) -> dict:
        try:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[0]
            return events_from_xspace(path, {d.id for d in devices})
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def reduce(self, devices) -> Summary:
        return Summary.of(self.events(devices))

