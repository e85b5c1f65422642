"""sp_ring_exposed_comm: on the worst device, the time in which a
collective of the sequence-parallel ring (an op under a ``ring.fwd``,
``ring.bwd`` or ``ring.home`` scope, ``core/schedule.py``) is in flight
and no compute op runs there, as a share of the traced window, in %.

Each traced collective is joined with the step's compiled text by
instruction name (``scopes.window_ops``, ``scopes.annotate``) for its
scope path, and falls in one class: the ring scope it sits under,
``other`` (a collective with a scope path but no ring scope, such as the
weight-gradient all-reduce) or unplaced (no scope path). A collective is
in flight over its span on the device's async line, or while it runs on
the ops line. Also given, for the worst device: the seconds in which any
collective is in flight (``collective_s``), split over the classes
(``device_reading`` says how), each class's share of it and the part of
that share exposed (no compute op running), and per ring scope the bytes
one step ships: the operands of its collective-permutes in the window,
from their shapes in the compiled text, over the steps.

The worst device is taken among those whose trace has an async line
with collectives on it: the profiler may write that line for some
devices only (on TPU v5 lite, device 0 alone), and where it is missing a
transfer shows only at its start and done ops.

A one-chip trace, or a program whose collectives carry no ring scope,
gives nothing to read."""
import re
from collections import defaultdict

import devtrace as T
import scopes

RING = ("ring.fwd", "ring.bwd", "ring.home")
OTHER, UNPLACED = "other", "unplaced"
CLASSES = (*RING, OTHER, UNPLACED)
SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
            "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
            "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def is_collective(ev) -> bool:
    return bool(T.COLLECTIVE.match(ev[1]))


def ring_class(op_name: str) -> str:
    """The ring scope an op sits under, ``other``, or ``unplaced``."""
    if not op_name:
        return UNPLACED
    for seg in scopes.segments(op_name):
        if seg in RING:
            return seg
    return OTHER


def _top_level(s: str):
    """The comma-separated parts of ``s`` outside any bracket."""
    parts, depth, cur = [], 0, ""
    for ch in s:
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == "," and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    return parts + [cur]


def _nbytes(shapes: str) -> int:
    total = 0
    for dt, dims in SHAPE.findall(shapes):
        n = ITEMSIZE.get(dt, 0)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def permute_bytes(text: str) -> dict:
    """{collective-permute instruction: bytes of its operand} of compiled
    HLO text. An asynchronous start's result is (operand, destination,
    contexts...): its first part is what it sends; a synchronous permute
    sends what it returns."""
    out = {}
    for line in text.splitlines():
        name, opcode = T.parse_hlo(line.strip().removeprefix("ROOT "))
        if not opcode.startswith("collective-permute") \
                or opcode.endswith("-done"):
            continue
        typ = line.split(" = ", 1)[1].split(f" {opcode}(", 1)[0].strip()
        if typ.startswith("(") and opcode != "collective-permute":
            typ = _top_level(typ[1:-1])[0]
        out[name] = _nbytes(typ)
    return out


def device_reading(ops, async_ops, lo: int, hi: int, nbytes: dict) -> dict:
    """Per class, on one device: seconds in flight, seconds exposed and,
    for the ring scopes, bytes shipped; ``ops`` and ``async_ops`` are
    annotated events of its ops and async lines.

    Each instant in which a collective is in flight goes to one class: to
    that of the collective op the ops line runs then (a synchronous
    collective, or a start or done op), else to the first class of
    ``CLASSES`` with an asynchronous collective in flight. So the
    classes' seconds add up to the time any collective is in flight, and
    a synchronous all-reduce that runs while a ring transfer is in
    flight is not counted as the ring's."""
    comp = T.union(T.clip([(e[2], e[2] + e[3]) for e in ops
                           if not is_collective(e)], lo, hi))
    on_ops, in_flight = {}, {}
    for line, evs in ((on_ops, ops), (in_flight, async_ops)):
        for e in evs:
            if is_collective(e):
                line.setdefault(ring_class(e[4]), []).append(
                    (e[2], e[2] + e[3]))
    own = {k: T.union(T.clip(on_ops.get(k, []), lo, hi)) for k in CLASSES}
    taken = T.union([iv for k in CLASSES for iv in own[k]])
    for k in CLASSES:
        more = T.subtract(T.union(T.clip(in_flight.get(k, []), lo, hi)),
                          taken)
        own[k] = T.union(own[k] + more)
        taken = T.union(taken + more)
    # one execution of a permute: its span on the async line, else its
    # start (or the synchronous permute) on the ops line
    runs = [e for e in async_ops if e[0] in nbytes] or \
        [e for e in ops if e[0] in nbytes]
    out = {}
    for k in CLASSES:
        out[k] = {"in_flight_s": T.measure(own[k]) / 1e9,
                  "exposed_s": T.measure(T.subtract(own[k], comp)) / 1e9}
        if k in RING:
            out[k]["bytes"] = sum(nbytes[e[0]] for e in runs
                                  if ring_class(e[4]) == k
                                  and lo <= e[2] < hi)
    out["collective_s"] = T.measure(taken) / 1e9
    out["ring_exposed_s"] = sum(out[k]["exposed_s"] for k in RING)
    out["has_ring"] = any(k in on_ops or k in in_flight for k in RING)
    return out


def read(ctx):
    s = ctx.trace_summary
    if s is None or len(s.events["devices"]) < 2:
        return None
    ops = scopes.window_ops(ctx)
    text = scopes.step_text(ctx)
    # an async op the text does not name is left unplaced
    table = defaultdict(lambda: ("", None), scopes.instructions(text))
    nbytes = permute_bytes(text)
    per = {}
    for d in ops:
        async_ops = scopes.annotate(s.events.get("async", {}).get(d, []),
                                    table)
        per[d] = device_reading(ops[d], async_ops, s.lo, s.hi, nbytes)
    if not any(r["has_ring"] for r in per.values()):
        return None
    # a device without an async line shows a transfer only at its start
    # and done ops, so it is compared only when no device has that line
    seen = [d for d in per if any(is_collective(e) for e in
                                  s.events.get("async", {}).get(d, []))]
    worst = max(seen or per, key=lambda d: per[d]["ring_exposed_s"])
    r = per[worst]
    steps = ctx.counts.get("steps") or 1
    out = {"value": 100.0 * r["ring_exposed_s"] / s.window_s,
           "device": worst, "exposed_s": r["ring_exposed_s"],
           "collective_s": r["collective_s"],
           "unplaced_s": r[UNPLACED]["in_flight_s"]}
    for k in (*RING, OTHER):
        out[k] = {"in_flight_s": r[k]["in_flight_s"],
                  "exposed_s": r[k]["exposed_s"]}
        if k in RING:
            out[k]["bytes_per_step"] = r[k]["bytes"] / steps
    return out
