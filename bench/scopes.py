"""Where each device op of a traced window sits in the program: its scope
path (the ``op_name`` that ``jax.named_scope`` and JAX's transforms build,
e.g. ``jit(step)/transpose(jvp())/while/body/closed_call/recompute/jvp()``)
and, for a Pallas call, the grid work it carries as ``kernel_metadata``
(``{"launched": ..., "executed": ...}``).

The trace names each op by its HLO instruction, and the compiled text of
the step holds both fields for every instruction. So the events of a
``devtrace.Summary`` are joined with that text by instruction name
(``instructions``, ``annotate``). The compiled text is the cell's step
lowered again after the window from abstract arguments with the driver's
own shardings (``step_text``); the compiler names the instructions as it
did for the run, and a window op whose name the text lacks stops the
reading (``window_ops``) rather than going unattributed.

An annotated event is a normalised event with two trailing fields,
``[name, opcode, start_ns, dur_ns, op_name, kernel_metadata]``; the last
two are "" and None where the instruction has neither.

Every op falls in one phase of the training step (``phase``), tested in
this order: ``optimizer`` (the scope), ``recompute`` (the remat-aware
combinator's scope, or ``jax.checkpoint``'s ``rematted_computation``),
``backward`` (a ``transpose(`` in the path), ``forward`` (the rest). An op
with no ``op_name``, in its own text or in the computation it calls, is
unattributed. ``head`` and ``attn`` are tags within a phase.
"""
from __future__ import annotations

import json
import re
from typing import Dict, Optional, Tuple

PHASES = ("forward", "backward", "recompute", "optimizer")
TAGS = ("head", "attn")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = ", re.M)
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+) \(", re.M)
CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
KERNEL_META = re.compile(r"\bkernel_metadata=(\{[^{}]*\})")
TRANSFORM = re.compile(r"^(?:[\w.]+\()+(.*?)\)+$")
FLASH = re.compile(r"^(flash_(?:fwd|bwd)\b.*?)\.\d+$")

Table = Dict[str, Tuple[str, Optional[dict]]]


def instructions(text: str) -> Table:
    """{instruction name: (op_name, kernel_metadata or None)} of compiled
    HLO text. Each instruction's fields are read from its own text, up to
    the next instruction (a kernel's metadata JSON spans lines). A fusion
    the compiler made carries no ``op_name`` of its own; it takes that of
    the named instruction nearest the root of the computation it calls."""
    own: Dict[str, Tuple[str, Optional[dict], Optional[str]]] = {}
    last: Dict[str, str] = {}          # computation: its last named op_name
    comp = None
    heads = [(m.start(), m.group(1)) for m in COMPUTATION.finditer(text)]
    starts = list(INSTR.finditer(text))
    for m, nxt in zip(starts, starts[1:] + [None]):
        while heads and heads[0][0] < m.start():
            comp = heads.pop(0)[1]
        body = text[m.end():nxt.start() if nxt else len(text)]
        op = OP_NAME.search(body)
        meta = KERNEL_META.search(body)
        calls = CALLS.search(body.split("\n", 1)[0])
        own[m.group(1)] = (op.group(1) if op else "",
                           json.loads(meta.group(1)) if meta else None,
                           calls.group(1) if calls else None)
        if op:
            last[comp] = op.group(1)
    return {n: (op or (callee is not None and last.get(callee)) or "", meta)
            for n, (op, meta, callee) in own.items()}


def annotate(evs, table: Table) -> list:
    """Copies of normalised device events, each with its scope path and
    kernel metadata as two trailing fields."""
    return [[*e[:4], *table[e[0]]] for e in evs]


def segments(op_name: str):
    """The scope names of a path, each stripped of the transforms JAX wraps
    it in (``transpose(jvp(head))`` gives ``head``)."""
    for seg in op_name.split("/"):
        m = TRANSFORM.match(seg)
        yield m.group(1) if m else seg


def phase(op_name: str) -> Optional[str]:
    """The step phase of an op by its scope path (see the module
    docstring), or None if it has none."""
    if not op_name:
        return None
    segs = set(segments(op_name))
    if "optimizer" in segs:
        return "optimizer"
    if "recompute" in segs or "rematted_computation" in segs:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


def tags(op_name: str):
    """The ``head``/``attn`` tags an op carries."""
    segs = set(segments(op_name))
    return [t for t in TAGS if t in segs]


def kernel(name: str) -> Optional[str]:
    """The flash kernel an instruction runs (``flash_bwd.dq.3`` gives
    ``flash_bwd.dq``), or None."""
    m = FLASH.match(name)
    return m.group(1) if m else None


# ------------------------------------------------------ the step's text

_TEXT: Dict[int, str] = {}


def step_text(ctx) -> str:
    """The compiled HLO text of the cell's train step; kept for the run's
    other readers. The step is built as the train driver builds it
    (``drivers/train.build``), but lowered from ``ShapeDtypeStruct``s
    with the same shardings, so no weights or optimizer state are made
    again."""
    if id(ctx) not in _TEXT:
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        import harness as H
        from repro.core.config import ShapeSpec, TrainConfig
        from repro.data.pipeline import input_specs
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import Runtime, build_model
        from repro.optim import adamw
        from repro.parallel.sharding import (make_parallel_config,
                                             param_shardings)
        from repro.train.step import jit_train_step

        c, t = ctx.config, ctx.workload["traffic"]
        cfg = H.program_config(c)
        S = t["seq_shards"]
        mesh = make_mesh((len(ctx.devices) // S, S), ("data", "model"),
                         devices=ctx.devices)
        shape = ShapeSpec(ctx.workload["name"], t["seq"], t["batch"],
                          "train", docs=t.get("docs", 1))
        par = make_parallel_config(mesh, shape, schedule=t["schedule"],
                                   remat=t["remat"])
        model = build_model(cfg, Runtime(mesh=mesh, par=par))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_sh = param_shardings(params, mesh, par)
        opt = jax.eval_shape(adamw.init, params)
        batch, bspec = input_specs(cfg, shape, par, mesh)
        b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspec,
                            is_leaf=lambda x: isinstance(x, P))

        def placed(tree, sh):
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), tree, sh)

        rep = NamedSharding(mesh, P())
        step = jit_train_step(model, TrainConfig(**ctx.workload["optimizer"]),
                              p_sh, b_sh)
        _TEXT[id(ctx)] = step.lower(
            placed(params, p_sh),
            placed(opt, adamw.AdamWState(step=rep, m=p_sh, v=p_sh)),
            placed(batch, b_sh)).compile().as_text()
    return _TEXT[id(ctx)]


def window_ops(ctx):
    """{device: [annotated leaf op in the window]} of the run's trace, or
    None without a trace. Raises ``BenchError`` if an op's instruction is
    not in the step's compiled text: the join would then misplace it."""
    s = ctx.trace_summary
    if s is None or not s.events["devices"]:
        return None
    table = instructions(step_text(ctx))
    missing = sorted({e[0] for d in s.events["devices"] for e in s.ops(d)
                      if e[0] not in table})
    if missing:
        import harness as H
        raise H.BenchError(f"{len(missing)} traced ops are not in the "
                           f"compiled step: {missing[:8]}")
    return {d: annotate(s.ops(d), table) for d in s.events["devices"]}


def clipped_s(ev, lo: int, hi: int) -> float:
    return max(min(ev[2] + ev[3], hi) - max(ev[2], lo), 0) / 1e9


# ------------------------------------------------------------ reductions

def grid_work(ops: dict, lo: int, hi: int) -> dict:
    """Per flash kernel, over every device: events, seconds in the window,
    grid steps launched and executed (from each event's own call's
    metadata), and ns per launched step. Events without metadata are left
    out."""
    out: Dict[str, dict] = {}
    for evs in ops.values():
        for e in evs:
            k = kernel(e[0])
            if k is None or not e[5]:
                continue
            r = out.setdefault(k, {"events": 0, "seconds": 0.0,
                                   "launched": 0, "executed": 0})
            r["events"] += 1
            r["seconds"] += clipped_s(e, lo, hi)
            r["launched"] += int(e[5]["launched"])
            r["executed"] += int(e[5]["executed"])
    for r in out.values():
        r["ns_per_launched_step"] = (1e9 * r["seconds"] / r["launched"]
                                     if r["launched"] else None)
    return out


def phase_split(ops: dict, lo: int, hi: int) -> dict:
    """Device seconds in the window of each phase, of each tag within each
    phase, and of ops no rule placed, summed over the devices."""
    secs = {p: 0.0 for p in PHASES}
    tagged = {t: {p: 0.0 for p in PHASES} for t in TAGS}
    un = 0.0
    for evs in ops.values():
        for e in evs:
            dt = clipped_s(e, lo, hi)
            p = phase(e[4])
            if p is None:
                un += dt
                continue
            secs[p] += dt
            for t in tags(e[4]):
                tagged[t][p] += dt
    return {"seconds": secs, **{f"{t}_seconds": tagged[t] for t in TAGS},
            "unattributed_s": un}
