"""The join of trace events with the compiled step (``scopes.py``) and the
two readers built on it, on a small trace recorded on a TPU v5 lite:
three steps of the train-8k cell's own step at seq 1024 with two of
smollm-360m's layers, each step in the benchmark's host spans
(``data/step_trace.json``), and that step compiled for a described v5e
(``data/step_hlo.txt.gz``, its stack-frame tables left out), which names
every traced instruction, scope path and kernel metadata as the chip's
compile did. The first recorded trace (``data/probe_trace.json``) holds
no names and must still reduce as it did."""
import gzip
import json
from pathlib import Path

import pytest

import devtrace as T
import harness as H
import scopes

DATA = Path(__file__).parent / "data"
STEP = json.loads((DATA / "step_trace.json").read_text())
STEP_HLO = gzip.decompress((DATA / "step_hlo.txt.gz").read_bytes()).decode()
PROBE = json.loads((DATA / "probe_trace.json").read_text())
L, S, BH = 2, 3, 15                 # layers, steps, batch × query heads
NQ = 1024 // 128
# what the compiler makes of its own accord: copies, layout moves and
# the asynchronous slices of the scanned layers' weights
MOVERS = ("copy", "copy-start", "copy-done", "async-start", "async-done",
          "reshape", "broadcast", "iota")


class _Ctx:
    """What a reader needs of a run: the summary and the step's text."""

    def __init__(self, events, text):
        self.trace_summary = T.Summary.of(events)
        scopes._TEXT[id(self)] = text


def _text(table):
    """Compiled HLO text holding ``table``'s entries, as the compiler
    prints them (the metadata JSON over several lines)."""
    lines = []
    for name, (op, meta) in table.items():
        attrs = ""
        if meta is not None:
            body = ",\n".join(f'"{k}":{v}' for k, v in sorted(meta.items()))
            attrs += ", frontend_attributes={kernel_metadata={\n" + body + \
                "\n}}"
        if op:
            attrs += f', metadata={{op_name="{op}" stack_frame_id=1}}'
        lines.append(f"  %{name} = f32[8]{{0}} custom-call(){attrs}")
    return "ENTRY %main {\n" + "\n".join(lines) + "\n}\n"


def _read(metric, ctx):
    return H.load_module("metrics", metric).read(ctx)


@pytest.fixture
def step_ctx():
    return _Ctx(STEP, STEP_HLO)


def test_text_parse_reads_names_scopes_and_kernel_work():
    table = {"flash_bwd.dq.3": ("jit(step)/attn/flash_bwd.dq/pallas_call",
                                {"executed": 540, "launched": 960}),
             "fusion.2": ("jit(step)/optimizer/mul", None),
             "copy.1": ("", None)}
    assert scopes.instructions(_text(table)) == table


def test_a_fusion_takes_the_scope_of_its_computation():
    text = """%fused_computation.7 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(step)/jvp(head)/exp"}
  %mul.2 = f32[8]{0} multiply(%exp.1, %param_0), metadata={op_name="jit(step)/transpose(jvp(head))/mul"}
  ROOT %bitcast.3 = f32[8]{0} bitcast(%mul.2)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.7
}
"""
    table = scopes.instructions(text)
    assert table["fusion.9"] == ("jit(step)/transpose(jvp(head))/mul", None)
    assert table["p"] == ("", None)


def test_every_traced_op_of_the_step_is_in_its_compiled_text():
    names = {e[0] for v in STEP["devices"].values() for e in v}
    assert names and names <= set(scopes.instructions(STEP_HLO))


def test_an_op_missing_from_the_compiled_text_stops_the_reading():
    ev = {"host": [["bench.dispatch", 0, 100]],
          "devices": {"0": [["fusion.1", "fusion", 0, 10],
                            ["fusion.2", "fusion", 20, 10]]}}
    ctx = _Ctx(ev, _text({"fusion.1": ("jit(step)/jvp()/dot", None)}))
    with pytest.raises(H.BenchError, match="fusion.2"):
        _read("train_recompute_share", ctx)


def test_flash_grid_useful_from_each_calls_own_counts(step_ctx):
    got = _read("flash_grid_useful", step_ctx)
    # causal, 8 × 8 blocks: 36 of 64 grid steps per (batch, head)
    assert got["value"] == pytest.approx(100 * 36 / 64)
    assert set(got["kernels"]) == {"flash_fwd", "flash_bwd.dq",
                                   "flash_bwd.dkv"}
    for k in got["kernels"].values():
        assert k["events"] == L * S
        assert k["launched"] == L * S * BH * NQ * NQ
        assert k["executed"] == L * S * BH * 36
        assert k["seconds"] > 0
        assert k["ns_per_launched_step"] == pytest.approx(
            1e9 * k["seconds"] / k["launched"])


def test_recompute_share_and_the_phase_split(step_ctx):
    got = _read("train_recompute_share", step_ctx)
    s = step_ctx.trace_summary
    assert 0 < got["value"] < 100
    secs = got["seconds"]
    assert got["value"] == pytest.approx(100 * secs["recompute"] /
                                         got["busy_s"])
    assert got["busy_s"] == pytest.approx(s.busy_s)
    assert all(v > 0 for v in secs.values()), secs
    # every op of the window is placed once: the phases and the
    # unattributed rest add up to the summed op time
    total = sum(scopes.clipped_s(e, s.lo, s.hi) for e in s.ops("0"))
    assert sum(secs.values()) + got["unattributed_s"] == pytest.approx(total)
    # what no rule places is the compiler's own data movement, and the
    # few ops it made with no scope in their text or their computation
    table = scopes.instructions(STEP_HLO)
    for e in scopes.window_ops(step_ctx)["0"]:
        if scopes.phase(e[4]) is None and e[1] not in MOVERS:
            assert e[1] in ("fusion", "custom-call"), e
            line = next(l for l in STEP_HLO.splitlines()
                        if l.lstrip().startswith(f"%{e[0]} = "))
            assert "op_name=" not in line and "tpu_custom_call" not in line
            assert table[e[0]] == ("", None)
    # the remat-aware step recomputes no attention forward
    assert got["attn_seconds"]["recompute"] == 0
    assert got["attn_seconds"]["forward"] > 0
    assert got["head_seconds"]["forward"] > 0
    assert got["head_seconds"]["backward"] > 0


def test_no_flash_forward_is_recomputed(step_ctx):
    ops = scopes.window_ops(step_ctx)
    fwd = [e for e in ops["0"] if scopes.kernel(e[0]) == "flash_fwd"]
    assert len(fwd) == L * S
    assert {scopes.phase(e[4]) for e in fwd} == {"forward"}


def test_readers_leave_out_a_program_without_names():
    """The first recorded trace is of a program that names nothing: each
    new reader finds nothing to read, and the old reduction reads as it
    did."""
    names = {e[0] for v in PROBE["devices"].values() for e in v}
    ctx = _Ctx(PROBE, _text({n: ("", None) for n in names}))
    for metric in ("flash_grid_useful", "train_recompute_share"):
        assert _read(metric, ctx) is None
    s = ctx.trace_summary
    assert s.kernel_s(r"flash_bwd\b")[1] == 6
    assert [g[0] for g in s.idle_gaps(3)] == ["bench.host_sleep"] * 3


def test_step_text_of_a_tiny_cell_names_every_phase(make_ctx):
    ctx = make_ctx("smollm-360m.train-8k")
    table = scopes.instructions(scopes.step_text(ctx))
    phases = {scopes.phase(op) for op, _ in table.values()}
    assert set(scopes.PHASES) <= phases


def test_step_text_is_the_step_the_driver_runs(make_ctx):
    """The step lowered from abstract arguments names every instruction
    as the driver's own step, lowered from its weights, does."""
    ctx = make_ctx("smollm-360m.train-8k")
    drv = H.load_module("drivers", "train")
    tr, _p_sh, b_sh, rows = drv.build(ctx)
    batch = drv.put(rows[:1], b_sh)[0]
    text = tr.step.lower(tr.params, tr.opt, batch).compile().as_text()
    assert scopes.instructions(scopes.step_text(ctx)) == \
        scopes.instructions(text)

