"""``sp_ring_exposed_comm``: the ring exchange's exposed time, read from a
trace joined with the step's compiled text. On hand-made events (two ring
collectives, one hidden behind compute and one not), and on a recorded,
trimmed trace of one step of ``qwen3-8b.train-sp4-32k`` on four TPU v5
lite chips (``data/sp4_trace.json.gz``) with that step's compiled text
from the same run (``data/sp4_hlo.txt.gz``)."""
import gzip
import json
import re
from pathlib import Path

import pytest

import devtrace as T
import harness as H
import scopes

DATA = Path(__file__).parent / "data"
READER = H.load_module("metrics", "sp_ring_exposed_comm")
RING = "jit(step)/jvp()/while/body/closed_call/attn/shard_map"
SP4 = json.loads(gzip.decompress((DATA / "sp4_trace.json.gz").read_bytes()))
SP4_HLO = gzip.decompress((DATA / "sp4_hlo.txt.gz").read_bytes()).decode()
L = 4                        # qwen3-8b's layers: each permute runs once each


class _Ctx:
    """What the reader needs of a run: the summary, the step's text and
    the steps in the window."""

    def __init__(self, events, text, steps=1):
        self.trace_summary = T.Summary.of(events)
        self.counts = {"steps": steps}
        scopes._TEXT[id(self)] = text


def _line(name, typ, opcode, op_name=""):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = {typ} {opcode}(f32[8]{{0}} %p){meta}"


HAND_TEXT = "ENTRY %main {\n" + "\n".join([
    _line("p", "f32[8]{0}", "parameter"),
    _line("fusion.1", "f32[8]{0}", "fusion", "jit(step)/jvp()/dot_general"),
    _line("fusion.2", "f32[8]{0}", "fusion", "jit(step)/jvp()/dot_general"),
    _line("collective-permute-start.1",
          "(bf16[2,4]{1,0}, bf16[2,4]{1,0}, u32[], u32[])",
          "collective-permute-start", f"{RING}/ring.fwd/ppermute"),
    _line("collective-permute-done.1", "bf16[2,4]{1,0}",
          "collective-permute-done", f"{RING}/ring.fwd/ppermute"),
    _line("collective-permute-start.2",
          "(f32[4]{0}, f32[4]{0}, u32[], u32[])",
          "collective-permute-start", f"{RING}/transpose(ring.bwd)/ppermute"),
    _line("collective-permute-done.2", "f32[4]{0}",
          "collective-permute-done", f"{RING}/transpose(ring.bwd)/ppermute"),
    _line("all-reduce.3", "bf16[8]{0}", "all-reduce",
          "jit(step)/transpose(jvp())/dot_general"),
    _line("all-reduce.4", "f32[8]{0}", "all-reduce"),
]) + "\n}\n"


def _hand_events():
    """Device 0 runs one op at a time, as a chip's ops line does: compute
    everywhere but at the collectives' own ops. The ring.fwd permute is in
    flight 10..61 and hidden but for its start and done ops (1 ns each).
    The ring.bwd permute is in flight 70..130: its start op is exposed,
    and 120..130 where its done op waits; 71..100 is hidden, and 100..120
    belongs to the gradient all-reduce that runs then. An unplaced
    all-reduce runs 130..140. Device 1 exposes less of its ring."""
    def op(name, start, dur):
        return [name, re.sub(r"\.\d+$", "", name), start, dur]
    return {"host": [["bench.dispatch", 0, 200]],
            "devices": {
                "0": [op("fusion.1", 0, 10),
                      op("collective-permute-start.1", 10, 1),
                      op("fusion.1", 11, 49),
                      op("collective-permute-done.1", 60, 1),
                      op("fusion.1", 61, 9),
                      op("collective-permute-start.2", 70, 1),
                      op("fusion.1", 71, 29),
                      op("all-reduce.3", 100, 20),
                      op("collective-permute-done.2", 120, 10),
                      op("all-reduce.4", 130, 10),
                      op("fusion.2", 140, 60)],
                "1": [op("fusion.1", 0, 70),
                      op("collective-permute-start.2", 70, 1),
                      op("fusion.1", 71, 44),
                      op("collective-permute-done.2", 115, 5),
                      op("fusion.2", 120, 80)]},
            "async": {
                "0": [op("collective-permute-start.1", 10, 50),
                      op("collective-permute-start.2", 70, 60)],
                "1": [op("collective-permute-start.2", 70, 50)]}}


def test_one_hidden_and_one_exposed_ring_collective_by_hand():
    got = READER.read(_Ctx(_hand_events(), HAND_TEXT, steps=2))
    assert got["device"] == "0"
    assert got["exposed_s"] == pytest.approx(13e-9)
    assert got["value"] == pytest.approx(100 * 13 / 200)
    assert got["ring.fwd"] == {"in_flight_s": pytest.approx(51e-9),
                               "exposed_s": pytest.approx(2e-9),
                               "bytes_per_step": 16 / 2}
    assert got["ring.bwd"] == {"in_flight_s": pytest.approx(40e-9),
                               "exposed_s": pytest.approx(11e-9),
                               "bytes_per_step": 16 / 2}
    assert got["ring.home"]["in_flight_s"] == 0
    assert got["other"] == {"in_flight_s": pytest.approx(20e-9),
                            "exposed_s": pytest.approx(20e-9)}
    assert got["unplaced_s"] == pytest.approx(10e-9)
    # every instant of 10..61 and 70..140 went to exactly one class
    assert got["collective_s"] == pytest.approx(121e-9)


@pytest.mark.parametrize("async_on, want", [
    (("0", "1"), "1"),
    (("0",), "0"),
    ((), "1"),
])
def test_the_worst_device_is_read_where_the_trace_has_async_lines(
        async_on, want):
    """Device 1 waits 30 ns on its ring.bwd done op, more than device 0
    exposes; without its async line, device 1 is compared only when no
    device has one."""
    ev = _hand_events()
    ev["devices"]["1"][2:4] = [
        ["fusion.1", "fusion", 71, 19],
        ["collective-permute-done.2", "collective-permute-done", 90, 30]]
    ev["async"] = {d: ev["async"][d] for d in async_on}
    got = READER.read(_Ctx(ev, HAND_TEXT))
    assert got["device"] == want
    assert got["exposed_s"] == pytest.approx(
        {"0": 13e-9, "1": 31e-9}[want])


@pytest.mark.parametrize("text, want", [
    ("%cp = (bf16[1,8192,8,128]{3,1,2,0:T(8,128)(2,1)}, "
     "bf16[1,8192,8,128]{3,1,2,0}, u32[]{:S(2)}, u32[]{:S(2)}) "
     "collective-permute-start(%copy.3), channel_id=1",
     2 * 8192 * 8 * 128),
    ("%cp.2 = ((f32[4]{0}, bf16[2]{0}), (f32[4]{0}, bf16[2]{0}), u32[], "
     "u32[]) collective-permute-start(%a, %b)", 4 * 4 + 2 * 2),
    ("%cp.3 = f32[1,16]{1,0} collective-permute(f32[1,16]{1,0} %x), "
     "source_target_pairs={{0,1},{1,0}}", 64),
    ("%cp-done = f32[4]{0} collective-permute-done(%cp.2)", None),
])
def test_bytes_a_permute_sends_from_its_compiled_shapes(text, want):
    name = text[1:].split(" = ", 1)[0]
    assert READER.permute_bytes(text).get(name) == want


def test_nothing_to_read_on_one_chip():
    step = json.loads((DATA / "step_trace.json").read_text())
    text = gzip.decompress((DATA / "step_hlo.txt.gz").read_bytes()).decode()
    assert READER.read(_Ctx(step, text)) is None


def test_nothing_to_read_where_no_collective_has_a_ring_scope():
    """The program before the ring was scoped: the same events, no
    ``ring.*`` in the text."""
    text = re.sub(r"(transpose\()?ring\.\w+\)?/", "", HAND_TEXT)
    assert "ring." not in text
    assert READER.read(_Ctx(_hand_events(), text)) is None


@pytest.fixture(scope="module")
def sp4():
    ctx = _Ctx(SP4, SP4_HLO, steps=1)
    return ctx, READER.read(ctx)


def test_the_recorded_step_splits_collective_time_over_the_classes(sp4):
    ctx, got = sp4
    assert 0 < got["value"] < 100
    parts = sum(got[k]["in_flight_s"] for k in (*READER.RING, "other"))
    assert parts + got["unplaced_s"] == pytest.approx(got["collective_s"])
    # ... which is all the time some collective is in flight there
    s, d = ctx.trace_summary, got["device"]
    ivs = [(e[2], e[2] + e[3]) for e in s.ops(d) + SP4["async"][d]
           if READER.is_collective(e)]
    assert got["collective_s"] == pytest.approx(
        T.measure(T.union(T.clip(ivs, s.lo, s.hi))) / 1e9)
    for k in (*READER.RING, "other"):
        assert 0 <= got[k]["exposed_s"] <= got[k]["in_flight_s"], k
    assert got["exposed_s"] == pytest.approx(
        sum(got[k]["exposed_s"] for k in READER.RING))
    assert got["other"]["in_flight_s"] > 0        # the gradient all-reduce


def test_every_permute_of_the_recorded_step_sits_in_a_ring_scope(sp4):
    _, got = sp4
    table = scopes.instructions(SP4_HLO)
    perms = [e for line in ("devices", "async") for evs in SP4[line].values()
             for e in evs if e[1].startswith("collective-permute")]
    assert perms
    assert {READER.ring_class(table[e[0]][0]) for e in perms} == set(
        READER.RING)
    assert got["unplaced_s"] == 0


def test_bytes_a_recorded_step_ships_per_ring_scope(sp4):
    """Every permute sits in the scanned layer, so a step ships each
    one's operand once per layer."""
    _, got = sp4
    table = scopes.instructions(SP4_HLO)
    want = dict.fromkeys(READER.RING, 0)
    for name, n in READER.permute_bytes(SP4_HLO).items():
        want[READER.ring_class(table[name][0])] += L * n
    for k in READER.RING:
        assert got[k]["bytes_per_step"] == want[k] > 0
