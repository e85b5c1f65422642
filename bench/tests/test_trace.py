"""The trace reduction, on a small trace recorded on a TPU v5 lite (three
steps of a jitted flash forward + backward and two matmuls, each step
wrapped in the benchmark's host spans, with a 10 ms host sleep between
steps) and on hand-made events."""
import json
from pathlib import Path

import numpy as np
import pytest

import devtrace as T

RECORDED = json.loads((Path(__file__).parent / "data" /
                       "probe_trace.json").read_text())


@pytest.fixture
def summary():
    return T.Summary.of(RECORDED)


def test_busy_time_is_the_union_of_device_ops(summary):
    lo, hi = summary.lo, summary.hi
    busy = np.zeros(hi - lo, bool)               # one slot per nanosecond
    for _, _, s, d in RECORDED["devices"]["0"]:
        busy[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
    assert summary.busy_s == pytest.approx(busy.sum() / 1e9, abs=1e-9)
    assert summary.window_s == pytest.approx((hi - lo) / 1e9)
    assert 0 < summary.idle_share() < 1


def test_idle_gaps_are_named_by_the_host_span_they_fall_in(summary):
    gaps = summary.idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench.host_sleep"] * 3
    assert all(g[1] > 0.010 for g in gaps)       # each sleep was 10 ms


def test_kernel_time_sums_the_kernel_events(summary):
    for pattern, name, n in ((r"flash_fwd\b", "flash_fwd", 3),
                             (r"flash_bwd\b", "flash_bwd", 6)):
        secs, events = summary.kernel_s(pattern)
        want = sum(min(s + d, summary.hi) - max(s, summary.lo)
                   for op, _, s, d in RECORDED["devices"]["0"]
                   if op.startswith(name + ".")) / 1e9
        assert events == n
        assert secs == pytest.approx(want)


def test_breakdown_lists_the_kernels_first(summary):
    top = [name for name, _ in summary.breakdown()["device_ops"][:2]]
    assert set(top) == {"flash_fwd", "flash_bwd"}


def test_exposed_collective_time_by_hand():
    ev = {"host": [["bench.dispatch", 0, 100]],
          "devices": {"0": [["fusion.1", "fusion", 0, 10],
                            ["fusion.2", "fusion", 20, 10],
                            ["all-reduce.3", "all-reduce", 50, 5]],
                      "1": [["fusion.1", "fusion", 0, 30]]},
          "async": {"0": [["collective-permute-start.4",
                           "collective-permute-start", 5, 20],
                          ["copy-start.5", "copy-start", 0, 90]],
                    "1": []}}
    s = T.Summary.of(ev)
    # in flight 5..25 and 50..55; compute 0..10, 20..30 (the all-reduce
    # is not compute): exposed 10..20 and 50..55
    assert s.exposed_collective_s("0") == pytest.approx(15e-9)
    assert s.exposed_collective_s("1") == 0


def test_control_flow_ops_that_span_others_do_not_count_twice():
    ev = {"host": [["bench.sync", 0, 100]],
          "devices": {"0": [["while.1", "while", 0, 50],
                            ["fusion.2", "fusion", 0, 20],
                            ["flash_fwd.3", "custom-call", 30, 20]]}}
    s = T.Summary.of(ev)
    assert [e[0] for e in s.ops("0")] == ["fusion.2", "flash_fwd.3"]
    assert s.busy_s == pytest.approx(40e-9)
    assert s.kernel_s(r"flash_fwd\b") == (pytest.approx(20e-9), 1)


@pytest.mark.parametrize("text, want", [
    ("%flash_fwd.7 = (bf16[1,15,8192,64]{3,2,1,0}, f32[1,15,1,8192]{3,2,1,0})"
     " custom-call(bf16[1,15,8192,64]{3,2,1,0} %b.7), custom_call_target=x",
     ("flash_fwd.7", "custom-call")),
    ("%copy.6 = bf16[1,2048,15,64]{3,1,2,0} copy(bf16[1,2048,15,64] %q.1)",
     ("copy.6", "copy")),
    ("%all-reduce-start.2 = f32[960]{0} all-reduce-start(f32[960]{0} %p)",
     ("all-reduce-start.2", "all-reduce-start")),
])
def test_hlo_names_and_opcodes(text, want):
    assert T.parse_hlo(text) == want
