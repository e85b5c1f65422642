"""Everything is found by name: a new cell, configuration or per-layer
metric is new files plus entries in BENCHMARK.json, with no edit."""
import json
import shutil

import pytest

import harness as H


def test_every_cell_and_metric_of_the_spec_has_its_files():
    spec = H.load_spec()
    for w in spec["workloads"]:
        wl = H.load_workload(w["name"])
        H.load_config(w["config"])
        H.load_module("drivers", wl["driver"])
    for m in spec["per_layer"]:
        assert hasattr(H.load_module("metrics", m["name"]), "read")
    for c in spec["configs"]:
        assert (H.ROOT / c["file"]).is_file()


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of bench/ and BENCHMARK.json to add files to."""
    root = tmp_path / "repo"
    shutil.copytree(H.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(H.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    monkeypatch.setattr(H, "ROOT", root)
    monkeypatch.setattr(H, "BENCH", root / "bench")
    return root


def test_a_new_workload_file_is_found_by_name(bench_copy):
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]
    new = dict(cell, name=cell["name"] + "-copy")
    spec["workloads"].append(new)
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    src = bench_copy / "bench" / "workloads" / f"{cell['name']}.json"
    (bench_copy / "bench" / "workloads" / f"{new['name']}.json").write_text(
        src.read_text())
    import run
    ctx = run.make_ctx(type("A", (), dict(workload=new["name"], seed=1,
                                          seconds=1.0, trace=0))())
    assert ctx.workload["name"] == new["name"]
    assert ctx.workload["driver"] == H.load_workload(cell["name"])["driver"]


def test_a_new_metric_file_is_read_by_name(bench_copy, make_ctx):
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]["name"]
    spec["per_layer"] = [{"name": "steps_seen", "unit": "steps",
                          "better": "higher", "source": "host_clock",
                          "layer": "host step loop", "moves": "setup_s",
                          "workloads": [cell]}]
    (bench_copy / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return {'value': ctx.counts['steps']}\n")
    ctx = make_ctx(cell)
    ctx.spec = spec
    ctx.counts["steps"] = 7
    assert H.per_layer_metrics(ctx) == {
        "steps_seen": {"value": 7, "unit": "steps"}}


def test_a_reader_with_nothing_to_read_leaves_its_metric_out(make_ctx):
    spec = H.load_spec()
    ctx = make_ctx(spec["workloads"][0]["name"])
    assert H.per_layer_metrics(ctx) == {}     # no trace, no counts


def test_unknown_device_kind_is_an_error():
    with pytest.raises(H.BenchError):
        H.peaks_for("TPU v99")


def test_no_tpu_means_no_result_line(capsys):
    import run
    cell = H.load_spec()["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def test_seeds_past_32_bits_give_distinct_streams():
    import numpy as np
    a = H.np_rng(2**31 + 5).integers(0, 1 << 30, 4)
    b = H.np_rng(2**31 + 6).integers(0, 1 << 30, 4)
    assert not np.array_equal(a, b)
    assert H.seed_words(2**33 + 3) != H.seed_words(3)
