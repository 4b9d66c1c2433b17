"""Cells, configurations, mixes and metrics are found by name from
files, so a later change adds one by adding files only."""
import json
import shutil

import pytest

import harness


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of the benchmark's files, with `harness` pointed at it."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    return root


def test_every_declared_name_resolves():
    bm = harness.benchmark()
    for w in bm["workloads"]:
        cfg = harness.config_for(bm, w["config"])
        mix = harness.traffic_mix(w["traffic"])
        assert cfg["name"] == w["config"]
        assert harness.driver(mix["driver"]).Traffic
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)


def test_new_cell_config_mix_and_metric_from_new_files(bench_copy):
    bm = json.loads((bench_copy / "BENCHMARK.json").read_text())
    cfg = json.loads((bench_copy / "bench/configs/gemmini-dosa4.json")
                     .read_text())
    cfg["name"] = "gemmini-small"
    (bench_copy / "bench/configs/gemmini-small.json").write_text(
        json.dumps(cfg))
    (bench_copy / "bench/traffic/sweep-p8.json").write_text(json.dumps(
        {"driver": "api_sweep", "workload": "bert", "population": 8,
         "start_points": "cosa-device"}))
    (bench_copy / "bench/metrics/window_s.py").write_text(
        "def read(run):\n    return run.window_s\n")
    bm["configs"].append({"name": "gemmini-small", "source": "x",
                          "file": "bench/configs/gemmini-small.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "gemmini-small.sweep-p8",
                            "config": "gemmini-small",
                            "traffic": "sweep-p8", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "window_s", "unit": "s",
                            "better": "lower", "source": "host_clock",
                            "layer": "device", "moves": "samples_per_s"})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bm))

    bm = harness.benchmark()
    entry = harness.cell_entry(bm, "gemmini-small.sweep-p8")
    assert harness.config_for(bm, entry["config"])["name"] == \
        "gemmini-small"
    mix = harness.traffic_mix(entry["traffic"])
    assert mix["population"] == 8
    names = [m["name"] for m in
             harness.metrics_for(bm, "gemmini-small.sweep-p8", trace=True)]
    assert "window_s" in names
    run = harness.Run(setup_s=1.0, t_open=2.0, t_close=5.0, completions=[])
    assert harness.metric_reader("window_s").read(run) == 3.0


def test_metrics_for_respects_workload_lists():
    bm = harness.benchmark()
    sweep = "gemmini-dosa4.sweep-p128"
    e2e = {m["name"] for m in harness.metrics_for(bm, sweep, False)}
    assert e2e == {"samples_per_s", "setup_s"}
    gemm = "tpuv5e-jamba-decode32k.serve-gemm"
    e2e = {m["name"] for m in harness.metrics_for(bm, gemm, False)}
    assert e2e == {"samples_per_s", "setup_s", "request_p50_s",
                   "request_p90_s"}
    layer = {m["name"] for m in harness.metrics_for(bm, sweep, True)}
    assert "oracle_share" in layer and "submit_p90_ms" not in layer


def test_unknown_names_fail_loudly():
    bm = harness.benchmark()
    with pytest.raises(KeyError):
        harness.cell_entry(bm, "no-such-cell")
    with pytest.raises(KeyError):
        harness.metric_reader("no_such_metric")
