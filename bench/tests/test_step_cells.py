"""The cells of whole-network requests: the reader of the start cost
per layer and try on hand-made spans with a known answer, and giving
nothing where the program records no such attr; the Kimi-K2
decode-step mix from `--seed`; and the Kimi cell end to end at a tiny
protocol, its float32 control not correct."""
import collections
import json

import pytest

import harness
import run as bench_run
from harness import Run

STEP = "tpuv5e-kimik2-decode.serve-step"


def read(name, run):
    return harness.metric_reader(name).read(run)


def _span(name, t0, t1, **attrs):
    return {"name": name, "t_start": t0, "t_end": t1, "attrs": attrs,
            "events": []}


def _run(spans, gap=None):
    return Run(setup_s=1.0, t_open=10.0, t_close=30.0, completions=[],
               spans=spans, gap=gap)


def test_start_us_per_layer_try():
    spans = [
        _span("search.starts", 11.0, 11.7, n=7, tries=7, layers=14),
        _span("search.starts", 12.0, 12.35, n=7, tries=10, layers=7),
        _span("search.starts", 1.0, 5.0, n=7, tries=7, layers=14),  # before
        _span("search.starts", 13.0, 14.0, n=7, tries=7),     # no layers
        _span("search.starts", 15.0, 16.0, n=7, layers=3),    # no tries
    ]
    want = 1e6 * (0.7 + 0.35) / (7 * 14 + 10 * 7)
    assert read("start_us_per_layer_try", _run(spans)) == pytest.approx(want)


def test_reader_leaves_out_the_gap():
    spans = [_span("search.starts", 11.0, 12.0, tries=1, layers=1),
             _span("search.starts", 20.5, 21.5, tries=1, layers=1)]
    run = _run(spans, gap=(20.0, 22.0))
    assert read("start_us_per_layer_try", run) == pytest.approx(1e6)


def test_nothing_without_the_attrs():
    """The spans a program without the `layers` attr records, as the
    parent of this change does: the reader gives nothing and does not
    raise."""
    spans = [_span("request", 11.0, 12.0, request_id="r", workload="w",
                   priority=0),
             _span("search.starts", 11.0, 11.5, n=7, tries=7)]
    name = "start_us_per_layer_try"
    assert read(name, _run(spans)) is None
    assert read(name, Run(setup_s=1.0, t_open=0.0, t_close=1.0,
                          completions=[])) is None


def _traffic(cell, seed, tmp_path):
    bm = harness.benchmark()
    entry = harness.cell_entry(bm, cell)
    mix = harness.traffic_mix(entry["traffic"])
    cfg = harness.config_for(bm, entry["config"])
    return harness.driver(mix["driver"]).Traffic(
        cfg, mix, seed, tmp_path, lambda msg: None), mix


def test_decode_step_mix_from_seed(tmp_path):
    """Every block of 6 holds 4 decode32k steps and 2 decode128k ones,
    in an order and with search seeds drawn from `--seed`; each request
    is a whole 13-GEMM decode step on TPU v5e."""
    big = 2**31 + 4321
    a, mix = _traffic(STEP, big, tmp_path)
    b, _ = _traffic(STEP, big, tmp_path)
    c, _ = _traffic(STEP, big + 1, tmp_path)
    assert json.dumps(a.payloads) == json.dumps(b.payloads)
    assert json.dumps(a.payloads) != json.dumps(c.payloads)
    for t in (a, c):
        for blk in range(mix["blocks"]):
            names = collections.Counter(
                p["workload"]["name"]
                for p in t.payloads[blk * 6:(blk + 1) * 6])
            assert names == {"decode32k": 4, "decode128k": 2}
    seeds = [p["config"]["seed"] for p in a.payloads]
    assert len(set(seeds)) == len(seeds)
    for p in a.payloads:
        assert len(p["workload"]["layers"]) == 13
        assert p["config"]["spec"] == "tpu_v5e"


@pytest.fixture
def tiny_step(monkeypatch):
    """The Kimi cell at a tiny protocol, one workload, short waits."""
    config_for, traffic_mix = harness.config_for, harness.traffic_mix

    def config(bm, name):
        c = config_for(bm, name)
        c["protocol"].update(steps=4, round_every=2, n_start_points=2)
        return c

    def mix(name):
        return dict(traffic_mix(name), lead_in_s=0.0, poll_s=0.01,
                    popularity=["decode128k"])

    monkeypatch.setattr(harness, "config_for", config)
    monkeypatch.setattr(harness, "traffic_mix", mix)
    drv = harness.driver("http_closed")
    monkeypatch.setattr(harness, "driver", lambda name: drv)
    monkeypatch.setattr(drv, "STALL_S", 60.0)
    monkeypatch.setattr(drv, "ANSWER_WAIT_S", 30.0)


def test_step_cell_correct_and_control_not(tiny_step, tmp_path):
    lim = harness.limits()["edp_gap"]
    sound = bench_run.run_cell(STEP, 2**31 + 11, 3.0, True, tmp_path)
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["edp_gap"]["value"] <= lim
    assert sound["metrics"]["start_us_per_layer_try"]["value"] > 0
    ctrl = bench_run.run_cell(STEP, 2**31 + 11, 3.0, False, tmp_path,
                              control=True)
    assert ctrl["attempted"] > 0
    assert not ctrl["correct"]
    assert ctrl["checks"]["edp_gap"]["value"] > lim
