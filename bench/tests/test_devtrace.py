"""The reduction from a profiler trace and host spans to device numbers,
on a small recorded trace and on synthetic planes with known answers."""
import time

import pytest

import devtrace
import harness

MS = 1e6   # ns


def _planes():
    """One device with two fused executions (ops inside them) and one
    other program; host anchor at profiler time 1000 ms."""
    dev = [("/device:TPU:0", {
        "XLA Modules": [("jit_run_fused(1)", 1100 * MS, 1200 * MS),
                        ("jit_other(2)", 1300 * MS, 1310 * MS),
                        ("jit_run_fused(1)", 1500 * MS, 1600 * MS)],
        "XLA Ops": [("fusion.1", 1100 * MS, 1150 * MS),
                    ("while.2", 1150 * MS, 1200 * MS),
                    ("copy.3", 1300 * MS, 1310 * MS),
                    ("fusion.1", 1500 * MS, 1600 * MS)],
    })]
    host = [("/host:CPU", {"python": [(devtrace.ANCHOR, 1000 * MS,
                                       1000 * MS)]})]
    return dev, host


def test_reduce_known_numbers():
    dev, host = _planes()
    # monotonic 50.0 s is profiler 1000 ms; window 50.0 .. 50.8 s
    spans = [{"name": "search.oracle", "t_start": 50.20, "t_end": 50.29},
             {"name": "segment", "t_start": 50.61, "t_end": 50.70}]
    r = devtrace.reduce(dev, host, 50.0, 50.0, 50.8, spans)
    assert r["window_s"] == pytest.approx(0.8)
    assert r["busy_s"] == pytest.approx(0.21)
    assert r["fused_s"] == pytest.approx(0.2)
    assert r["fused_runs"] == 2
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(0.15)]
    assert [n for n, _ in r["top_ops"]] == ["fusion.1", "while.2"]
    # idle: 1000-1100 (no span), 1200-1300 (replay), 1310-1500 (no
    # span), 1600-1800 (a segment), longest first
    got = [(n, round(s, 6)) for n, s in r["gaps"]]
    assert got[:2] == [("segment", 0.2), ("unattributed", 0.19)]
    assert sorted(got[2:]) == [("search.oracle", 0.1),
                               ("unattributed", 0.1)]


def test_idle_share_reads_from_the_trace():
    dev, host = _planes()
    run = harness.Run(setup_s=0, t_open=50.0, t_close=50.8, completions=[])
    run.trace = devtrace.reduce(dev, host, 50.0, 50.0, 50.8, [])
    share = harness.metric_reader("device_idle_share").read(run)
    assert share == pytest.approx(1 - 0.21 / 0.8)


def test_fused_per_member_step_counts_dispatched_work():
    dev, host = _planes()
    spans = [{"name": "search.fused_dispatch", "t_start": 50.05,
              "t_end": 50.06, "events": [],
              "attrs": {"population": 4, "n_full": 2, "rem": 1}}]
    run = harness.Run(setup_s=0, t_open=50.0, t_close=50.8, completions=[],
                      spans=spans, params={"round_every": 10,
                                           "n_start_points": 4,
                                           "steps": 21})
    run.trace = devtrace.reduce(dev, host, 50.0, 50.0, 50.8, spans)
    got = harness.metric_reader("fused_us_per_member_step").read(run)
    assert got == pytest.approx(1e6 * 0.2 / (4 * 21))


def test_recorded_trace_has_the_anchor(tmp_path):
    """A real profiler trace (CPU): the anchor ties the monotonic clock
    to the profiler's, and a host annotation lands where it ran."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(128)
    f(x).block_until_ready()
    devtrace.start(str(tmp_path))
    t_anchor = devtrace.clock_anchor()
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.probe"):
        time.sleep(0.05)
    t1 = time.monotonic()
    devtrace.stop()
    data = devtrace.load(str(tmp_path))
    _, host = devtrace.planes_of(data)
    off = devtrace.anchor_offset_ns(host, t_anchor)
    assert off is not None
    probe = [(e.start_ns, e.start_ns + e.duration_ns)
             for plane in data.planes for line in plane.lines
             for e in line.events if e.name == "bench.probe"]
    assert len(probe) == 1
    a, b = probe[0]
    assert (a - off) / 1e9 == pytest.approx(t0, abs=2e-3)
    assert (b - off) / 1e9 == pytest.approx(t1, abs=2e-3)
