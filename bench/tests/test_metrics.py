"""Each metric reader on a synthetic run with known answers."""
import pytest

import harness
from harness import Completion, Run


def read(name, run):
    return harness.metric_reader(name).read(run)


def _run():
    comps = [Completion(t_submit=10.0 + i, t_done=11.0 + 2 * i,
                        samples=100, ok=True, t_accepted=10.0 + i + 0.01 * i)
             for i in range(10)]
    ev = lambda t, tid, n: (t, "batch_join", {"task_id": tid,  # noqa
                                              "batch_size": n})
    spans = [
        {"name": "queue_wait", "t_start": 10.0, "t_end": 10.5,
         "attrs": {}, "events": []},
        {"name": "queue_wait", "t_start": 12.0, "t_end": 12.1,
         "attrs": {}, "events": []},
        {"name": "queue_wait", "t_start": 1.0, "t_end": 2.0,  # before
         "attrs": {}, "events": []},
        {"name": "request", "t_start": 10.0, "t_end": 30.0, "attrs": {},
         "events": [ev(10.5, "a", 2), ev(12.1, "b", 1)]},
        {"name": "request", "t_start": 10.0, "t_end": 30.0, "attrs": {},
         "events": [ev(10.5, "a", 2)]},
    ]
    for tid, seg, t0, t1 in (("a", 0, 10.5, 12.5), ("a", 1, 12.5, 13.0),
                             ("a", 2, 13.0, 13.5), ("b", 0, 13.5, 14.5)):
        for _ in range(2):   # siblings share the interval
            spans.append({"name": "segment", "t_start": t0, "t_end": t1,
                          "attrs": {"task_id": tid, "segment": seg},
                          "events": []})
    spans.append({"name": "engine.compile", "t_start": 20.0,
                  "t_end": 20.5, "attrs": {}, "events": []})
    spans.append({"name": "search.oracle", "t_start": 9.0, "t_end": 11.0,
                  "attrs": {}, "events": []})
    return Run(setup_s=42.0, t_open=10.0, t_close=30.0, completions=comps,
               spans=spans)


def test_end_to_end_readers():
    run = _run()
    assert read("setup_s", run) == 42.0
    assert read("samples_per_s", run) == pytest.approx(1000 / 20.0)
    # latencies 1, 2, ..., 10 s
    assert read("request_p50_s", run) == pytest.approx(5.0)
    assert read("request_p90_s", run) == pytest.approx(9.0)


def test_layer_readers():
    run = _run()
    # POST times 0, 10, ..., 90 ms
    assert read("submit_p90_ms", run) == pytest.approx(80.0)
    assert read("queue_wait_ms", run) == pytest.approx(300.0)
    assert read("first_segment_s", run) == pytest.approx(1.5)
    assert read("later_segment_s", run) == pytest.approx(0.5)
    assert read("requests_per_task", run) == pytest.approx(1.5)
    assert read("window_compiles", run) == 1
    assert read("oracle_share", run) == pytest.approx(1.0 / 20.0)


def test_readers_return_nothing_without_data():
    empty = Run(setup_s=1.0, t_open=0.0, t_close=1.0, completions=[])
    for name in ("samples_per_s", "request_p50_s", "queue_wait_ms",
                 "first_segment_s", "requests_per_task",
                 "device_idle_share", "fused_us_per_member_step"):
        assert read(name, empty) is None


def test_readers_leave_out_the_gap():
    """A traced run's profiler export is left out of the host readings:
    spans and events that touch it, and its length from the window."""
    run = _run()
    run.gap = (12.05, 12.2)
    assert run.window_s == pytest.approx(19.85)
    assert read("queue_wait_ms", run) == pytest.approx(500.0)
    assert read("requests_per_task", run) == pytest.approx(2.0)
    assert read("oracle_share", run) == pytest.approx(1.0 / 19.85)
    assert read("window_compiles", run) == 1
    assert not run.holds(11.0, 12.1) and run.holds(12.2, 13.0)
