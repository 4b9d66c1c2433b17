"""The readers of the served path's phase spans and the replay counts,
each on hand-made spans with a known answer, and each giving nothing
where the program records no such span or count."""
import pytest

import harness
from harness import Completion, Run

READERS = ("start_gen_ms", "oracle_us_per_candidate", "checkpoint_ms",
           "lock_wait_p90_ms", "other_steps_ms")


def read(name, run):
    return harness.metric_reader(name).read(run)


def _span(name, t0, t1, events=(), **attrs):
    return {"name": name, "t_start": t0, "t_end": t1, "attrs": attrs,
            "events": list(events)}


def _join(t, task_id):
    return (t, "batch_join", {"task_id": task_id, "batch_size": 1})


def _phase_run():
    """A served window 10 .. 30 s: two tasks' steps and their phases,
    two requests and the front-end's lock waits."""
    spans = [
        _span("search.starts", 1.0, 2.0, n=7, tries=7),      # before
        _span("search.starts", 10.0, 10.2, n=7, tries=9),
        _span("search.starts", 12.0, 12.4, n=7, tries=7),
        _span("search.oracle", 9.0, 11.0),                   # no count
        _span("search.oracle", 14.0, 14.5, candidates=100, improved=1),
        _span("search.oracle", 15.0, 15.25, candidates=150, improved=0),
        _span("service.step", 11.0, 11.5, task_id="a", segment=0),
        _span("service.step", 12.0, 12.5, task_id="b", segment=0),
        _span("service.step", 13.0, 13.5, task_id="a", segment=1),
        _span("service.step", 40.0, 40.5, task_id="a", segment=2),
        _span("checkpoint.restore", 11.0, 11.01),
        _span("checkpoint.save", 11.1, 11.15),
        _span("checkpoint.gc", 13.4, 13.44),
        _span("checkpoint.save", 40.1, 40.2),                # after
        _span("request", 10.5, 13.6, [
            _join(10.9, "a"),
            (12.0, "dedup_hit", {"alias": "r1b", "lock_wait_s": 0.002}),
            (14.0, "delivered", {"request_id": "r1",
                                 "lock_wait_s": 0.004}),
        ], lock_wait_s=0.001),
        _span("request", 10.6, 12.6, [_join(11.9, "b")],
              lock_wait_s=0.003),
        _span("request", 5.0, 6.0, [                          # before
            _join(5.5, "z"),
            (5.9, "delivered", {"request_id": "r0", "lock_wait_s": 9.0}),
        ], lock_wait_s=9.9),
        _span("request", 29.0, None, [_join(29.5, "c")],      # open
              lock_wait_s=0.0005),
    ]
    return Run(setup_s=1.0, t_open=10.0, t_close=30.0, completions=[],
               spans=spans)


def test_phase_readers_known_numbers():
    run = _phase_run()
    assert read("start_gen_ms", run) == pytest.approx(300.0)
    assert read("oracle_us_per_candidate", run) == pytest.approx(
        1e6 * 0.75 / 250)
    # (10 + 50 + 40) ms of checkpoints over the window's 3 steps
    assert read("checkpoint_ms", run) == pytest.approx(100.0 / 3)
    # waits 0.5, 1, 2, 3, 4 ms in the window: nearest-rank p90 is 4
    assert read("lock_wait_p90_ms", run) == pytest.approx(4.0)
    # request 1 waits out task b's 0.5 s step, request 2 nothing
    assert read("other_steps_ms", run) == pytest.approx(250.0)


def test_phase_readers_leave_out_the_gap():
    """The profiler's export (the gap) is left out: spans that touch
    it, requests whose wait touches it, and waits recorded in it."""
    run = _phase_run()
    run.gap = (11.8, 12.55)   # the second start generation, b's step
    assert read("start_gen_ms", run) == pytest.approx(200.0)
    assert read("checkpoint_ms", run) == pytest.approx(100.0 / 2)
    assert read("other_steps_ms", run) is None    # both requests touch it
    # the dedup_hit's 2 ms at 12.0 falls in it: 0.5, 1, 3, 4 ms left
    assert read("lock_wait_p90_ms", run) == pytest.approx(4.0)
    run.gap = (13.55, 13.58)  # request 1's finalize
    assert read("other_steps_ms", run) == pytest.approx(0.0)


@pytest.mark.parametrize("name", READERS)
def test_phase_readers_give_nothing_without_their_spans(name):
    """Nothing to read (no spans), or spans of a program without phase
    spans, replay counts and lock waits: no number."""
    empty = Run(setup_s=1.0, t_open=0.0, t_close=1.0, completions=[])
    assert read(name, empty) is None
    comps = [Completion(t_submit=10.0, t_done=11.0, samples=1, ok=True)]
    older = Run(setup_s=1.0, t_open=10.0, t_close=30.0, completions=comps,
                spans=[_span("request", 10.0, 12.0, [_join(10.5, "a")]),
                       _span("queue_wait", 10.0, 10.5),
                       _span("segment", 10.5, 12.0, task_id="a",
                             segment=0),
                       _span("search.oracle", 11.0, 11.5, segment=0),
                       _span("checkpoint.save", 11.6, 11.7)])
    assert read(name, older) is None
