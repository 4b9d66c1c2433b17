"""A whole run on the CPU, past the look for a chip, at a tiny protocol:
sound, it is `correct`; with the timed path broken underneath, it is
not.  One case per fault a cell can have:

* an answer altered where it is produced (the oracle's EDP off by a
  millionth);
* half of a batch left out (every other request of a served batch
  never answered; every other candidate of a sweep not replayed);
* a step that returns its state unchanged (a served segment that does
  not advance; in the sweep, every GD sub-scan of the fused program:
  the search reference's best EDP is then far from the answer's).

No cell spans chips, so there is no exchange between chips to leave
out.
"""
import pytest

import harness
import run as bench_run

SWEEP = "gemmini-dosa4.sweep-p128"
GEMM = "tpuv5e-jamba-decode32k.serve-gemm"
PAPER = "gemmini-dosa4.serve-paper"


def _in_window(monkeypatch, apply):
    """Plant a fault once set-up is done, as the window opens."""
    drv = harness.driver("http_closed")
    real = drv.Traffic.window

    def window(self, seconds, before_open, after_done):
        apply()
        return real(self, seconds, before_open, after_done)
    monkeypatch.setattr(drv.Traffic, "window", window)


def _run(cell, tmp_path, seconds=2.0):
    return bench_run.run_cell(cell, 2**31 + 77, seconds, False, tmp_path)


def _alter_answers(monkeypatch):
    from repro.core import search
    real = search._oracle_edp

    def altered(*args, **kwargs):
        return real(*args, **kwargs) * (1.0 + 1e-6)
    monkeypatch.setattr(search, "_oracle_edp", altered)


@pytest.mark.parametrize("cell", [SWEEP, GEMM, PAPER])
def test_sound_run_is_correct(tiny, tmp_path, cell):
    line = _run(cell, tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert {"samples_per_s", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("cell", [SWEEP, GEMM, PAPER])
def test_altered_answer_is_caught(tiny, tmp_path, monkeypatch, cell):
    _alter_answers(monkeypatch)
    line = _run(cell, tmp_path)
    assert not line["correct"]
    assert line["checks"]["edp_gap"]["value"] > \
        line["checks"]["edp_gap"]["limit"]


@pytest.fixture
def fresh_engines():
    """Engines built in this test are dropped after it, so a fault
    planted in one never reaches another test's runs."""
    from repro.core import search
    search._ENGINE_CACHE.clear()
    yield
    search._ENGINE_CACHE.clear()


def _freeze_gd(monkeypatch):
    from repro.core import search
    monkeypatch.setattr(search, "_adam_scan",
                        lambda pop_grad, lr, theta, args, n_steps: theta)


@pytest.mark.parametrize("frozen", [False, True])
def test_frozen_gd_is_caught(searched, fresh_engines, tmp_path, monkeypatch,
                             frozen):
    if frozen:
        _freeze_gd(monkeypatch)
    line = bench_run.run_cell(SWEEP, 2**31 + 91, 1.0, False, tmp_path)
    gap = line["checks"]["search_gap"]
    assert line["correct"] is not frozen, line["checks"]
    assert (gap["value"] > gap["limit"]) is frozen


def test_half_the_sweep_left_out_is_caught(tiny, tmp_path, monkeypatch):
    from repro.core import search
    real = search._Recorder.record
    calls = {"n": 0}

    def every_other(self, mappings):
        calls["n"] += 1
        if calls["n"] % 2:
            return real(self, mappings)
        return float("inf")
    monkeypatch.setattr(search._Recorder, "record", every_other)
    line = _run(SWEEP, tmp_path)
    assert not line["correct"]
    assert line["checks"]["accounting"]["value"] > 0


@pytest.mark.parametrize("cell", [GEMM, PAPER])
def test_half_a_batch_left_out_is_caught(tiny, tmp_path, monkeypatch,
                                         cell):
    from repro.serve import cosearch_service as cs
    real = cs._BatchTask.final_outcomes

    def half(self):
        return real(self)[::2]
    _in_window(monkeypatch, lambda: monkeypatch.setattr(
        cs._BatchTask, "final_outcomes", half))
    # requests of one workload waiting together form one batch: four
    # clients keep several waiting
    mix = harness.traffic_mix
    monkeypatch.setattr(harness, "traffic_mix",
                        lambda name: dict(mix(name), clients=4))
    line = _run(cell, tmp_path, seconds=3.0)
    assert not line["correct"]
    assert line["checks"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("cell", [GEMM, PAPER])
def test_state_left_unchanged_is_caught(tiny, tmp_path, monkeypatch, cell):
    from repro.serve import cosearch_service as cs

    def stuck(self, fault_hook):
        return None     # the segment runs nothing and keeps its state
    _in_window(monkeypatch, lambda: monkeypatch.setattr(
        cs._BatchTask, "_advance_once", stuck))
    line = _run(cell, tmp_path)
    assert not line["correct"]
    assert line["checks"]["unanswered"]["value"] > 0
