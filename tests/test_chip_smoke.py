"""`chip_smoke.py` on the CPU: its phases at a tiny budget, and its
refusal to run anywhere but on a TPU."""
import importlib.util
import pathlib

import pytest

from repro.core.problem import Layer, Workload

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small_wl():
    return Workload(layers=(Layer.conv(32, 64, 3, 28, name="c"),
                            Layer.matmul(128, 256, 192, name="m")),
                    name="small")


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""     # no result line


def test_phases_at_tiny_budget(smoke, small_wl, tmp_path):
    budget = {"steps": 4, "round_every": 2, "n_start_points": 2,
              "seed": 1}
    first = smoke.payload(small_wl, "gemmini", budget)
    payloads = [first, smoke.payload(small_wl, "tpu_v5e", budget),
                dict(first),
                smoke.payload(small_wl, "gemmini",
                              dict(budget, steps=2, n_start_points=1))]
    served = smoke.serve_phase(payloads, tmp_path, timeout_s=600)
    recs = served["records"]
    assert [r["deduplicated"] for r in recs] == [False, False, True,
                                                 False]
    assert all(r["seconds"] > 0 for r in recs)
    smoke.check_served(served)
    smoke.check_reference(recs[-1])


def test_check_served_rejects_a_moved_fault_counter(smoke, small_wl,
                                                    tmp_path):
    budget = {"steps": 2, "round_every": 2, "n_start_points": 1,
              "seed": 2}
    served = smoke.serve_phase([smoke.payload(small_wl, "gemmini",
                                              budget)], tmp_path)
    smoke.check_served(served)
    served["stats"]["faults"]["retries"] = 1
    with pytest.raises(AssertionError, match="retries"):
        smoke.check_served(served)


def test_sharded_phase_on_one_device(smoke, small_wl):
    out = smoke.sharded_phase(small_wl, population=4, shards=1, steps=4,
                              round_every=2)
    assert out["members_per_device"] == [(0, 4)]
