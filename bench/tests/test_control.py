"""The control: the reference computed in float32 put in the program's
place (`run.as_control`).  At a size a test run holds, a sound run of
each cell comes out correct and the control, judged by the same
comparison in `run_cell`, does not (the chip readings that set the
limit are in PERF.md)."""
import pytest

import harness
import run as bench_run


@pytest.mark.parametrize("cell", ["gemmini-dosa4.sweep-p128",
                                  "tpuv5e-jamba-decode32k.serve-gemm"])
def test_control_fails_where_the_program_passes(tiny, tmp_path, cell):
    lim = harness.limits()["edp_gap"]
    sound = bench_run.run_cell(cell, 2**31 + 3, 2.0, False, tmp_path)
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["edp_gap"]["value"] <= lim
    ctrl = bench_run.run_cell(cell, 2**31 + 3, 2.0, False, tmp_path,
                              control=True)
    assert ctrl["attempted"] > 0
    assert not ctrl["correct"]
    assert ctrl["checks"]["edp_gap"]["value"] > lim
