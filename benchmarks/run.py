"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Scale via
REPRO_BENCH_SCALE={quick|paper} (default quick); select benchmarks with
``python -m benchmarks.run fig4 fig7 ...``.
"""
from __future__ import annotations

import sys
import traceback

from .common import scale

BENCHES = ("fig4", "fig6", "fig7", "fig8", "fig9", "fig10_11", "fig12",
           "roofline", "tpu_autotune", "multi_target", "fleet", "timing",
           "calibration", "serve", "chaos", "analysis", "obs")

_MODULES = {
    "analysis": "benchmarks.analysis",
    "multi_target": "benchmarks.multi_target",
    "fleet": "benchmarks.fleet",
    "timing": "benchmarks.timing",
    "calibration": "benchmarks.calibration",
    "serve": "benchmarks.serve",
    "chaos": "benchmarks.chaos",
    "obs": "benchmarks.obs",
    "fig4": "benchmarks.fig4_correlation",
    "fig6": "benchmarks.fig6_loop_ordering",
    "fig7": "benchmarks.fig7_cosearch",
    "fig8": "benchmarks.fig8_baseline_accels",
    "fig9": "benchmarks.fig9_hw_map_separation",
    "fig10_11": "benchmarks.fig10_11_pred_accuracy",
    "fig12": "benchmarks.fig12_rtl_opt",
    "roofline": "benchmarks.roofline",
    "tpu_autotune": "benchmarks.tpu_autotune",
}

# Artifacts each benchmark promises to leave in common.OUTPUT_DIR — a
# registered benchmark that "passes" without its artifact is a silent
# reporting regression, so the driver fails the run.
_ARTIFACTS = {
    "analysis": ("analysis_report.json",),
    "multi_target": ("multi_target.json",),
    "fleet": ("fleet.json", "fleet_frontier.csv"),
    "timing": ("search_timing.json",),
    "calibration": ("calibration_metrics.json",),
    "serve": ("serve_metrics.json",),
    "chaos": ("chaos_metrics.json",),
    "obs": ("obs_metrics.json",),
    "fig4": ("fig4.json",),
    "fig6": ("fig6.json",),
    "fig7": ("fig7.json",),
    "fig8": ("fig8.json",),
    "fig9": ("fig9.json",),
    "fig10_11": ("fig10_11.json",),
    "fig12": ("fig12_table7.json",),
    "roofline": ("roofline.json",),
    "tpu_autotune": ("tpu_autotune.json",),
}


def _missing_artifacts(key: str) -> list[str]:
    from .common import OUTPUT_DIR
    return [name for name in _ARTIFACTS.get(key, ())
            if not (OUTPUT_DIR / name).is_file()]


def main() -> None:
    import importlib
    selected = sys.argv[1:] or list(BENCHES)
    unknown = [k for k in selected if k not in _MODULES]
    if unknown:
        sys.exit(f"unknown benchmarks {unknown}; choose from {list(BENCHES)}")
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    sc = scale()
    print(f"# repro benchmarks  scale={sc}")
    print("name,us_per_call,derived")
    failures = []
    for key in selected:
        try:
            mod = importlib.import_module(_MODULES[key])
            for row in mod.run(sc):
                print(row.csv(), flush=True)
            missing = _missing_artifacts(key)
            if missing:
                raise FileNotFoundError(
                    f"benchmark {key!r} completed without writing its "
                    f"declared artifacts {missing}")
        except Exception:
            failures.append(key)
            traceback.print_exc()
            print(f"{key},nan,FAILED", flush=True)
    if failures:
        # Non-zero exit so CI smoke jobs gate on benchmark regressions.
        sys.exit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
