#!/usr/bin/env python3
"""Readings that set the limits of `correct`, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 --mode sound|control|frozen-gd [--out FILE]

For each seed it runs the cell as `bench/run.py` does (set-up, window,
the reference's judgement), all seeds in one process so the programs
load once, and prints the numbers compared beside their limits and
whether the run came out `correct`:

* `sound`: the program as it is (the lower readings);
* `control`: the reference computed in float32, the precision below
  the configuration's float64, put in the program's place: every
  answer's EDP is the control's (`run.as_control`); it has to come
  out not correct.  The program's own numbers from the same window
  are printed beside it (`program_checks`);
* `frozen-gd`: a fault planted first: every GD sub-scan of the fused
  program returns its state unchanged.

The benchmark's own runs never run this script.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
import run as bench_run  # noqa: E402


def freeze_gd() -> None:
    """Every Adam sub-scan of the fused program returns theta as it
    got it (planted before any engine is built)."""
    from repro.core import search

    def frozen(pop_grad, lr, theta, args, n_steps):
        return theta
    search._adam_scan = frozen


def readings(cell: str, seed: int, seconds: float, mode: str,
             out_dir: pathlib.Path) -> dict:
    keep: dict = {}
    line = bench_run.run_cell(cell, seed, seconds, False, out_dir,
                              keep=keep, control=mode == "control")
    row = {"seed": seed, "mode": mode, "correct": line["correct"],
           "answers": len(keep["answers"]),
           "edps": [a["best_edp"] for a in keep["answers"] if a["ok"]],
           "checks": line["checks"]}
    if mode == "control":
        judged = bench_run.judge_answers(keep["config"],
                                         keep["program_answers"])
        lim = harness.limits()
        row["program_checks"] = harness.decide(judged, len(judged), lim)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--mode", choices=("sound", "control", "frozen-gd"),
                    default="sound")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = bench_run.device_info()
    if dev["platform"] != "tpu":
        print("control: no TPU; nothing run", file=sys.stderr)
        return 1
    bench_run.enable_cache()
    if args.mode == "frozen-gd":
        freeze_gd()
    out_dir = harness.ROOT / ".bench_run" / ("control-" + args.workload)
    for s in args.seeds.split(","):
        row = dict(readings(args.workload, int(s), args.seconds, args.mode,
                            out_dir), device=dev)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
