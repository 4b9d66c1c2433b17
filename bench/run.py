#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of `BENCHMARK.json`'s `workloads`: a configuration
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<traffic>.json`), whose `driver` names the generator
(`bench/drivers/<driver>.py`).  One process: it turns on JAX's
persistent compilation cache, warms exactly the cell's programs
(set-up), measures for `--seconds`, checks every answer completed in
the window against the plain reference (`bench/reference.py`), and
prints one JSON line last on stdout.  With `--trace 1` it records the
JAX profiler over the window and reports the per-layer metrics
(`bench/metrics/<metric>.py`) instead of the end-to-end ones.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import devtrace  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def enable_cache() -> str:
    """The program's persistent compilation cache: a fixed directory in
    the checkout, or the one `JAX_COMPILATION_CACHE_DIR` names.  Every
    program is kept, however fast it compiled, so a second run loads
    them all."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def judge_answers(config: dict, answers: list[dict]) -> list[dict]:
    spec = reference.Spec(config["spec"])
    judged = []
    for a in answers:
        if not a["ok"]:
            judged.append({"ok": False})
            continue
        judged.append(dict(reference.judge(spec, a, a["layers"],
                                           a.get("padded", False)),
                           ok=True))
    return judged


def judge_searches(config: dict, answers: list[dict], judged: list[dict],
                   count: int, seed: int) -> None:
    """Re-run `count` of the answered searches, drawn from the seed,
    through the search reference, and set each one's `search_gap`:
    |best_edp - the reference's best| / the reference's best."""
    import search_ref
    done = [i for i, (a, j) in enumerate(zip(answers, judged))
            if j["ok"] and a.get("search", {}).get("start_points")
            == "cosa-device"]
    rng = np.random.default_rng([seed, 1])
    for i in sorted(rng.choice(done, size=min(count, len(done)),
                               replace=False)):
        a = answers[i]
        t0 = time.monotonic()
        best = search_ref.best_edp(config["spec"], a["layers"],
                                   a["search"]["protocol"],
                                   a["search"]["seed"],
                                   a["search"]["members"])
        judged[i]["search_gap"] = abs(a["best_edp"] - best) / best
        log(f"search reference: seed {a['search']['seed']} best "
            f"{best!r} against {a['best_edp']!r} "
            f"({time.monotonic() - t0:.1f} s)")


def as_control(config: dict, answers: list[dict]) -> None:
    """Put the control in the program's place: every answer's EDP is
    the reference's own, computed in float32 (the precision below the
    configuration's float64) at the answer's mappings, in its best
    and in the history entries that held the best."""
    spec = reference.Spec(config["spec"])
    for a in answers:
        if a["ok"]:
            layers = reference.tiled_layers(a["mappings"], a["layers"])
            low = reference.network_edp(spec, a["mappings"], layers,
                                        np.float32)
            a["history"] = [[e, low if v == a["best_edp"] else v]
                            for e, v in a["history"]]
            a["best_edp"] = low


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             out_dir: pathlib.Path, t_process: float | None = None,
             keep: dict | None = None, control: bool = False) -> dict:
    """Everything of a run after the look for a chip; returns the
    result line as a dict.  `keep`, where given, receives the judged
    answers and the configuration (for `bench/control.py`); `control`
    judges the float32 control in the program's place
    (`as_control`), which has to come out not correct."""
    t_process = T_PROCESS if t_process is None else t_process
    bm = harness.benchmark()
    entry = harness.cell_entry(bm, cell)
    config = harness.config_for(bm, entry["config"])
    mix = harness.traffic_mix(entry["traffic"])
    mod = harness.driver(mix["driver"])
    workdir = out_dir / "work"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)

    from repro import obs
    tracer = obs.Tracer(enabled=trace)
    obs.set_tracer(tracer)
    traffic = mod.Traffic(config, mix, seed, workdir, log)
    prof_dir = out_dir / "profile"
    marks = {}

    def before_open():
        marks["t_setup"] = time.monotonic()
        if trace:
            shutil.rmtree(prof_dir, ignore_errors=True)
            devtrace.start(str(prof_dir))
            marks["t_anchor"] = devtrace.clock_anchor()
            marks["t_trace0"] = time.monotonic()

    trace_s = mix.get("trace_s", devtrace.TRACE_S)

    def after_done(t_done: float, t_open: float):
        """The profiler records from the window's opening to the first
        completion `trace_s` later (or the window's close)."""
        if trace and "t_trace1" not in marks and t_done - t_open >= trace_s:
            marks["t_trace1"] = time.monotonic()
            devtrace.stop()
            marks["t_exported"] = time.monotonic()

    try:
        with obs.compile_spans():
            traffic.setup()
            t_open, t_close, comps, failed = traffic.window(
                seconds, before_open, after_done)
            if trace and "t_trace1" not in marks:
                marks["t_trace1"] = time.monotonic()
                devtrace.stop()
        mem = peak_bytes()
        unanswered = (traffic.unanswered()
                      if hasattr(traffic, "unanswered") else 0)
        answers = traffic.answers()
        spans = traffic.spans() + [
            {"name": s.name, "t_start": s.t_start, "t_end": s.t_end,
             "attrs": dict(s.attrs), "events": []}
            for s in tracer.spans()]
    finally:
        traffic.close()

    # Spans and completions are read over the whole window but the
    # profiler's export; the profiler's readings over the part of the
    # window that it recorded.
    gap = ((marks["t_trace1"], marks["t_exported"])
           if "t_exported" in marks else None)
    run = harness.Run(setup_s=marks["t_setup"] - t_process, t_open=t_open,
                      t_close=t_close, spans=spans, params=traffic.params(),
                      completions=comps, gap=gap)
    run.completions = [c for c in comps if run.holds(c.t_submit, c.t_done)]
    device = dict(device_info(), memory_peak_bytes=mem)
    if trace:
        lo = max(t_open, marks["t_trace0"])
        hi = min(t_close, marks["t_trace1"])
        dev, host = devtrace.planes_of(devtrace.load(str(prof_dir)))
        run.trace = devtrace.reduce(dev, host, marks["t_anchor"], lo, hi,
                                    spans)
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])

    metrics = {}
    for m in harness.metrics_for(bm, cell, trace):
        value = harness.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    if control:
        if keep is not None:
            keep["program_answers"] = copy.deepcopy(answers)
        as_control(config, answers)
    judged = judge_answers(config, answers)
    searched = mix.get("search_sample", 0)
    if searched:
        judge_searches(config, answers, judged, searched, seed)
    if keep is not None:
        keep.update(answers=answers, judged=judged, config=config)
    lim = dict(harness.limits(), **config.get("limits", {}))
    checks = harness.decide(judged, len(answers) + unanswered, lim,
                            searched)
    line = {"correct": harness.passed(checks), "attempted": len(comps),
            "failed": failed + sum(not c.ok for c in comps),
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": run.trace["top_ops"],
                             "idle_gaps": run.trace["gaps"]}
    line["checks"] = checks
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = harness.benchmark()
    entry = harness.cell_entry(bm, args.workload)
    import jax
    dev = device_info()
    if dev["platform"] != "tpu":
        log(f"no TPU: JAX platform is {dev['platform']!r}; nothing run")
        return 1
    if dev["count"] < entry["chips"]:
        log(f"{args.workload} needs {entry['chips']} chips, JAX sees "
            f"{dev['count']}; nothing run")
        return 1
    cache = enable_cache()
    log(f"jax {jax.__version__}  {dev['kind']} x{dev['count']}  "
        f"compile cache {cache}")
    out_dir = harness.ROOT / ".bench_run" / args.workload
    line = run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), out_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
