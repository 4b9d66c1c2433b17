"""Shared machinery of the benchmark: loading by name, the run record
that metric readers see, and the comparison that decides `correct`.

Everything a cell needs is found by name under `bench/`:

* `BENCHMARK.json` (repo root) names the cell's configuration and
  traffic mix, and lists the metrics each cell reports;
* `bench/configs/<config>.json`: the deployment (accelerator tables,
  workload layers, search protocol);
* `bench/traffic/<traffic>.json`: the traffic mix, which names its
  generator (`driver`) and holds that generator's parameters;
* `bench/drivers/<driver>.py`: a traffic generator;
* `bench/metrics/<metric>.py`: one reader per metric, `read(run)`.

A later change adds a cell, a mix or a metric by adding such files and
a `BENCHMARK.json` entry; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import statistics

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_entry(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_for(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise KeyError(f"no file {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return _load_module(BENCH / "drivers" / f"{name}.py",
                        f"bench_driver_{name}")


def metric_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_"))


def metrics_for(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of `cell` reports: the end-to-end ones without
    tracing, the per-layer ones with it; a metric with a `workloads`
    list only in the cells it names."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------ run record

@dataclasses.dataclass
class Completion:
    """One request or search that finished: times on the host's
    monotonic clock (shared by every process of the machine)."""
    t_submit: float
    t_done: float
    samples: int
    ok: bool
    t_accepted: float | None = None     # HTTP: reply to the POST
    key: str = ""                       # request id or search index


@dataclasses.dataclass
class Run:
    """What one run measured; metric readers take their number from it.

    `gap`, where set, is a stretch of the window that readers leave
    out: in a traced run, the profiler's export of its trace, which
    loads the host for seconds."""
    setup_s: float
    t_open: float
    t_close: float
    completions: list[Completion]       # those inside (t_open, t_close]
    spans: list[dict] = dataclasses.field(default_factory=list)
    trace: dict | None = None           # devtrace.reduce output
    params: dict = dataclasses.field(default_factory=dict)
    gap: tuple[float, float] | None = None

    def covered(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] inside the window and outside the gap."""
        def overlap(a, b):
            return max(0.0, min(t1, b) - max(t0, a))
        out = overlap(self.t_open, self.t_close)
        if self.gap is not None:
            lo, hi = max(self.gap[0], self.t_open), min(self.gap[1],
                                                        self.t_close)
            if hi > lo:
                out -= overlap(lo, hi)
        return out

    def holds(self, t0: float, t1: float) -> bool:
        """Whether [t0, t1] lies inside the window and clear of the gap."""
        if not (self.t_open < t1 <= self.t_close):
            return False
        return self.gap is None or t1 <= self.gap[0] or t0 >= self.gap[1]

    @property
    def window_s(self) -> float:
        return self.covered(self.t_open, self.t_close)

    def spans_in_window(self, name: str) -> list[dict]:
        """Finished spans called `name` that end inside the window and
        stay clear of the gap."""
        return [s for s in self.spans
                if s["name"] == name and s["t_end"] is not None
                and self.holds(s["t_start"], s["t_end"])]


def quantile(values: list[float], q: float) -> float | None:
    """Nearest-rank quantile: the smallest value with at least a share
    `q` of the sample at or below it."""
    if not values:
        return None
    vals = sorted(values)
    rank = max(1, math.ceil(q * len(vals)))
    return vals[rank - 1]


def mean(values: list[float]) -> float | None:
    return statistics.fmean(values) if values else None


# ------------------------------------------------------------ correctness

def limits() -> dict:
    return load_json(BENCH / "limits.json")["limits"]


def decide(judged: list[dict], expected: int, lim: dict,
           searched: int = 0) -> dict:
    """Reduce per-answer readings to the numbers compared, each beside
    its limit.  `expected` counts the answers that were due; a missing
    or failed one counts in `unanswered`.  Where `searched` answers
    were to be re-run by the search reference, `search_gap` is the
    widest of their gaps, unbounded where fewer were re-run."""
    finite = [j for j in judged if j.get("ok")]

    def worst(key):
        return max((j[key] for j in finite), default=0)
    checks = {
        "unanswered": (expected - len(finite), lim["unanswered"]),
        "invalid": (worst("invalid"), lim["invalid"]),
        "accounting": (worst("accounting"), lim["accounting"]),
        "edp_gap": (worst("edp_gap"), lim["edp_gap"]),
    }
    if searched:
        gaps = [j["search_gap"] for j in finite if "search_gap" in j]
        short = not finite or len(gaps) < min(searched, len(finite))
        checks["search_gap"] = (math.inf if short else max(gaps),
                                lim["search_gap"])
    return {k: {"value": _finite(v), "limit": l}
            for k, (v, l) in checks.items()}


def _finite(v):
    """JSON has no infinity: an unbounded reading is written as 1e300."""
    if isinstance(v, float) and not math.isfinite(v):
        return 1e300
    return v


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
