"""Telemetry-spine gates: instrumentation must be free when off,
invisible to the numbers when on, and complete when served.

Three gates (benchmarks.run exits non-zero on failure):

* **parity** — a fused search with an enabled tracer reports the
  bit-identical (best EDP, sample count, history) result of the same
  seeded search with telemetry off.  Instrumentation never touches the
  compiled program or the oracle replay, only observes the host driver.
* **no-op overhead** — the disabled tracer's cost on the fused loop,
  gated as a *derived* bound (per-disabled-span cost, measured over
  many calls, times the span count the instrumented search actually
  emits, over the fused loop time) <= 2%.  The direct enabled/disabled
  wall-clock delta is reported alongside but not gated — at CI's
  millisecond loop times that delta is dominated by run-to-run noise.
* **served span tree** — a request driven through the co-search
  service yields a complete rooted lifecycle trace: a ``request`` root
  whose events start at ``submitted`` and end at ``drain``, with a
  ``queue_wait`` child and one ``segment`` child per rounding segment.

Writes ``bench_results/obs_metrics.json``.
"""
from __future__ import annotations

from repro.api import SearchRequest
from repro.core.problem import Layer, Workload
from repro.core.search import SearchConfig, dosa_search
from repro.obs import telemetry as obs
from repro.serve.cosearch_service import CoSearchService, ServiceConfig

from .common import Row, Timer, save_json

POPULATION = 4
WL = Workload(layers=(Layer.matmul(32, 32, 32, name="m"),), name="obs_wl")

NOOP_GATE = 0.02                   # <= 2% derived no-op overhead
NOOP_PROBE_CALLS = 200_000


def _cfg(steps: int, round_every: int) -> SearchConfig:
    return SearchConfig(seed=7, steps=steps, round_every=round_every,
                        n_start_points=POPULATION)


def _key(res):
    return (res.best_edp, res.n_evals, tuple(map(tuple, res.history)))


def _noop_span_cost_s() -> float:
    """Per-call cost of a disabled tracer span (shared no-op context
    manager; the price every fused-loop instrumentation point pays when
    telemetry is off)."""
    tracer = obs.Tracer(enabled=False)
    with Timer() as t:
        for _ in range(NOOP_PROBE_CALLS):
            with tracer.span("probe", segment=0, population=POPULATION):
                pass
    return t.seconds / NOOP_PROBE_CALLS


def run(scale: str = "quick") -> list[Row]:
    steps, round_every = (40, 10) if scale == "paper" else (8, 2)
    cfg = _cfg(steps, round_every)

    # ---- warm the fused engine (compiles are not the loop under test)
    dosa_search(WL, cfg, population=POPULATION, fused=True)

    # ---- gate 1: telemetry-on is seeded bit-identical to telemetry-off
    res_off = dosa_search(WL, cfg, population=POPULATION, fused=True)
    tracer = obs.Tracer()
    old = obs.set_tracer(tracer)
    try:
        res_on = dosa_search(WL, cfg, population=POPULATION, fused=True)
    finally:
        obs.set_tracer(old)
    assert _key(res_on) == _key(res_off), (
        "telemetry-enabled fused search diverged from telemetry-off: "
        f"{_key(res_on)[:2]} vs {_key(res_off)[:2]}")
    span_names = sorted({s.name for s in tracer.spans()})
    n_points = len(tracer.spans())
    assert n_points > 0 and "search.fused_dispatch" in span_names

    # ---- gate 2: derived no-op overhead bound on the fused loop
    per_span_s = _noop_span_cost_s()
    with Timer() as t_off:
        dosa_search(WL, cfg, population=POPULATION, fused=True)
    old = obs.set_tracer(obs.Tracer())
    try:
        with Timer() as t_on:
            dosa_search(WL, cfg, population=POPULATION, fused=True)
    finally:
        obs.set_tracer(old)
    derived_overhead = n_points * per_span_s / t_off.seconds
    measured_delta = (t_on.seconds - t_off.seconds) / t_off.seconds
    assert derived_overhead <= NOOP_GATE, (
        f"no-op telemetry overhead {derived_overhead:.4%} "
        f"({n_points} spans x {per_span_s*1e6:.3f}us over "
        f"{t_off.seconds:.3f}s) exceeds the {NOOP_GATE:.0%} gate")

    # ---- gate 3: served lifecycle trace
    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    req = SearchRequest(workload=WL, config=cfg)
    rid = svc.submit(req)
    out = svc.drain()[rid]
    assert out.status == "ok", f"served search failed: {out.status}"

    tree = svc.request_trace(rid)
    assert tree is not None and tree["name"] == "request"
    assert tree["t_end"] is not None, "root span not closed at drain"
    ev_names = [e["name"] for e in tree["events"]]
    assert ev_names[0] == "submitted" and ev_names[-1] == "drain", (
        f"incomplete lifecycle events: {ev_names}")
    kids = [c["name"] for c in tree["children"]]
    segs = [c for c in tree["children"] if c["name"] == "segment"]
    n_segments = svc.events(rid)[-1].n_segments
    assert "queue_wait" in kids and len(segs) == n_segments, (
        f"span tree has {len(segs)} segment children, expected "
        f"{n_segments} (children: {kids})")

    metrics_text = svc.metrics_text()
    assert "serve_requests_completed_total" in metrics_text

    save_json("obs_metrics", {
        "scale": scale, "workload": WL.name, "population": POPULATION,
        "steps": steps, "round_every": round_every,
        "parity": {"best_edp": res_on.best_edp,
                   "n_evals": res_on.n_evals,
                   "identical_to_off": True},
        "noop_overhead": {
            "per_disabled_span_us": per_span_s * 1e6,
            "instrumentation_points": n_points,
            "fused_loop_s": t_off.seconds,
            "derived_overhead_fraction": derived_overhead,
            "measured_delta_fraction": measured_delta,
            "gate": NOOP_GATE,
        },
        "span_names": span_names,
        "served": {"n_segments": n_segments,
                   "segment_children": len(segs),
                   "lifecycle_events": ev_names},
        "service_metrics": svc.metrics.snapshot(),
    })
    return [
        Row("obs_parity", 0.0,
            f"on==off edp={res_on.best_edp:.4e} evals={res_on.n_evals}"),
        Row("obs_noop_overhead", per_span_s * 1e6,
            f"derived={derived_overhead:.5%} (gate {NOOP_GATE:.0%}) "
            f"points={n_points} measured_delta={measured_delta:+.2%}"),
        Row("obs_served_trace", 0.0,
            f"segments={len(segs)}/{n_segments} "
            f"events={len(ev_names)} drain=ok"),
    ]
