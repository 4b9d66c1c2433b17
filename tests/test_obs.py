"""Telemetry spine tests: tracer/span semantics under an injected
clock, the metrics registry + Prometheus text rendering, per-entry
engine-cache build accounting, and the served request lifecycle (span
tree, per-step phase spans, fault events, /v1/metrics families) driven
through the real service."""
import pytest

from repro.api import SearchRequest
from repro.core.lru import LRUCache
from repro.core.problem import Layer, Workload
from repro.core.search import SearchConfig, _ENGINE_CACHE, dosa_search
from repro.obs import telemetry as obs
from repro.serve.cosearch_service import CoSearchService, ServiceConfig

WL = Workload(layers=(Layer.matmul(16, 16, 16, name="a"),), name="wa")


def _cfg(seed=1, steps=4, round_every=2):
    return SearchConfig(steps=steps, round_every=round_every,
                        n_start_points=2, seed=seed)


def _req(seed=1, **kw):
    return SearchRequest(workload=WL, config=_cfg(seed), **kw)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def tick(self, dt=1.0):
        self.t += dt

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Tracer / span semantics
# ---------------------------------------------------------------------------

def test_span_nesting_durations_and_injected_clock():
    clk = _Clock()
    tr = obs.Tracer(clock=clk)
    with tr.span("outer", k=1) as outer:
        clk.tick()
        with tr.span("inner") as inner:
            clk.tick(2.0)
            inner.event("mark", x=3)
        clk.tick()
    spans = {s.name: s for s in tr.spans()}
    assert spans["outer"].parent_id is None
    assert spans["inner"].parent_id == outer.span_id
    assert spans["outer"].duration_s == pytest.approx(4.0)
    assert spans["inner"].duration_s == pytest.approx(2.0)
    assert spans["outer"].attrs == {"k": 1}
    (t, name, attrs), = spans["inner"].events
    assert (name, attrs) == ("mark", {"x": 3})
    assert tr.total_s("inner") == pytest.approx(2.0)


def test_explicit_parenting_across_call_frames():
    tr = obs.Tracer(clock=_Clock())
    root = tr.start_span("request")
    child = tr.start_span("segment", parent_id=root, segment=0)
    tr.end_span(child, outcome="ok")
    tr.end_span(root)
    tree = tr.tree(root)
    assert tree["name"] == "request"
    assert [c["name"] for c in tree["children"]] == ["segment"]
    assert tree["children"][0]["attrs"]["outcome"] == "ok"
    assert tr.tree(999) is None


def test_disabled_tracer_is_a_true_noop():
    tr = obs.Tracer(enabled=False)
    a = tr.span("x")
    b = tr.span("y", attr=1)
    assert a is b                       # shared stateless context mgr
    with a as sp:
        sp.event("e")
        sp.set(k=1)
    assert tr.start_span("z") == -1
    tr.end_span(-1)
    tr.add_event(-1, "e")
    assert tr.spans() == [] and tr.dropped == 0


def test_span_error_attr_on_exception():
    tr = obs.Tracer(clock=_Clock())
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    (sp,) = tr.spans()
    assert "ValueError" in sp.attrs["error"]
    assert sp.t_end is not None


def test_eviction_drops_finished_never_open_roots():
    tr = obs.Tracer(clock=_Clock(), max_spans=4)
    root = tr.start_span("request")       # stays open
    for i in range(10):
        with tr.span("seg", parent_id=root, i=i):
            pass
    assert tr.dropped > 0
    live = tr.spans()
    assert any(s.span_id == root for s in live)
    assert len(live) <= 5


def test_eviction_is_bounded_and_keeps_an_old_open_root():
    """Past `max_spans` the oldest finished span goes, one per insert:
    the open root that came first survives 2x the cap of inserts, the
    store stays at the cap and `dropped` counts the rest."""
    cap = 50
    tr = obs.Tracer(clock=_Clock(), max_spans=cap)
    root = tr.start_span("request")       # stays open
    for i in range(2 * cap):
        with tr.span("seg", parent_id=root, i=i):
            pass
    live = tr.spans()
    assert len(live) == len(tr) == cap
    assert live[0].span_id == root and live[0].t_end is None
    assert tr.dropped == 2 * cap + 1 - cap
    # the newest finished spans are the ones kept
    assert [s.attrs["i"] for s in live[1:]] == list(range(cap + 1, 2 * cap))


# ---------------------------------------------------------------------------
# Metrics registry + Prometheus rendering
# ---------------------------------------------------------------------------

def _parse_prometheus(text: str) -> dict:
    """name{labels} -> float for every sample line; '# TYPE' lines
    collected under '__types__'."""
    out, types = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
        elif line and not line.startswith("#"):
            key, val = line.rsplit(" ", 1)
            out[key] = float(val)
    out["__types__"] = types
    return out


def test_counter_gauge_histogram_render_and_parse():
    reg = obs.MetricsRegistry()
    c = reg.counter("req_total", "requests", ("status",))
    c.inc(status="ok")
    c.inc(2, status="err")
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)

    assert c.total() == 3.0 and c.value(status="ok") == 1.0
    assert h.count() == 4 and h.sum() == pytest.approx(55.55)

    m = _parse_prometheus(reg.to_prometheus())
    assert m["__types__"] == {"req_total": "counter", "depth": "gauge",
                              "lat_seconds": "histogram"}
    assert m['req_total{status="ok"}'] == 1.0
    assert m['req_total{status="err"}'] == 2.0
    assert m["depth"] == 7.0
    # cumulative buckets + +Inf == count
    assert m['lat_seconds_bucket{le="0.1"}'] == 1.0
    assert m['lat_seconds_bucket{le="1"}'] == 2.0
    assert m['lat_seconds_bucket{le="10"}'] == 3.0
    assert m['lat_seconds_bucket{le="+Inf"}'] == 4.0
    assert m["lat_seconds_count"] == 4.0


def test_registry_idempotent_and_type_checked():
    reg = obs.MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="labels"):
        a.inc(bogus="l")
    with pytest.raises(ValueError, match=">= 0"):
        a.inc(-1)


def test_render_prometheus_merges_registries():
    r1, r2 = obs.MetricsRegistry(), obs.MetricsRegistry()
    r1.counter("a_total").inc()
    r2.counter("b_total").inc()
    m = _parse_prometheus(obs.render_prometheus(r1, r2))
    assert m["a_total"] == 1.0 and m["b_total"] == 1.0


# ---------------------------------------------------------------------------
# Engine-cache build accounting (schema-pinned)
# ---------------------------------------------------------------------------

def test_lru_stats_schema_pinned():
    """The stats dict `/v1/stats` publishes per cache — downstream
    dashboards key on exactly these fields."""
    c = LRUCache(maxsize=2)
    c.put("a", 1)
    c.get("a")
    c.get("nope")
    c.note_build_time("fused:wa", 0.25)
    c.note_build_time("fused:wb", 0.5)
    st = c.stats()
    assert set(st) == {"size", "maxsize", "hits", "misses", "evictions",
                       "hit_rate", "build_count", "build_seconds_total",
                       "build_seconds"}
    assert st["build_count"] == 2
    assert st["build_seconds_total"] == pytest.approx(0.75)
    assert st["build_seconds"]["fused:wa"] == 0.25
    c.clear(reset_stats=True)
    st = c.stats()
    assert st["build_count"] == 0 and st["build_seconds"] == {}


def test_build_label_store_is_bounded():
    c = LRUCache(maxsize=2)
    for i in range(20):
        c.note_build_time(f"l{i}", 0.1)
    assert len(c.stats()["build_seconds"]) <= 8   # 4 * maxsize
    assert c.stats()["build_count"] == 20


def test_engine_build_span_and_cache_build_time():
    """A cache-miss engine build is timed by an engine.build span and
    lands in both the global registry and the cache's stats."""
    tr = obs.Tracer()
    old = obs.set_tracer(tr)
    _ENGINE_CACHE.clear(reset_stats=True)
    before = obs.get_metrics().counter(
        "engine_build_total", labelnames=("cache", "kind")).total()
    try:
        dosa_search(WL, _cfg(1), population=2, fused=True)
    finally:
        obs.set_tracer(old)
    builds = tr.spans_named("engine.build")
    assert builds and builds[0].attrs["cache"] == "search"
    assert builds[0].duration_s > 0
    after = obs.get_metrics().counter(
        "engine_build_total", labelnames=("cache", "kind")).total()
    assert after > before
    st = _ENGINE_CACHE.stats()
    assert st["build_count"] >= 1
    assert any(lbl.startswith("fused:") for lbl in st["build_seconds"])


def test_compile_spans_record_xla_compiles_under_the_open_span():
    """Inside `compile_spans()` each XLA compile becomes a finished
    engine.compile span parented on the compiling thread's span;
    outside it nothing is recorded."""
    import jax
    import jax.numpy as jnp
    tr = obs.Tracer()
    old = obs.set_tracer(tr)
    try:
        with obs.compile_spans():
            with tr.span("search.fused_dispatch"):
                jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(5.0))
        jax.jit(lambda x: x - 2.0)(jnp.arange(5.0))
    finally:
        obs.set_tracer(old)
    parent = tr.spans_named("search.fused_dispatch")[0]
    compiles = tr.spans_named("engine.compile")
    assert any("lambda" in s.attrs["fun_name"] for s in compiles)
    assert all(s.parent_id == parent.span_id for s in compiles)
    assert all(s.t_end is not None and s.duration_s > 0
               for s in compiles)
    assert tr.total_s("engine.compile") <= parent.duration_s


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(
        monkeypatch):
    import jax
    from repro.runtime import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert path.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# Served request lifecycle: span tree, phase spans, metrics
# ---------------------------------------------------------------------------

def test_served_request_full_span_tree_and_history():
    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    rid = svc.submit(_req(31))
    out = svc.drain()[rid]
    assert out.status == "ok"

    tree = svc.request_trace(rid)
    assert tree["name"] == "request"
    assert tree["attrs"]["request_id"] == rid
    assert tree["t_end"] is not None            # closed at drain
    ev_names = [e["name"] for e in tree["events"]]
    assert ev_names[0] == "submitted"
    assert "batch_join" in ev_names and ev_names[-1] == "drain"

    kids = [c["name"] for c in tree["children"]]
    assert kids[0] == "queue_wait"
    segs = [c for c in tree["children"] if c["name"] == "segment"]
    assert [s["attrs"]["segment"] for s in segs] == [0, 1]
    assert all(s["attrs"]["outcome"] == "ok" for s in segs)
    # the final segment's span attrs carry the request's answer
    assert segs[-1]["attrs"]["best_edp"] == out.result.best_edp
    assert svc.request_trace("doesnotexist") is None

    # each segment names the service.step span that ran it
    steps = {s.span_id: s for s in svc.tracer.spans_named("service.step")}
    assert [steps[s["attrs"]["step_span"]].attrs["segment"]
            for s in segs] == [0, 1]

    m = _parse_prometheus(svc.metrics_text())
    assert m["serve_requests_submitted_total"] >= 1.0
    assert m['serve_requests_completed_total{status="ok"}'] >= 1.0
    assert m["serve_segments_total"] >= 2.0
    assert m['serve_batches_total{kind="fused"}'] >= 1.0
    assert m["serve_request_seconds_count"] >= 1.0
    assert m['engine_cache_size{cache="search"}'] >= 0.0
    # global registry merged in: engine builds + checkpoint families
    assert any(k.startswith("engine_build_total") for k in m)

    st = svc.stats()
    assert st["n_batches"] >= 1 and st["n_grouped_batches"] == 0
    assert st["telemetry"]["spans"] == len(svc.tracer.spans()) >= 4
    assert set(st["telemetry"]) == {"spans", "spans_dropped"}


def _children(tracer, span_id):
    return [c["name"] for c in tracer.tree(span_id)["children"]]


def test_served_step_phase_spans_on_the_service_tracer(tmp_path):
    """On a fake clock, every `service.step` holds the task's phases in
    order: on the first segment only, the look for a checkpoint to
    resume, start generation and the seg-0 checkpoint; then dispatch, read-back, oracle replay, rebuild, checkpoint, and on the
    last segment finalize and checkpoint GC.  Nothing reaches the
    (enabled) global tracer."""
    dosa_search(WL, _cfg(41), population=2, fused=True)   # engine warm
    glob = obs.Tracer()
    old = obs.set_tracer(glob)
    try:
        svc = CoSearchService(ServiceConfig(
            bucket_workloads=False, clock_fn=_Clock(),
            checkpoint_dir=str(tmp_path / "ck")))
        rid = svc.submit(_req(41))
        out = svc.drain()[rid]
    finally:
        obs.set_tracer(old)
    assert out.status == "ok"
    assert glob.spans() == []

    tr = svc.tracer
    steps = tr.spans_named("service.step")
    assert [s.attrs["segment"] for s in steps] == [0, 1]
    assert all(s.attrs["kind"] == "fused" and s.attrs["batch_size"] == 1
               and s.parent_id is None for s in steps)
    body = ["task.dispatch", "task.readback", "search.oracle",
            "task.rebuild", "checkpoint.save"]
    assert _children(tr, steps[0].span_id) == \
        ["checkpoint.restore", "search.starts", "checkpoint.save"] + body
    assert _children(tr, steps[1].span_id) == \
        body + ["task.finalize", "checkpoint.gc"]

    (starts,) = tr.spans_named("search.starts")
    assert starts.attrs["n"] == 2 and starts.attrs["tries"] >= 2
    for sp in tr.spans_named("search.oracle"):
        assert sp.attrs["candidates"] == 2
        assert 0 <= sp.attrs["improved"] <= sp.attrs["candidates"]
    # samples: tries + the starts' own replay + GD steps + candidates
    assert out.result.n_evals == starts.attrs["tries"] + 2 + 2 * (4 + 2)


def test_direct_fused_oracle_spans_count_candidates():
    """A direct fused search's `search.oracle` spans count every
    replayed candidate (starts x segments) and the improvements among
    them; `search.starts` counts its oracle-checked tries."""
    cfg = SearchConfig(steps=6, round_every=2, n_start_points=3, seed=5)
    tr = obs.Tracer()
    old = obs.set_tracer(tr)
    try:
        res = dosa_search(WL, cfg, population=2, fused=True)
    finally:
        obs.set_tracer(old)
    oracle = tr.spans_named("search.oracle")
    assert sum(s.attrs["candidates"] for s in oracle) == 3 * 3
    assert all(0 <= s.attrs["improved"] <= s.attrs["candidates"]
               for s in oracle)
    (starts,) = tr.spans_named("search.starts")
    assert starts.attrs["n"] == 3
    assert 3 <= starts.attrs["tries"] <= 3 * cfg.max_reject_tries
    assert res.n_evals == starts.attrs["tries"] + 3 + 3 * (6 + 3)


def test_trace_records_retry_and_backoff_events():
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        backoff_base_s=0.5,
                                        sleep_fn=lambda s: None))
    rid = svc.submit(_req(32))
    fired = []

    def flaky(task_id, seg, request_ids):
        if not fired:
            fired.append(True)
            raise RuntimeError("chaos: transient blip")

    svc.fault_hook = flaky
    out = svc.drain()[rid]
    assert out.status == "ok"
    tree = svc.request_trace(rid)
    names = [e["name"] for e in tree["events"]]
    assert "retry" in names and "backoff" in names
    retry = next(e for e in tree["events"] if e["name"] == "retry")
    assert retry["attrs"]["type"] == "RuntimeError"
    m = _parse_prometheus(svc.metrics_text())
    assert m["serve_retries_total"] == 1.0
    assert m["serve_backoff_seconds_total"] > 0.0
    assert m['serve_fault_events_total{event="retry"}'] == 1.0
    assert svc.fault_stats()["retries"] == 1


def test_trace_records_quarantine_and_split_events():
    reqs = [_req(s) for s in (33, 34)]
    target = reqs[-1].request_id

    def poison(task_id, seg, request_ids):
        if target in request_ids:
            raise ValueError("chaos: poison input")

    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        backoff_base_s=0.0))
    svc.fault_hook = poison
    for r in reqs:
        svc.submit(r)
    outs = svc.drain()
    assert outs[target].status == "error"
    assert outs[reqs[0].request_id].status == "ok"

    bad = svc.request_trace(target)
    names = [e["name"] for e in bad["events"]]
    assert "split" in names and "quarantine" in names
    q = next(e for e in bad["events"] if e["name"] == "quarantine")
    assert q["attrs"]["fault_class"] == "poison"
    m = _parse_prometheus(svc.metrics_text())
    assert m["serve_quarantined_total"] == 1.0
    assert m["serve_batch_splits_total"] == 1.0
    assert m['serve_requests_completed_total{status="error"}'] == 1.0
