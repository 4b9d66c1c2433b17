"""Transport front-end tests: payload-boundary validation units plus a
live HTTP round-trip against `serve.server.CoSearchServer` (ephemeral
port, real sockets, stdlib client)."""
import json
import urllib.error
import urllib.request

import pytest

from repro.core.archspec import TPU_V5E_SPEC
from repro.core.problem import Layer, Workload
from repro.core.search import SearchConfig, dosa_search
from repro.serve.cosearch_service import ServiceConfig
from repro.serve.server import CoSearchServer, parse_search_payload

WL_JSON = {"name": "t", "layers": [{"matmul": [16, 16, 16],
                                    "name": "a"}]}
CFG_JSON = {"steps": 4, "round_every": 2, "n_start_points": 2,
            "seed": 21}


# ---------------------------------------------------------------------------
# Boundary validation (no sockets)
# ---------------------------------------------------------------------------

def test_parse_payload_roundtrip():
    req = parse_search_payload({"workload": WL_JSON, "config": CFG_JSON,
                                "priority": 2, "segment_budget": 3})
    assert req.workload == Workload(
        layers=(Layer.matmul(16, 16, 16, name="a"),), name="t")
    assert req.config.steps == 4 and req.config.seed == 21
    assert req.priority == 2 and req.segment_budget == 3


def test_parse_payload_explicit_dims_and_spec():
    req = parse_search_payload({
        "workload": {"layers": [{"dims": [1, 1, 8, 1, 8, 8, 1],
                                 "repeat": 2}]},
        "config": {"spec": "tpu_v5e"}})
    assert req.workload.layers[0].dims == (1, 1, 8, 1, 8, 8, 1)
    assert req.workload.layers[0].repeat == 2
    assert req.config.spec is TPU_V5E_SPEC


@pytest.mark.parametrize("payload,match", [
    ([1, 2], "JSON object"),
    ({"workload": WL_JSON, "bogus": 1}, "unknown request field"),
    ({}, "needs a 'workload'"),
    ({"workload": {"layers": []}}, "non-empty"),
    ({"workload": {"layers": [{"dims": [1, 2]}]}}, "7 ints"),
    ({"workload": {"layers": [{"nope": 1}]}}, "needs one of"),
    ({"workload": WL_JSON, "config": {"stepz": 4}}, "not a serveable"),
    ({"workload": WL_JSON, "config": {"steps": "many"}}, "must be int"),
    ({"workload": WL_JSON, "config": {"spec": "hal9000"}},
     "unknown spec"),
    ({"workload": WL_JSON, "config": {"ordering_mode": "wat"}},
     "ordering_mode"),
    ({"workload": WL_JSON, "priority": "high"}, "priority"),
    ({"workload": WL_JSON, "deadline_s": -1}, "deadline_s"),
    ({"workload": WL_JSON, "request_id": 7}, "request_id"),
])
def test_parse_payload_rejects_malformed(payload, match):
    with pytest.raises(ValueError, match=match):
        parse_search_payload(payload)


def test_parse_payload_zero_dim_rejected_by_layer():
    """Semantic layer validation (dims >= 1) fires at the boundary."""
    with pytest.raises(ValueError, match="dims must be >= 1"):
        parse_search_payload(
            {"workload": {"layers": [{"dims": [0, 1, 1, 1, 1, 1, 1]}]}})


# ---------------------------------------------------------------------------
# Live HTTP round-trip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    srv = CoSearchServer(ServiceConfig(bucket_workloads=False))
    host, port = srv.start()
    yield srv, f"http://{host}:{port}"
    srv.stop()


def _post(base, path, body):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(base, path):
    try:
        with urllib.request.urlopen(base + path, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_submit_poll_result_matches_direct(server):
    """The full wire path: POST a search, poll until done, compare the
    JSON result against direct dosa_search for the same seed."""
    srv, base = server
    code, sub = _post(base, "/v1/search",
                      {"workload": WL_JSON, "config": CFG_JSON})
    assert code == 202 and not sub["deduplicated"]
    rid = sub["request_id"]

    assert srv.wait_idle(timeout=300)
    code, out = _get(base, f"/v1/result/{rid}")
    assert code == 200
    assert out["status"] == "ok" and out["ok"]

    wl = Workload(layers=(Layer.matmul(16, 16, 16, name="a"),),
                  name="t")
    direct = dosa_search(wl, SearchConfig(**CFG_JSON), population=2,
                         fused=True)
    assert out["best_edp"] == direct.best_edp
    assert out["n_evals"] == direct.n_evals
    assert out["history"] == [[e, v] for e, v in direct.history]

    code, evs = _get(base, f"/v1/events/{rid}")
    assert code == 200
    assert [ev["segment"] for ev in evs["events"]] == [1, 2]
    assert evs["events"][-1]["done"]

    code, frontier = _get(base, "/v1/frontier")
    assert code == 200 and len(frontier["frontier"]) == 1


def test_http_dedup_flag(server):
    srv, base = server
    body = {"workload": WL_JSON, "config": CFG_JSON}
    _, first = _post(base, "/v1/search", body)
    _, second = _post(base, "/v1/search", body)
    assert second["request_id"] == first["request_id"]
    assert second["deduplicated"]
    assert srv.wait_idle(timeout=300)


def test_http_rejects_malformed_with_400(server):
    _, base = server
    for body, frag in [
        ({"workload": WL_JSON, "config": {"stepz": 1}}, "serveable"),
        ({"workload": {"layers": [{"dims": [1, 2]}]}}, "7 ints"),
        ({"workload": WL_JSON, "config": {"spec": "nope"}},
         "unknown spec"),
        (None, "JSON object"),
    ]:
        code, out = _post(base, "/v1/search", body)
        assert code == 400
        assert frag in out["error"]["message"]
    # malformed JSON body (not just malformed schema)
    req = urllib.request.Request(
        base + "/v1/search", data=b"{nope",
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400


def test_http_unknown_routes_and_ids(server):
    _, base = server
    assert _get(base, "/v1/result/doesnotexist")[0] == 404
    assert _get(base, "/v1/events/doesnotexist")[0] == 404
    assert _get(base, "/nope")[0] == 404
    assert _post(base, "/nope", {})[0] == 404


def test_http_health_and_stats(server):
    srv, base = server
    code, health = _get(base, "/v1/healthz")
    assert code == 200 and health["ok"]
    code, stats = _get(base, "/v1/stats")
    assert code == 200
    assert stats["n_requests_done"] >= 1
    faults = stats["faults"]
    assert faults["dedup_hits"] >= 1
    assert "retries" in faults and "quarantined" in faults
    # engine-cache stats now carry per-entry build accounting
    assert "build_seconds_total" in stats["engine_cache"]


def test_http_metrics_prometheus(server):
    """/v1/metrics speaks the Prometheus text exposition and carries
    the request, fault and engine-cache families."""
    srv, base = server
    with urllib.request.urlopen(base + "/v1/metrics", timeout=30) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    samples, types = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
        elif line and not line.startswith("#"):
            key, val = line.rsplit(" ", 1)
            samples[key] = float(val)
    assert types["serve_requests_submitted_total"] == "counter"
    assert types["serve_request_seconds"] == "histogram"
    assert types["engine_cache_hit_rate"] == "gauge"
    assert samples["serve_requests_submitted_total"] >= 1.0
    assert samples['serve_requests_completed_total{status="ok"}'] >= 1.0
    assert samples["serve_dedup_hits_total"] >= 1.0
    assert samples["serve_request_seconds_count"] >= 1.0
    assert any(k.startswith("engine_build_total") for k in samples)


def test_http_trace_span_tree_and_404(server):
    """/v1/trace/<rid> returns the rooted lifecycle span tree; unknown
    ids 404."""
    srv, base = server
    code, sub = _post(base, "/v1/search",
                      {"workload": WL_JSON, "config": dict(CFG_JSON,
                                                           seed=99)})
    rid = sub["request_id"]
    assert srv.wait_idle(timeout=300)
    code, out = _get(base, f"/v1/trace/{rid}")
    assert code == 200 and out["request_id"] == rid
    tree = out["trace"]
    assert tree["name"] == "request"
    assert tree["attrs"]["request_id"] == rid
    names = [e["name"] for e in tree["events"]]
    assert names[0] == "submitted" and names[-1] == "drain"
    kids = [c["name"] for c in tree["children"]]
    assert kids[0] == "queue_wait"
    assert kids.count("segment") == 2
    assert _get(base, "/v1/trace/doesnotexist")[0] == 404


def test_http_trace_segments_hold_their_step_phases(tmp_path):
    """Each segment of /v1/trace/<rid> holds the `service.step` span
    that advanced it, and under it the phase spans: dispatch, read-back,
    oracle replay and the checkpoint save."""
    srv = CoSearchServer(ServiceConfig(bucket_workloads=False,
                                       checkpoint_dir=str(tmp_path)))
    host, port = srv.start()
    base = f"http://{host}:{port}"
    try:
        _, sub = _post(base, "/v1/search",
                       {"workload": WL_JSON,
                        "config": dict(CFG_JSON, seed=61)})
        rid = sub["request_id"]
        assert srv.wait_idle(timeout=300)
        code, out = _get(base, f"/v1/trace/{rid}")
    finally:
        srv.stop()
    assert code == 200
    segs = [c for c in out["trace"]["children"] if c["name"] == "segment"]
    assert [s["attrs"]["segment"] for s in segs] == [0, 1]
    for seg in segs:
        (step,) = seg["children"]
        assert step["name"] == "service.step"
        assert step["span_id"] == seg["attrs"]["step_span"]
        assert step["attrs"]["task_id"] == seg["attrs"]["task_id"]
        phases = [c["name"] for c in step["children"]]
        for name in ("task.dispatch", "task.readback", "search.oracle",
                     "checkpoint.save"):
            assert name in phases, (name, phases)
        oracle = step["children"][phases.index("search.oracle")]
        assert oracle["attrs"]["candidates"] == CFG_JSON["n_start_points"]
    assert "search.starts" in [c["name"] for c in segs[0]["children"][0]
                               ["children"]]


def test_lock_waits_on_post_and_delivering_get():
    """A POST's wait for the service lock lands on its request's root
    span (a duplicate's on the `dedup_hit` event); a GET that delivers
    the outcome adds a `delivered` event to the canonical root, alias
    deliveries included, and a pending poll records nothing."""
    srv = CoSearchServer(ServiceConfig(bucket_workloads=False))
    body = {"workload": WL_JSON, "config": dict(CFG_JSON, seed=57)}
    canon = srv.submit_json(dict(body, request_id="lw-canon"))
    alias = srv.submit_json(dict(body, request_id="lw-alias"))
    assert alias["deduplicated"] and not canon["deduplicated"]
    assert srv.result_json("lw-canon")[0] == 202
    srv.service.drain()
    assert srv.result_json("lw-canon")[0] == 200
    assert srv.result_json("lw-alias")[0] == 200

    tree = srv.service.request_trace("lw-alias")
    assert tree["attrs"]["request_id"] == "lw-canon"
    assert tree["attrs"]["lock_wait_s"] >= 0.0
    events = {e["name"]: [] for e in tree["events"]}
    for e in tree["events"]:
        events[e["name"]].append(e["attrs"])
    (dup,) = events["dedup_hit"]
    assert dup["alias"] == "lw-alias" and dup["lock_wait_s"] >= 0.0
    assert [d["request_id"] for d in events["delivered"]] == \
        ["lw-canon", "lw-alias"]
    assert all(d["lock_wait_s"] >= 0.0 for d in events["delivered"])
    assert [e["name"] for e in tree["events"]][-3:] == \
        ["drain", "delivered", "delivered"]


def test_scheduler_idle_is_a_sched_wait_span(server):
    """The scheduler's idle stretches are closed `sched.wait` spans on
    the service's tracer, none of them overlapping a `service.step`."""
    srv, base = server
    _post(base, "/v1/search",
          {"workload": WL_JSON, "config": dict(CFG_JSON, seed=58)})
    assert srv.wait_idle(timeout=300)
    tr = srv.service.tracer
    waits = [s for s in tr.spans_named("sched.wait") if s.t_end is not None]
    steps = tr.spans_named("service.step")
    assert waits and steps
    for w in waits:
        assert w.parent_id is None
        assert not any(s.t_start < w.t_end and w.t_start < s.t_end
                       for s in steps)
