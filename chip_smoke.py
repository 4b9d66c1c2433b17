#!/usr/bin/env python3
"""Chip smoke test: the co-search service end to end on a TPU.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: the sharded engine

One process.  With no option it starts `serve.server.CoSearchServer`
in-process (checkpointing on, under `--out`), POSTs full-width
requests to `/v1/search` over localhost HTTP and polls
`/v1/result/<id>`:

* `resnet50` (the paper's workload) on Gemmini;
* `qwen3_0_6b` x `decode_32k` at published widths on the TPU v5e spec;
* an exact duplicate of the first (dedup);
* resnet50 again at a small budget, for the plain-reference check.

Each served outcome must be `ok` and not degraded, identical
(`best_edp`, `n_evals`, `history`) to a direct `api.run_request` of
the same request in this process; its best mappings must re-evaluate
to `best_edp` exactly under the host oracle, and its `best_hw` must be
their minimal hardware (`check_oracle`).  The
small-budget answer must equal the sequential `dosa_search` (the plain
reference).  `/v1/stats` must show no retry, quarantine, timeout or
degraded request.  The server runs with `bucket_workloads=False`, so
every request is searched at the widths it names.

`--chips 4` runs only the sharded phase: a 1024-member resnet50
population on Gemmini at `shards=4` against `shards=1`, bit-identical,
with the population's output spread over all four devices.

Exits non-zero, printing no result line, where JAX finds no TPU or a
phase fails.  The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import urllib.error
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The budgets the smoke serves at; the small one is also run by the
# sequential reference driver.
SERVE_BUDGET = {"steps": 100, "round_every": 25, "n_start_points": 8,
                "seed": 0}
REFERENCE_BUDGET = {"steps": 50, "round_every": 25, "n_start_points": 2,
                    "seed": 0}
SHARDED_POPULATION = 1024
SHARDED_CHIPS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------- requests

def workload_json(wl) -> dict:
    """A `Workload` as the `/v1/search` payload spells it."""
    return {"name": wl.name,
            "layers": [{"dims": list(lay.dims), "wstride": lay.wstride,
                        "hstride": lay.hstride, "repeat": lay.repeat,
                        "name": lay.name} for lay in wl.layers]}


def payload(wl, spec: str, budget: dict) -> dict:
    return {"workload": workload_json(wl),
            "config": dict(budget, spec=spec)}


def smoke_payloads() -> list[dict]:
    """The one-chip requests, in submission order: resnet50 on Gemmini,
    qwen3-0.6B decode_32k on TPU v5e, a duplicate of the first, and
    resnet50 at the reference budget (last)."""
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.workloads.dnn_zoo import resnet50
    from repro.workloads.lm_extract import extract

    resnet = resnet50()
    qwen = extract(get_config("qwen3_0_6b"), SHAPES["decode_32k"])
    first = payload(resnet, "gemmini", SERVE_BUDGET)
    return [first, payload(qwen, "tpu_v5e", SERVE_BUDGET), dict(first),
            payload(resnet, "gemmini", REFERENCE_BUDGET)]


# ---------------------------------------------------------------- phases

def _http(method: str, url: str, body: dict | None = None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read() or b"null")


def serve_phase(payloads: list[dict], out_dir: pathlib.Path,
                timeout_s: float = 900.0) -> dict:
    """Serve `payloads` through a live `CoSearchServer`: POST each, then
    poll until every request has an outcome.  Returns one record per
    payload (request id, dedup flag, submit->outcome seconds, the HTTP
    outcome and the in-process `SearchOutcome`) and `/v1/stats`."""
    from repro.serve.cosearch_service import ServiceConfig
    from repro.serve.server import CoSearchServer

    server = CoSearchServer(ServiceConfig(
        bucket_workloads=False, checkpoint_dir=str(out_dir / "ckpt")))
    host, port = server.start()
    base = f"http://{host}:{port}"
    try:
        records = []
        for body in payloads:
            t0 = time.monotonic()
            code, reply = _http("POST", base + "/v1/search", body)
            if code != 202:
                raise RuntimeError(f"submit refused ({code}): {reply}")
            records.append({"payload": body, "t_submit": t0,
                            "request_id": reply["request_id"],
                            "deduplicated": reply["deduplicated"]})
        deadline = time.monotonic() + timeout_s
        pending = list(records)
        while pending:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{len(pending)} request(s) without an outcome "
                    f"after {timeout_s:.0f} s")
            for rec in list(pending):
                code, reply = _http(
                    "GET", base + "/v1/result/" + rec["request_id"])
                if code == 200:
                    rec["seconds"] = time.monotonic() - rec["t_submit"]
                    rec["outcome_json"] = reply
                    rec["outcome"] = server.service.outcome(
                        rec["request_id"])
                    pending.remove(rec)
                elif code != 202:
                    raise RuntimeError(f"result poll failed ({code}): "
                                       f"{reply}")
            time.sleep(0.05)
        code, stats = _http("GET", base + "/v1/stats")
        if code != 200:
            raise RuntimeError(f"/v1/stats failed ({code}): {stats}")
    finally:
        server.stop()
    return {"records": records, "stats": stats}


def _direct_request(body: dict):
    """The request a payload asks for, as the service runs it: the
    fused population engine with one member per start point."""
    from repro.serve.server import parse_search_payload
    req = parse_search_payload(body)
    return dataclasses.replace(req, population=req.config.n_start_points,
                               fused=True, request_id=None)


def _same_answer(served: dict, result, what: str) -> None:
    history = [[int(e), float(v)] for e, v in result.history]
    got = (served["best_edp"], served["n_evals"], served["history"])
    want = (float(result.best_edp), int(result.n_evals), history)
    if got != want:
        raise AssertionError(
            f"{what}: served best_edp/n_evals {got[:2]} vs {want[:2]}"
            f" (history equal: {got[2] == want[2]})")


def check_oracle(result, req, best_edp: float, name: str) -> None:
    """The host oracle reproduces `best_edp` exactly at the returned
    mappings, evaluated as the search's oracle replay evaluates them:
    on `config.fixed_hw`, which for a co-search (None) means each layer
    on the minimal hardware of its own mapping.  The returned
    `best_hw` must be the network's minimal hardware for those
    mappings; its EDP is printed beside `best_edp`, since one shared
    accelerator costs more than per-layer hardware."""
    from repro.core.archspec import resolve_spec
    from repro.core.hw_infer import minimal_hw_for
    from repro.core.oracle import evaluate_workload

    cspec = resolve_spec(req.config.spec)
    layers = list(req.workload.layers)
    edp, _ = evaluate_workload(result.best_mappings, layers,
                               hw=req.config.fixed_hw, spec=cspec)
    if float(edp) != best_edp:
        raise AssertionError(f"{name}: oracle re-evaluates the best "
                             f"mappings to {float(edp)!r}, served "
                             f"{best_edp!r}")
    hw = minimal_hw_for(cspec, result.best_mappings, layers)
    if result.best_hw != hw:
        raise AssertionError(f"{name}: best_hw {result.best_hw} is not "
                             f"the mappings' minimal hardware {hw}")
    edp_hw, _ = evaluate_workload(result.best_mappings, layers, hw=hw,
                                  spec=cspec)
    log(f"oracle {name}: best_edp={best_edp!r} reproduced; at the "
        f"returned best_hw {hw}: edp={float(edp_hw)!r}")


def check_served(served: dict) -> None:
    """Every outcome ok and not degraded, equal to a direct
    `api.run_request`, and reproduced by the host oracle; the
    duplicate deduplicated; no fault counter moved."""
    from repro.api import run_request

    records = served["records"]
    checked = set()
    for i, rec in enumerate(records):
        if rec["request_id"] in checked:
            continue
        checked.add(rec["request_id"])
        out = rec["outcome_json"]
        name = f"request {i} ({rec['payload']['workload']['name']})"
        if out["status"] != "ok" or out["degraded"]:
            raise AssertionError(f"{name}: status {out['status']!r}, "
                                 f"degraded {out['degraded']}, "
                                 f"error {out['error']}")
        req = _direct_request(rec["payload"])
        _same_answer(out, run_request(req).result, name + " vs direct")
        check_oracle(rec["outcome"].result, req, out["best_edp"], name)
    dups = [r for r in records[1:]
            if r["payload"] == records[0]["payload"]]
    if not all(r["deduplicated"]
               and r["request_id"] == records[0]["request_id"]
               for r in dups):
        raise AssertionError("a duplicate submission was not "
                             "deduplicated onto the first request")
    faults = served["stats"]["faults"]
    moved = {k: faults[k] for k in ("retries", "quarantined", "timeouts",
                                    "degraded_requests") if faults[k]}
    if moved:
        raise AssertionError(f"fault counters moved: {moved}")


def check_reference(record: dict) -> None:
    """The plain reference: the sequential driver on the same workload
    and config gives the served `best_edp` and `n_evals`."""
    from repro.core.search import dosa_search

    req = _direct_request(record["payload"])
    ref = dosa_search(req.workload, req.config)
    out = record["outcome_json"]
    got = (out["best_edp"], out["n_evals"])
    want = (float(ref.best_edp), int(ref.n_evals))
    if got != want:
        raise AssertionError(f"served {got} vs sequential reference "
                             f"{want}")


def sharded_phase(wl, population: int, shards: int, steps: int = 100,
                  round_every: int = 25) -> dict:
    """A `population`-member search on Gemmini at `shards` against
    `shards=1`: bit-identical results, and the fused program's output
    spread over `shards` devices with an equal block each."""
    import jax
    from repro.core.mapping import seed_population
    from repro.core.search import (SearchConfig, dosa_search,
                                   make_fused_runner, shard_population)

    base = SearchConfig(steps=steps, round_every=round_every,
                        n_start_points=population, seed=0,
                        start_points="cosa-device")
    runs = {}
    for n in (1, shards):
        cfg = dataclasses.replace(base, shards=n)
        t0 = time.monotonic()
        res = dosa_search(wl, cfg, population=population)
        runs[n] = (res, time.monotonic() - t0)
        log(f"sharded: shards={n} P={population} "
            f"best_edp={res.best_edp!r} n_evals={res.n_evals} "
            f"seconds={runs[n][1]:.3f}")
    a, b = runs[1][0], runs[shards][0]
    if (a.best_edp, a.n_evals, a.history) != \
            (b.best_edp, b.n_evals, b.history):
        raise AssertionError(f"shards={shards} differs from shards=1: "
                             f"{b.best_edp!r}/{b.n_evals} vs "
                             f"{a.best_edp!r}/{a.n_evals}")

    cfg = dataclasses.replace(base, shards=shards)
    _, theta, orders = seed_population(
        wl.dims_array(), population, jax.random.PRNGKey(0), mode="cosa")
    theta, orders = shard_population(theta, orders, shards)
    (f_seg, _, _), _ = make_fused_runner(wl, cfg)[0](
        theta, orders, n_full=steps // round_every,
        rem=steps % round_every, seg_len=round_every, shards=shards)
    placed = sorted((s.device.id, s.data.shape[1])
                    for s in f_seg.addressable_shards)
    log(f"sharded: output members per device {placed}")
    if len(placed) != shards or any(m != population // shards
                                    for _, m in placed):
        raise AssertionError(f"population not spread over {shards} "
                             f"devices: {placed}")
    return {"identical": True, "members_per_device": placed,
            "seconds": {n: runs[n][1] for n in runs}}


# ---------------------------------------------------------------- main

def _compile_report(tracer) -> str:
    spans = tracer.spans_named("engine.compile")
    return (f"compile: {len(spans)} programs, "
            f"{tracer.total_s('engine.compile'):.3f} s")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1, help="4: run only the sharded phase")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "smoke"),
                    help="output directory (server checkpoints)")
    args = ap.parse_args(argv)

    import jax
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev['platform']!r}); "
              "nothing run", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {dev['count']}", file=sys.stderr)
        return 1

    from repro import obs
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"jax {jax.__version__}  device_kind {dev['kind']}  "
        f"devices {dev['count']}  compile cache {cache_dir}")
    tracer = obs.Tracer()
    obs.set_tracer(tracer)
    with obs.compile_spans():
        if args.chips == SHARDED_CHIPS:
            from repro.workloads.dnn_zoo import resnet50
            sharded_phase(resnet50(), SHARDED_POPULATION, SHARDED_CHIPS)
        else:
            out_dir = pathlib.Path(args.out)
            payloads = smoke_payloads()
            served = serve_phase(payloads, out_dir)
            for rec in served["records"]:
                out = rec["outcome_json"]
                log(f"served {rec['payload']['workload']['name']} "
                    f"on {rec['payload']['config']['spec']} "
                    f"steps={rec['payload']['config']['steps']}: "
                    f"{out['status']} best_edp={out['best_edp']!r} "
                    f"n_evals={out['n_evals']} "
                    f"dedup={rec['deduplicated']} "
                    f"submit_to_outcome_s={rec['seconds']:.3f}")
            log("served " + _compile_report(tracer))
            check_served(served)
            check_reference(served["records"][-1])
            log(f"faults {json.dumps(served['stats']['faults'])}")
        log("total " + _compile_report(tracer))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
