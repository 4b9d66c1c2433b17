"""Traffic generator: closed-loop clients of `CoSearchServer` over HTTP.

The server runs in this process, on the chip; the clients run in a
child process (`http_client.py`) that never imports JAX.  Each client
sends one search, waits for its outcome, then sends the next, so the
loop is closed: a slow server is offered less load.

Mix parameters (`bench/traffic/<mix>.json`):

* `workload`: the configuration's workload set requests draw from;
* `pick`: `"layers"` (each request is one layer of the set, as a
  single-layer workload) or `"networks"` (each request is a whole
  network named in `networks`);
* `popularity`: items in order of popularity; item k (from 1) has
  weight 1 / k**`zipf_s` (`zipf_s` 0 is uniform);
* `block`, `repeat_share`: requests come in blocks of `block`; each
  block holds every item in proportion to its weight and
  `repeat_share` exact repeats of an earlier request of the run, in an
  order drawn from `--seed` (so every seed sends the same set of
  requests per block, in another order, with other search seeds);
* `clients`, `poll_s`, `lead_in_s`: the closed loop;
* `trace_s` (optional): how much of a traced run's window the profiler
  records (see `devtrace.TRACE_S`); a served mix runs ~25 short
  programs a second, ~1 million device ops.

Set-up warms every (served workload, member bucket) program the run
can reach: for each served workload it sends, to a second in-process
service, one batch per bucket of as few requests as land in it.  The
window opens at the first outcome at least `lead_in_s` after the
clients start, and closes at the first outcome at least `seconds`
after that.
"""
from __future__ import annotations

import json
import pathlib
import queue
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
WARM_SEED_BASE = 2**31      # warm-up seeds never equal a timed one
STALL_S = 120.0     # no outcome for this long: the window ends anyway
ANSWER_WAIT_S = 60.0  # how long past the close an answer may come


def _apportion(weights: np.ndarray, total: int) -> np.ndarray:
    """Largest-remainder split of `total` in proportion to weights."""
    share = weights / weights.sum() * total
    counts = np.floor(share).astype(int)
    rest = total - counts.sum()
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int, workdir, log):
        self.config, self.mix, self.log = config, mix, log
        self.workdir = pathlib.Path(workdir)
        proto = config["protocol"]
        self.search = {"steps": proto["steps"],
                       "round_every": proto["round_every"],
                       "n_start_points": proto["n_start_points"],
                       "max_reject_tries": proto["max_reject_tries"],
                       "spec": config["spec_name"]}
        self.protocol = {k: proto[k] for k in
                         ("steps", "round_every", "n_start_points",
                          "max_reject_tries")}
        self.protocol["device_seeded"] = False
        ss = np.random.SeedSequence(seed)
        self._rng_warm, self._rng_timed = (np.random.default_rng(s)
                                           for s in ss.spawn(2))
        self.items = self._items()
        self.payloads = self._stream()
        self.server = None
        self.padded = False     # the served service pads problems up
        self.done: list[dict] = []
        self.accepted: dict[str, dict] = {}
        self.failures: list[dict] = []
        self.lateness: dict = {}
        self.t_close = None

    # ---------------------------------------------------------- requests

    def _items(self) -> list[tuple[str, list[dict]]]:
        """(name, layers) of every item requests draw from, most popular
        first."""
        wls = self.config["workloads"]
        mix = self.mix
        if mix["pick"] == "layers":
            by_name = {lay["name"]: lay
                       for lay in wls[mix["workload"]]["layers"]}
            return [(n, [by_name[n]]) for n in mix["popularity"]]
        return [(n, wls[n]["layers"]) for n in mix["popularity"]]

    def _payload(self, item: int, seed: int) -> dict:
        name, layers = self.items[item]
        return {"workload": {"name": name, "layers": [dict(lay)
                                                      for lay in layers]},
                "config": dict(self.search, seed=seed)}

    def _stream(self) -> list[dict]:
        mix, rng = self.mix, self._rng_timed
        ranks = np.arange(1, len(self.items) + 1, dtype=float)
        weights = ranks ** -float(mix["zipf_s"])
        n_rep = int(round(mix["repeat_share"] * mix["block"]))
        counts = _apportion(weights, mix["block"] - n_rep)
        fresh: list[dict] = []
        out: list[dict] = []
        for _ in range(mix["blocks"]):
            block = np.concatenate([np.repeat(np.arange(len(counts)),
                                              counts),
                                    np.full(n_rep, -1)])
            rng.shuffle(block)
            for k in block:
                if k < 0 and fresh:
                    out.append(fresh[int(rng.integers(len(fresh)))])
                    continue
                if k < 0:     # a repeat before any request: a fresh one
                    k = int(rng.integers(len(self.items)))
                p = self._payload(int(k), int(rng.integers(2**31 - 1)))
                fresh.append(p)
                out.append(p)
        return out

    # ---------------------------------------------------------- set-up

    def _service_config(self, sub: str):
        from repro.serve.cosearch_service import ServiceConfig
        path = self.workdir / sub
        shutil.rmtree(path, ignore_errors=True)
        return ServiceConfig(checkpoint_dir=str(path))

    def setup(self) -> None:
        from repro.core.archspec import bucket_workload
        from repro.serve.cosearch_service import CoSearchService, _pad_size
        from repro.serve.server import CoSearchServer, parse_search_payload
        cfg = self._service_config("warm")
        starts = self.search["n_start_points"]
        most = min(self.mix["clients"], cfg.batch_max)
        sizes: dict[int, int] = {}
        for n in range(1, most + 1):
            sizes.setdefault(_pad_size(n * starts, cfg.member_buckets), n)
        steps, every = self.search["steps"], self.search["round_every"]
        # one segment runs every program when all segments are alike
        budget = 1 if steps % every == 0 else None
        served = {}     # the service's canonical workload -> an item
        for k in range(len(self.items)):
            wl = parse_search_payload(self._payload(k, 0)).workload
            if cfg.bucket_workloads:
                wl = bucket_workload(wl)
            served.setdefault(tuple((lay.dims, lay.wstride, lay.hstride,
                                     lay.repeat) for lay in wl.layers), k)
        svc = CoSearchService(cfg)
        seed = WARM_SEED_BASE
        for k in served.values():
            for n in sorted(sizes.values()):
                for _ in range(n):
                    seed += 1 + int(self._rng_warm.integers(1000))
                    body = self._payload(k, seed)
                    # one start-point try each: the programs are the
                    # same, the host's rejection retries are not needed
                    body["config"]["max_reject_tries"] = 1
                    req = parse_search_payload(
                        dict(body, segment_budget=budget))
                    svc.submit(req)
                svc.drain()
        self.log(f"warm-up: {len(served)} served workloads x member "
                 f"buckets {sorted(sizes)}")
        serve_cfg = self._service_config("serve")
        self.padded = serve_cfg.bucket_workloads
        self.server = CoSearchServer(serve_cfg)
        self.url = "http://%s:%d" % self.server.start()

    # ---------------------------------------------------------- window

    def window(self, seconds: float, before_open, after_done) -> tuple:
        from harness import Completion
        before_open()
        job = {"url": self.url, "payloads": self.payloads,
               "clients": self.mix["clients"],
               "poll_s": self.mix["poll_s"]}
        child = subprocess.Popen(
            [sys.executable, str(HERE / "http_client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)
        lines: queue.Queue = queue.Queue()

        def pump():
            for line in child.stdout:
                lines.put(line)
            lines.put(None)

        reader = threading.Thread(target=pump, daemon=True)
        reader.start()
        child.stdin.write(json.dumps(job) + "\n")
        child.stdin.flush()
        t_start = time.monotonic()
        t_open = t_close = None
        t_last = t_start + self.mix["lead_in_s"]
        try:
            while t_close is None:
                now = time.monotonic()
                if now - t_last > STALL_S:
                    # requests are stuck: end the window where it stands;
                    # the stuck ones count as unanswered
                    self.log(f"no outcome for {STALL_S} s: window ends")
                    t_open = now if t_open is None else t_open
                    t_close = now
                    break
                try:
                    line = lines.get(timeout=0.5)
                except queue.Empty:
                    continue
                if line is None:
                    raise RuntimeError("the client process ended early")
                ev = self._event(json.loads(line))
                if ev["event"] != "done":
                    continue
                t_last = ev["t_done"]
                if t_open is None:
                    if t_last >= t_start + self.mix["lead_in_s"]:
                        t_open = t_last
                    continue
                after_done(t_last, t_open)
                if t_last >= t_open + seconds:
                    t_close = t_last
        finally:
            child.stdin.write("stop\n")
            child.stdin.flush()
            child.stdin.close()
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
            reader.join(timeout=10)
            while not lines.empty():
                line = lines.get()
                if line is not None:
                    self._event(json.loads(line))
        self.t_close = t_close
        self.log("load generator lateness: " + json.dumps(self.lateness))
        inside = [d for d in self.done if t_open < d["t_done"] <= t_close]
        comps = [Completion(t_submit=d["t_submit"], t_done=d["t_done"],
                            samples=int(d["n_evals"]),
                            ok=d["status"] == "ok",
                            t_accepted=d["t_accepted"], key=d["rid"])
                 for d in inside]
        self.window_done = inside
        return t_open, t_close, comps, len(
            [f for f in self.failures if t_open < f["t"] <= t_close])

    def _event(self, ev: dict) -> dict:
        kind = ev["event"]
        if kind == "accepted":
            self.accepted[ev["rid"]] = ev
        elif kind == "done":
            self.done.append(ev)
        elif kind in ("refused", "lost"):
            self.failures.append(ev)
        elif kind == "lateness":
            self.lateness = ev
        return ev

    # ---------------------------------------------------------- answers

    def unanswered(self) -> int:
        """Requests accepted before the window closed that have no good
        outcome a minute past the close."""
        # an outcome, once stored, is never changed: reading the
        # service's table while its scheduler runs is safe
        svc = self.server.service
        due = [rid for rid, ev in self.accepted.items()
               if ev["t_accepted"] <= self.t_close]
        deadline = time.monotonic() + ANSWER_WAIT_S
        while time.monotonic() < deadline:
            missing = [rid for rid in due if svc.outcome(rid) is None]
            if not missing:
                break
            time.sleep(0.05)
        bad = 0
        for rid in due:
            out = svc.outcome(rid)
            bad += out is None or out.status != "ok"
        return bad + len(self.failures)

    def answers(self) -> list[dict]:
        svc = self.server.service
        out = []
        for d in self.window_done:
            o = svc.outcome(d["rid"])
            res = None if o is None else o.result
            payload = self.payloads[d["i"]]
            out.append({
                "ok": o is not None and o.status == "ok" and res is not None,
                "key": d["rid"],
                "best_edp": None if res is None else float(res.best_edp),
                "n_evals": None if res is None else int(res.n_evals),
                "history": [] if res is None else
                [[int(e), float(v)] for e, v in res.history],
                "mappings": [] if res is None else
                [(m.f.tolist(), m.order.tolist())
                 for m in res.best_mappings],
                "protocol": self.protocol,
                "layers": payload["workload"]["layers"],
                "padded": self.padded})
        return out

    def spans(self) -> list[dict]:
        tracer = self.server.service.tracer
        return [{"name": s.name, "t_start": s.t_start, "t_end": s.t_end,
                 "attrs": dict(s.attrs),
                 "events": [(t, n, dict(a)) for t, n, a in s.events]}
                for s in tracer.spans()]

    def params(self) -> dict:
        return {"n_start_points": self.search["n_start_points"],
                "round_every": self.search["round_every"],
                "steps": self.search["steps"]}

    def close(self) -> None:
        if self.server is not None:
            self.server.stop()
            self.server = None
        shutil.rmtree(self.workdir, ignore_errors=True)
