"""Traffic generator: back-to-back searches through `api.run_request`.

An architect's population sweep: one network, one large population of
device-seeded start points, the configuration's protocol, searched one
after another from a single caller.  Search seeds come from `--seed`.

Mix parameters (`bench/traffic/<mix>.json`):

* `workload`: a workload name of the configuration;
* `population`: start points, all advanced as one population;
* `start_points`: the program's start-point mode (e.g. "cosa-device");
* `search_sample` (optional): how many of the window's searches the
  search reference (`bench/search_ref.py`) re-runs after the window,
  drawn from `--seed`.

The window opens as the first timed search starts (nothing is in
flight then) and closes when the first search that ends at least
`seconds` later completes, so no search is cut or counted twice.
"""
from __future__ import annotations

import time

import numpy as np


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int, workdir, log):
        self.config, self.mix, self.log = config, mix, log
        self.layers = config["workloads"][mix["workload"]]["layers"]
        proto = config["protocol"]
        self.protocol = {"steps": proto["steps"],
                         "round_every": proto["round_every"],
                         "n_start_points": mix["population"],
                         "max_reject_tries": proto["max_reject_tries"],
                         "device_seeded": mix["start_points"] != "cosa"}
        ss = np.random.SeedSequence(seed)
        warm, timed = ss.spawn(2)
        self._warm_seed = int(np.random.default_rng(warm).integers(2**31 - 1))
        self._seeds = np.random.default_rng(timed)
        self.results = []       # (completion, outcome) of timed searches

    def _request(self, seed: int):
        from repro.api import SearchRequest
        from repro.core.problem import Layer, Workload
        from repro.serve.server import SPEC_REGISTRY
        from repro.core.search import SearchConfig
        wl = Workload(layers=tuple(
            Layer(dims=tuple(lay["dims"]), wstride=lay["wstride"],
                  hstride=lay["hstride"], repeat=lay["repeat"],
                  name=lay["name"]) for lay in self.layers),
            name=self.mix["workload"])
        p = self.protocol
        cfg = SearchConfig(steps=p["steps"], round_every=p["round_every"],
                           n_start_points=p["n_start_points"], seed=seed,
                           max_reject_tries=p["max_reject_tries"],
                           lr=self.config["protocol"]["lr"],
                           penalty_weight=self.config["protocol"][
                               "penalty_weight"],
                           start_points=self.mix["start_points"],
                           spec=SPEC_REGISTRY[self.config["spec_name"]])
        return SearchRequest(workload=wl, config=cfg,
                             population=p["n_start_points"])

    def setup(self) -> None:
        """One whole search warms every program and host path the timed
        searches take (same shapes, another seed)."""
        from repro.api import run_request
        run_request(self._request(self._warm_seed))

    def window(self, seconds: float, before_open, after_done) -> tuple:
        from harness import Completion
        from repro.api import run_request
        before_open()
        t_open = time.monotonic()
        done = []
        while True:
            seed = int(self._seeds.integers(2**31 - 1))
            t0 = time.monotonic()
            out = run_request(self._request(seed))
            t1 = time.monotonic()
            c = Completion(t_submit=t0, t_done=t1, samples=int(out.n_evals),
                           ok=out.ok, key=str(seed))
            done.append(c)
            self.results.append((c, out))
            after_done(t1, t_open)
            if t1 - t_open >= seconds:
                return t_open, t1, done, 0

    def answers(self) -> list[dict]:
        out = []
        for c, o in self.results:
            res = o.result
            out.append({
                "ok": o.ok and res is not None, "key": c.key,
                "best_edp": None if res is None else float(res.best_edp),
                "n_evals": None if res is None else int(res.n_evals),
                "history": [] if res is None else
                [[int(e), float(v)] for e, v in res.history],
                "mappings": [] if res is None else
                [(m.f.tolist(), m.order.tolist())
                 for m in res.best_mappings],
                "protocol": self.protocol, "layers": self.layers,
                "search": {"seed": int(c.key),
                           "members": self.mix["population"],
                           "start_points": self.mix["start_points"],
                           "protocol": self.config["protocol"]}})
        return out

    def spans(self) -> list[dict]:
        return []

    def params(self) -> dict:
        return {k: self.protocol[k] for k in
                ("steps", "round_every", "n_start_points")}

    def close(self) -> None:
        pass
