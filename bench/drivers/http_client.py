"""Closed-loop HTTP clients of the co-search server.

Run as its own process by `http_closed`, so the load it offers never
takes the server's interpreter lock.  It imports nothing but the
standard library (no JAX).  It reads one JSON job from stdin:

    {"url": "http://127.0.0.1:PORT", "payloads": [...], "clients": 8,
     "poll_s": 0.02}

Each client thread takes the next payload in order, POSTs it to
`/v1/search`, polls `/v1/result/<id>` every `poll_s` until the outcome
is there, and then takes the next one.  It prints one JSON line per
event on stdout: `accepted` when the POST is answered, `done` when the
outcome is seen, both with times on the machine's monotonic clock.  A
line `stop` on stdin ends the run: no new request is sent and the
process exits.  Its last line, `lateness`, says how late the polls
woke against their schedule.
"""
from __future__ import annotations

import json
import sys
import threading
import time
import urllib.error
import urllib.request


def _call(method: str, url: str, body: dict | None = None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type":
                                          "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read() or b"null")


class Clients:
    def __init__(self, job: dict):
        self.url = job["url"]
        self.payloads = job["payloads"]
        self.poll_s = float(job["poll_s"])
        self.n = int(job["clients"])
        self.stop = threading.Event()
        self._lock = threading.Lock()
        self._next = 0
        self.late: list[float] = []

    def emit(self, **rec) -> None:
        line = json.dumps(rec)
        with self._lock:
            sys.stdout.write(line + "\n")
            sys.stdout.flush()

    def _take(self) -> int | None:
        with self._lock:
            if self.stop.is_set() or self._next >= len(self.payloads):
                return None
            i = self._next
            self._next += 1
            return i

    def client(self) -> None:
        while True:
            i = self._take()
            if i is None:
                return
            t_submit = time.monotonic()
            code, reply = _call("POST", self.url + "/v1/search",
                                self.payloads[i])
            t_acc = time.monotonic()
            if code != 202:
                self.emit(event="refused", i=i, code=code, t=t_acc,
                          reply=reply)
                continue
            rid = reply["request_id"]
            self.emit(event="accepted", i=i, rid=rid, t_submit=t_submit,
                      t_accepted=t_acc, dedup=reply["deduplicated"])
            due = t_acc
            while not self.stop.is_set():
                code, out = _call("GET", self.url + "/v1/result/" + rid)
                if code == 200:
                    self.emit(event="done", i=i, rid=rid,
                              t_submit=t_submit, t_accepted=t_acc,
                              t_done=time.monotonic(),
                              status=out["status"],
                              n_evals=out.get("n_evals", 0))
                    break
                if code != 202:
                    self.emit(event="lost", i=i, rid=rid, code=code,
                              t=time.monotonic())
                    break
                due += self.poll_s
                wait = due - time.monotonic()
                if wait > 0:
                    self.stop.wait(wait)
                woke = time.monotonic()
                with self._lock:
                    self.late.append(max(0.0, woke - due))
                due = max(due, woke)

    def run(self) -> None:
        threads = [threading.Thread(target=self.client, daemon=True)
                   for _ in range(self.n)]
        for t in threads:
            t.start()
        for line in sys.stdin:
            if line.strip() == "stop":
                break
        self.stop.set()
        for t in threads:
            t.join(timeout=5.0)
        late = sorted(self.late)
        self.emit(event="lateness", polls=len(late),
                  mean_ms=1e3 * sum(late) / max(len(late), 1),
                  p99_ms=1e3 * late[int(0.99 * (len(late) - 1))]
                  if late else 0.0,
                  max_ms=1e3 * late[-1] if late else 0.0)


def main() -> int:
    job = json.loads(sys.stdin.readline())
    Clients(job).run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
