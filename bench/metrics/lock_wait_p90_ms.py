"""Nearest-rank 90th percentile of the front-end's waits for the
service lock in the window: each POST's `lock_wait_s` (on its request's
root span, opened once the lock is held, or on the `dedup_hit` event of
a duplicate) and each delivering GET's (on a `delivered` event).  A
wait counts where it lies, from its start to the reading, inside the
window and clear of the gap."""
from harness import quantile


def read(run):
    waits = []
    for s in run.spans:
        if s["name"] != "request":
            continue
        marks = [(s["t_start"], s["attrs"].get("lock_wait_s"))]
        marks += [(t, attrs.get("lock_wait_s"))
                  for t, name, attrs in s.get("events", [])
                  if name in ("dedup_hit", "delivered")]
        waits += [w for t, w in marks
                  if w is not None and run.holds(t - w, t)]
    return None if not waits else 1e3 * quantile(waits, 0.9)
