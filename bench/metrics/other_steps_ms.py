"""Scheduler interleave: over the requests finalized in the window,
the mean time, between a request's batch join and its finalize, that
the scheduler spent in `service.step` spans of other tasks."""
from harness import mean


def read(run):
    steps = [(s["t_start"], s["t_end"], s["attrs"].get("task_id"))
             for s in run.spans
             if s["name"] == "service.step" and s["t_end"] is not None]
    if not steps:
        return None
    waits = []
    for s in run.spans:
        if s["name"] != "request" or s["t_end"] is None:
            continue
        joins = [(t, a.get("task_id")) for t, name, a in s.get("events", [])
                 if name == "batch_join"]
        if not joins or not run.holds(joins[0][0], s["t_end"]):
            continue
        t0, t1 = joins[0][0], s["t_end"]
        own = {tid for _, tid in joins}
        waits.append(sum(max(0.0, min(t1, b) - max(t0, a))
                         for a, b, tid in steps if tid not in own))
    return None if not waits else 1e3 * mean(waits)
