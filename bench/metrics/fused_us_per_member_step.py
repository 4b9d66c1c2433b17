"""Device time of the fused search program per GD member-step: the
durations of its executions in the profiler trace (found by its jit
name), over the member-steps the program was asked to advance in the
traced window.  Member-steps count the search's own start points, so
padding a population up to a bucket shows as a higher figure."""


def member_steps(run) -> int:
    t0, t1 = run.trace["t0"], run.trace["t1"]
    p = run.params
    steps = 0
    for s in run.spans:
        if s["t_end"] is None or not (t0 <= s["t_start"] and s["t_end"] <= t1):
            continue
        a = s["attrs"]
        if s["name"] == "search.fused_dispatch":
            steps += a["population"] * (a["n_full"] * p["round_every"]
                                        + a["rem"])
    seen = set()
    members = {}
    for s in run.spans:
        if s["name"] != "request":
            continue
        for _, name, attrs in s.get("events", []):
            if name == "batch_join":
                members[attrs["task_id"]] = (attrs["batch_size"]
                                             * p["n_start_points"])
    n_seg = -(-p["steps"] // p["round_every"]) if "steps" in p else 0
    for s in run.spans:
        if s["name"] != "segment" or s["t_end"] is None:
            continue
        if not (t0 <= s["t_start"] and s["t_end"] <= t1):
            continue
        a = s["attrs"]
        key = (a["task_id"], a["segment"])
        if key in seen or a["task_id"] not in members:
            continue
        seen.add(key)
        last = a["segment"] == n_seg - 1
        length = (p["steps"] - p["round_every"] * (n_seg - 1) if last
                  else p["round_every"])
        steps += members[a["task_id"]] * length
    return steps


def read(run):
    if run.trace is None or run.trace["fused_runs"] == 0:
        return None
    steps = member_steps(run)
    if steps == 0:
        return None
    return 1e6 * run.trace["fused_s"] / steps
