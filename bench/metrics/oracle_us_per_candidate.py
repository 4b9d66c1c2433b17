"""Host oracle replay per candidate: the summed length of the
`search.oracle` spans ended in the window over the rounded candidates
they replayed (their `candidates` attr)."""


def read(run):
    spent, candidates = 0.0, 0
    for s in run.spans_in_window("search.oracle"):
        n = s["attrs"].get("candidates")
        if n is None:
            continue
        spent += s["t_end"] - s["t_start"]
        candidates += n
    return 1e6 * spent / candidates if candidates else None
