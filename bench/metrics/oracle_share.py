"""Share of the window spent in the `search.oracle` spans of a direct
(unserved) search: host replay of every rounded candidate through the
oracle."""


def read(run):
    if run.window_s <= 0:
        return None
    busy = 0.0
    for s in run.spans:
        if s["name"] != "search.oracle" or s["t_end"] is None:
            continue
        busy += run.covered(s["t_start"], s["t_end"])
    return busy / run.window_s
