"""Mean `search.starts` span of the served tasks, ended in the window:
host CoSA start generation with oracle-checked rejection
(`_BatchTask._start_fresh`)."""
from harness import mean


def read(run):
    return mean([1e3 * (s["t_end"] - s["t_start"])
                 for s in run.spans_in_window("search.starts")])
