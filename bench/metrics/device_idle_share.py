"""1 - device busy time / traced window, from the profiler trace (the
union of the intervals in which an operation ran on the device)."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
