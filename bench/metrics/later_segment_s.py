"""Mean length of the `segment` spans after a task's first, ended in the
window (each task and segment once): dispatch, read-back, oracle replay,
theta rebuild and checkpoint."""
from harness import mean, metric_reader


def read(run):
    return mean(metric_reader("first_segment_s").segments(run, first=False))
