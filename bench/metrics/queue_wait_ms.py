"""Mean of the service's `queue_wait` spans (submit to batch join) that
closed in the window."""
from harness import mean


def read(run):
    waits = [1e3 * (s["t_end"] - s["t_start"])
             for s in run.spans_in_window("queue_wait")]
    return mean(waits)
