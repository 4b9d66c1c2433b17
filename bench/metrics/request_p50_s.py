"""Median submit-to-outcome time of the requests completed in the
window: from the client's POST to its first poll that saw the
outcome."""
from harness import quantile


def read(run):
    return quantile([c.t_done - c.t_submit for c in run.completions], 0.5)
