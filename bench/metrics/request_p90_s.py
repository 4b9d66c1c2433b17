"""90th percentile (nearest rank) of submit-to-outcome time over the
requests completed in the window."""
from harness import quantile


def read(run):
    return quantile([c.t_done - c.t_submit for c in run.completions], 0.9)
