"""90th percentile of the client's POST /v1/search time, from sending
to the server's reply, over the requests completed in the window.  The
front-end answers only once it gets the service's lock, which the
scheduler holds for a whole step."""
from harness import quantile


def read(run):
    waits = [1e3 * (c.t_accepted - c.t_submit) for c in run.completions
             if c.t_accepted is not None]
    return quantile(waits, 0.9)
