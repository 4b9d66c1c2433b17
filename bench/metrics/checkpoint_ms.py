"""Checkpoint time per scheduler step: the summed `checkpoint.*` spans
(save, restore, gc) ended in the window over the `service.step` spans
ended in it."""


def read(run):
    steps = len(run.spans_in_window("service.step"))
    if not steps:
        return None
    spent = sum(s["t_end"] - s["t_start"] for s in run.spans
                if s["name"].startswith("checkpoint.")
                and s["t_end"] is not None
                and run.holds(s["t_start"], s["t_end"]))
    return 1e3 * spent / steps
