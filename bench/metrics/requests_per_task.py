"""Mean batch size of the tasks formed in the window: the `batch_size`
of each task's `batch_join` events on the requests' root spans."""
from harness import mean


def read(run):
    sizes = {}
    for s in run.spans:
        if s["name"] != "request":
            continue
        for t, name, attrs in s.get("events", []):
            if name == "batch_join" and run.holds(t, t):
                sizes[attrs["task_id"]] = attrs["batch_size"]
    return mean(list(sizes.values()))
