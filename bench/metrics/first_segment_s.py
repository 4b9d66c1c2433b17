"""Mean length of each task's first `segment` span ended in the window:
host CoSA start generation with rejection, the seg-0 checkpoint and the
first fused segment.  Siblings of one batch share the span's interval,
so each task counts once."""
from harness import mean


def segments(run, first: bool) -> list[float]:
    seen = {}
    for s in run.spans_in_window("segment"):
        a = s["attrs"]
        if (a.get("segment") == 0) != first:
            continue
        seen[(a.get("task_id"), a.get("segment"))] = s["t_end"] - s["t_start"]
    return list(seen.values())


def read(run):
    return mean(segments(run, first=True))
