"""Reduce a profiler trace and host spans to device numbers.

The traced run records the JAX profiler (`.xplane.pb`) over the window.
This module turns it into:

* `busy_s`: the union of the intervals in which an operation ran on
  the device, inside the traced window (averaged over devices);
* `fused_s`, `fused_runs`: device time and count of the fused search
  program's executions, found by its jit name;
* `top_ops`: the device operations of the fused program that took
  most time;
* `gaps`: the longest idle gaps, each named by the host span that
  covers most of it, or "unattributed" where no span touches it.

Host spans come on the host's monotonic clock; `clock_anchor` ties
that clock to the profiler's: an annotation opened right after a
monotonic reading marks the same instant in the trace.
"""
from __future__ import annotations

import bisect
import glob
import os
import time

FUSED_PROGRAM = "run_fused"
ANCHOR = "bench.clock_anchor"
# The profiler records from the window's opening to the first
# completion this long after it (a mix may set its own `trace_s`): a
# trace holds every device op, ~0.5 million (~80 MB) a second of fused
# program, so a whole 51 s window would be slow to write and read back.
TRACE_S = 20.0
# Device lines read; ops nest (a while op spans its body's ops), so
# only leaf ops rank among the top ones.
DEVICE_LINES = ("XLA Modules", "XLA Ops")
CONTAINERS = ("%while", "%conditional", "%call")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python calls are not traced
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def clock_anchor() -> float:
    """A monotonic reading marked in the trace by an annotation."""
    import jax
    t = time.monotonic()
    with jax.profiler.TraceAnnotation(ANCHOR):
        pass
    return t


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


def planes_of(data) -> tuple[list, list]:
    """(device planes, host planes) as lists of (name, {line: events}),
    each event a (name, start_ns, end_ns) tuple; of a device plane only
    `DEVICE_LINES`, of the host only the clock anchor."""
    dev, host = [], []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") and \
            "CPU" not in plane.name and "CUSTOM" not in plane.name
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            if is_dev and line.name not in DEVICE_LINES:
                continue
            events = []
            for e in line.events:
                if is_dev or e.name == ANCHOR:
                    start = float(e.start_ns)
                    events.append((e.name, start,
                                   start + float(e.duration_ns)))
            # one line per thread, and threads share names ("python")
            lines.setdefault(line.name, []).extend(events)
        (dev if is_dev else host).append((plane.name, lines))
    return dev, host


def op_label(name: str) -> str:
    """A short label of an HLO op event: its instruction name and
    result type (`%fusion.12 f32[128,21,7]`)."""
    head, _, rest = name.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head} {kind}".strip()


def union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]



def anchor_offset_ns(host: list, t_anchor: float) -> float | None:
    """Profiler ns minus monotonic ns, from the anchor annotation."""
    for _, lines in host:
        for events in lines.values():
            for name, start, _ in events:
                if name == ANCHOR:
                    return start - t_anchor * 1e9
    return None


def reduce(dev: list, host: list, t_anchor: float, t0: float, t1: float,
           spans: list[dict], n_top: int = 10) -> dict:
    """Device numbers for the traced window [t0, t1] (monotonic s)."""
    off = anchor_offset_ns(host, t_anchor)
    if off is None:
        raise ValueError("the clock anchor is not in the trace")
    lo, hi = t0 * 1e9 + off, t1 * 1e9 + off
    busy, fused_s, fused_runs, ops = [], 0.0, 0, {}
    busy_iv: list[list[float]] = []
    for _, lines in dev:
        op_line = lines.get("XLA Ops", [])
        mod_line = lines.get("XLA Modules", [])
        ivs = _clip([(a, b) for _, a, b in (op_line or mod_line)], lo, hi)
        merged = union(ivs)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        busy_iv += merged
        fused = [(a, b) for n, a, b in mod_line if FUSED_PROGRAM in n]
        for a, b in _clip(fused, lo, hi):
            fused_s += (b - a) / 1e9
            fused_runs += 1
        fused_iv = union(fused)
        starts = [a for a, _ in fused_iv]
        for name, a, b in op_line:
            if b <= lo or a >= hi or name.startswith(CONTAINERS) \
                    or not _inside(fused_iv, starts, a):
                continue
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + (min(b, hi) - max(a, lo)) / 1e9
    n_dev = max(len(dev), 1)
    gaps = _gaps(union(busy_iv), lo, hi)
    host_spans = [(s["name"], s["t_start"] * 1e9 + off,
                   s["t_end"] * 1e9 + off) for s in spans
                  if s.get("t_end") is not None]
    named = [[_attribute(a, b, host_spans), (b - a) / 1e9]
             for a, b in gaps[:n_top]]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:n_top]
    return {"busy_s": sum(busy) / n_dev, "window_s": t1 - t0,
            "t0": t0, "t1": t1,
            "fused_s": fused_s / n_dev, "fused_runs": fused_runs,
            "devices": len(dev),
            "top_ops": [[n, s] for n, s in top], "gaps": named}


def _inside(merged: list[list[float]], starts: list[float],
            t: float) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and merged[i][0] <= t <= merged[i][1]


def _gaps(busy: list[list[float]], lo: float, hi: float):
    """Idle intervals between busy ones inside [lo, hi], longest
    first."""
    out, prev = [], lo
    for a, b in busy:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    if hi > prev:
        out.append((prev, hi))
    return sorted(out, key=lambda g: -(g[1] - g[0]))


def _attribute(a: float, b: float, spans) -> str:
    """The host span that covers most of the gap [a, b]; among spans
    that cover it alike, the shortest (innermost).  A gap that no span
    touches is "unattributed"."""
    best, cover = "unattributed", 0.0
    best_len = float("inf")
    for name, s, e in spans:
        c = min(b, e) - max(a, s)
        if c <= 0:
            continue
        if c > cover * 1.000001 or (c >= cover * 0.999999
                                    and e - s < best_len):
            best, cover, best_len = name, c, e - s
    return best
