"""Programs compiled or loaded from the persistent cache inside the
window (`engine.compile` spans of the program's compile listener),
the profiler's export included.  Set-up warms every program the cell
uses, so this should read 0."""


def read(run):
    return sum(1 for s in run.spans
               if s["name"] == "engine.compile" and s["t_end"] is not None
               and run.t_open < s["t_end"] <= run.t_close)
