"""Set-up time: process start to the opening of the window (imports,
the persistent cache's loads or compiles, the cell's warm-up)."""


def read(run):
    return run.setup_s
