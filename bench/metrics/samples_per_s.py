"""DOSA samples per second: every GD member-step and every oracle
evaluation (the paper's `n_evals`) of the searches completed in the
window, over the window's length.  The window opens and closes where
no search is cut, so each counts whole or not at all."""


def read(run):
    if run.window_s <= 0 or not run.completions:
        return None
    return sum(c.samples for c in run.completions if c.ok) / run.window_s
