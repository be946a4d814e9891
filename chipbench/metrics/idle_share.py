"""idle_share (%): 1 - (union of the intervals in which an operation ran
on the device) / (the traced window)."""

from harness import trace as tr


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - tr.busy_s(run.trace) / tr.window_s(run.trace))
