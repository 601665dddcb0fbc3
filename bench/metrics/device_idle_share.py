"""Device: the share of the traced window in which no operation ran on the
chip, in %.  Device trace."""
from bench import trace as T


def read(run):
    if run.trace is None or not run.trace.ops and not run.trace.modules:
        return None
    return 100.0 * (1.0 - T.busy_s(run.trace) / run.trace.window_s)
