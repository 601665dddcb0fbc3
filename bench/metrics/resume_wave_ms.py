"""Movement: device time per resume wave (``resume_many``, the only resume
the scheduler issues), in ms.  Device trace."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    secs, n = T.program_time(run.trace, "_resume_many_body")
    return secs * 1e3 / n if n else None
