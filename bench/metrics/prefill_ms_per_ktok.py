"""Engine prefill: device time of the prefill programs
(``prefill_insert``) per thousand prompt tokens prefilled in the window.
Device trace."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    secs, n = T.program_time(run.trace, "prefill_insert")
    tokens = sum(run.rec["prefill"])
    if not n or not tokens:
        return None
    return secs * 1e3 / (tokens / 1e3)
