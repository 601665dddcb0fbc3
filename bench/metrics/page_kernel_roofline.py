"""Kernels: the page kernels' share of the HBM roofline, in %.  What the
window's suspends and resumes need is each moved session's held keys and
values read once and written once, at the configuration's dtype
(``bench/work.py``, the builder's counts); the time is the device time of
every ``villa_gather`` and ``villa_scatter`` kernel in the window, whatever
they move.  Device trace."""
from bench import trace as T
from bench import work


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs = sum(T.op_time(run.trace, k)[0]
               for k in ("villa_gather", "villa_scatter"))
    held = sum(sum(w) for w in run.rec["suspend"] + run.rec["resume"])
    if not secs or not held:
        return None
    need = 2 * held * work.kv_bytes_per_position(run)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / secs
