"""Engine decode: the least time the chip could take for the decode steps
of the window over their device time (``decode_step_batched``), in %.
The least time of a step is the larger of the operations it needs over the
peak rate and the bytes it needs over the HBM bandwidth (``bench/work.py``,
the builder's counts at the configuration's dtype: weights read once, the
keys and values of the positions held, one new row per active slot).
Device trace."""
from bench import trace as T
from bench import work


def read(run):
    if run.trace is None or run.peaks is None or not run.rec["decode"]:
        return None
    secs, n = T.program_time(run.trace, "decode_step_batched")
    if not n:
        return None
    least = [max(f / run.peaks["flops_per_s"],
                 b / run.peaks["hbm_bytes_per_s"])
             for f, b in (work.decode_step(run, a, h)
                          for a, h in run.rec["decode"])]
    return 100.0 * sum(least) / len(least) * n / secs
