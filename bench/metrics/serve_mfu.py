"""Device, whole step: the operations the window's prompts and output
tokens need (``bench/work.py``, the builder's counts: every prompt through
the layers with causal attention and the head for its last position; every
decode step's slots through the layers and the head, attending over the
positions they hold) over the window's seconds times the chip's peak, in
%.  The work comes from the harness's counts, the window from the host
clock."""
from bench import work


def read(run):
    if run.peaks is None:
        return None
    w = run.window
    flops = sum(work.prefill_flops(run, n) for n in run.rec["prefill"])
    flops += sum(work.decode_step(run, a, h)[0]
                 for a, h in run.rec["decode"])
    return 100.0 * flops / ((w.t1 - w.t0) * run.peaks["flops_per_s"])
