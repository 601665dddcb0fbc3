"""Output tokens emitted in the window over the window's seconds.  Host
clock."""
from bench import driver


def read(run):
    w = run.window
    return driver.window_tokens(w) / (w.t1 - w.t0)
