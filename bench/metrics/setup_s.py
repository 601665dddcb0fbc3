"""Set-up: process start to the opening of the window (imports, weights
from the seed, engine and store, every program warmed or loaded from the
compile cache).  Host clock."""


def read(run):
    return run.setup_s
