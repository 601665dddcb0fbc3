"""Operations and bytes that the served work needs: the one entry point
for counts.

Each count is the run's builder's (``bench/models/<model>.py``, the module
the run's configuration names), so a new architecture brings its own.
Bytes are counted at the configuration's published dtype (``torch_dtype``):
what the published model needs does not change with the dtype in which
the program happens to hold a weight or a cache.  ``run`` is the namespace
a metric reader gets: ``cfg``, ``conf`` and ``model``, the builder.
"""
from __future__ import annotations

import jax.numpy as jnp


def value_bytes(conf: dict) -> int:
    """Bytes of one value at the configuration's dtype."""
    return jnp.dtype(conf["torch_dtype"]).itemsize


def kv_bytes_per_position(run) -> int:
    """Keys and values (or their latent form) of one position across the
    layers."""
    return run.model.kv_bytes_per_position(run.cfg, value_bytes(run.conf))


def decode_step(run, n_active: int, held: int) -> tuple:
    """(flops, bytes) of one decode step of ``n_active`` slots holding
    ``held`` positions between them."""
    return run.model.decode_step(run.cfg, n_active, held,
                                 value_bytes(run.conf))


def prefill_flops(run, n: int) -> float:
    """Operations of one prompt of ``n`` tokens."""
    return run.model.prefill_flops(run.cfg, n)
