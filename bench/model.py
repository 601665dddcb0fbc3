"""A configuration file's model: its builder, found by name, and its
weights.

A configuration file names its builder as it names its reference:
``"model": "<name>"`` is the module ``bench/models/<name>.py`` under the
run's root, loaded by path, so that a copy of the tree finds its own
files.  A builder gives ``model_config(conf)``, the engine's
``ModelConfig`` for the file; ``weights(cfg, key)``, the weights made from
the seed in the engine's layout; and the counts of the work its served
requests need, which ``bench/work.py`` dispatches to
(``kv_bytes_per_position``, ``decode_step``, ``prefill_flops``).  A new
architecture is a new builder; it may import ``bench.models.dense`` and
override only what differs.
"""
from __future__ import annotations

import pathlib

import jax

from repro.models import lm

ROOT = pathlib.Path(__file__).resolve().parents[1]


def builder(conf: dict, root: pathlib.Path = ROOT):
    """The builder that the configuration ``conf`` names, under ``root``."""
    from bench.run import load_module
    return load_module(root / "bench" / "models" / f"{conf['model']}.py")


def init_weights(build, cfg, seed_key: jax.Array) -> dict:
    """The builder's weights in one jitted call on the default device.
    Raises if the engine's parameter layout differs from the one the
    builder makes."""
    want = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.key(0)))
    got = jax.eval_shape(lambda: build.weights(cfg, seed_key))
    shape = lambda t: jax.tree.map(lambda s: (s.shape, s.dtype), t)
    if shape(want) != shape(got):
        raise ValueError(f"the engine's parameter layout differs from the "
                         f"weight layout of builder {build.__name__}")
    return jax.jit(build.weights, static_argnums=0)(cfg, seed_key)
