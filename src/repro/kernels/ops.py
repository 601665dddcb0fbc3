"""Jit'd public wrappers for the Pallas kernels.

Lowered for the CPU the kernels run in interpret mode; lowered for a TPU
they compile to Mosaic (``repro.kernels.lowered_for_platform``).
``use_pallas_attention()`` lets the model stack swap the pure-jnp chunked
attention for the kernel on real hardware.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rbm_copy import (rbm_copy as _copy, villa_gather as _gather,
                                    villa_scatter as _scatter)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret), (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    # Backward via the jnp oracle (flash-recompute): on TPU this is where a
    # dedicated bwd kernel slots in.  The oracle applies the same causal +
    # window masking as the forward kernel, and block_q/block_k are pure
    # tiling (no semantic effect), so gradients are block-size invariant —
    # guarded by test_kernels.py::test_flash_attention_windowed_causal_
    # grad_equivalence and ..._grad_block_size_invariant.
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.flash_attention_ref(
        q_, k_, v_, causal=causal, window=window), q, k, v)
    return vjp(g)


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128, interpret=None):
    return _flash_vjp(q, k, v, causal, window, block_q, block_k, interpret)


@partial(jax.jit, static_argnames=("tile_rows", "lanes", "interpret"))
def rbm_copy(x, *, tile_rows: int = 256, lanes: int = 128, interpret=None):
    return _copy(x, tile_rows=tile_rows, lanes=lanes, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def villa_gather(pages, table, *, interpret=None):
    return _gather(pages, table, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def villa_scatter(pages, table, updates, *, interpret=None):
    """NOTE: ``pages`` is DONATED (it aliases the output, the whole point of
    the in-place row-buffer write) — on backends that honor donation the
    caller must not reuse it afterwards; pass ``pages + 0`` to keep a copy."""
    return _scatter(pages, table, updates, interpret=interpret)


# Oracles re-exported for benchmarks/tests.
flash_attention_ref = jax.jit(ref.flash_attention_ref,
                              static_argnames=("causal", "window"))
rbm_copy_ref = jax.jit(ref.rbm_copy_ref)
villa_gather_ref = jax.jit(ref.villa_gather_ref)
villa_scatter_ref = jax.jit(ref.villa_scatter_ref)
