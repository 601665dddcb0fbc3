"""Pallas TPU bulk-copy kernel: RBM at the VMEM level.

A row-buffer movement is a wide, latency-optimal transfer between adjacent
storage arrays.  The TPU analogue at the kernel level is a tiled HBM->HBM
copy staged through VMEM: the Pallas grid pipeline keeps *two* tile buffers
in flight — while tile i computes (stores), tile i+1's DMA is already running
("precharging" the idle buffer: LISA-LIP, DESIGN.md Sec. 5.4).

Tiles are (rows x 128-lane) MXU/VPU-aligned.  ``rbm_copy`` is the movement
engine used by the serving tier-promotion path and checkpoint resharding when
running on real TPUs; on CPU it validates in interpret mode against the
identity oracle.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lowered_for_platform


def _copy_kernel(src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def rbm_copy(x: jax.Array, *, tile_rows: int = 256, lanes: int = 128,
             interpret: Optional[bool] = None) -> jax.Array:
    """Copy ``x`` (any shape) through VMEM tiles of (tile_rows, lanes)."""
    flat = x.reshape(-1)
    n = flat.size
    per_tile = tile_rows * lanes
    n_tiles = -(-n // per_tile)
    pad = n_tiles * per_tile - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    tiled = flat.reshape(n_tiles * tile_rows, lanes)

    out = lowered_for_platform(partial(
        pl.pallas_call,
        _copy_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile_rows, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(tiled.shape, x.dtype)),
        tiled, interpret=interpret)
    return out.reshape(-1)[:n].reshape(x.shape)


def _gather_kernel(table_ref, pages_ref, out_ref):
    # pages_ref block is selected by the scalar-prefetched table entry;
    # the body is a pure VMEM move.
    out_ref[...] = pages_ref[...]


def villa_gather(pages: jax.Array, table: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Gather whole pages by a page table: out[j] = pages[table[j]].

    pages: (N, P, d) — P*d must tile to (8, 128) multiples for real TPUs.
    The page table is scalar-prefetched so the grid pipeline can launch the
    DMA for page j+1 while page j is being written (LIP again) — this is the
    VILLA fast-tier read path.
    """
    N, P, d = pages.shape
    n_out = table.shape[0]

    return lowered_for_platform(partial(
        pl.pallas_call,
        _gather_kernel,
        name="villa_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_out,),
            in_specs=[pl.BlockSpec((1, P, d), lambda j, table: (table[j], 0, 0))],
            out_specs=pl.BlockSpec((1, P, d), lambda j, table: (j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_out, P, d), pages.dtype)),
        table.astype(jnp.int32), pages, interpret=interpret)


def _scatter_kernel(table_ref, pages_ref, upd_ref, out_ref):
    # out block j is routed to pages[table[j]] by the scalar-prefetched
    # table; the body is a pure VMEM store of the staged update tile.
    out_ref[...] = upd_ref[...]


def villa_scatter(pages: jax.Array, table: jax.Array, updates: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Scatter whole pages by a page table: out = pages; out[table[j]] = updates[j].

    pages: (N, P, d), updates: (n, P, d) — the VILLA fast-tier *write* path,
    dual of :func:`villa_gather`.  The grid runs over updates only: page j+1's
    DMA is in flight while page j stores (LIP double buffering, DESIGN.md
    Sec. 5.4), and untouched pages never move — ``pages`` is aliased into the
    output (the donated row buffer), so cost is O(touched pages), not O(N).
    Duplicate table entries resolve in grid order (last write wins).
    """
    N, P, d = pages.shape
    n_upd = updates.shape[0]

    return lowered_for_platform(partial(
        pl.pallas_call,
        _scatter_kernel,
        name="villa_scatter",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_upd,),
            in_specs=[pl.BlockSpec((1, P, d), lambda j, table: (0, 0, 0)),
                      pl.BlockSpec((1, P, d), lambda j, table: (j, 0, 0))],
            out_specs=pl.BlockSpec((1, P, d), lambda j, table: (table[j], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, P, d), pages.dtype),
        input_output_aliases={1: 0}),   # pages buffer IS the output
        table.astype(jnp.int32), pages, updates, interpret=interpret)
