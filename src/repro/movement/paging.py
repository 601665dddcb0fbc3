"""Byte-paged, dtype-preserving layout staging for movement plans.

A snapshot of any pytree slice (e.g. one slot of a batched KV cache) is
staged as fixed-size *pages* of raw bytes (default 8x128 = 1 KB — one DRAM
row in the paper's geometry).  Every leaf is bitcast to uint8, so int8 stays
1 byte/elem and bf16 stays 2 — no float32 upcast anywhere on a movement
path, and restore is bit-exact by construction.  This is the ``pack_pages``
/ ``unpack_pages`` leg pair of a :class:`~repro.movement.plan.MovementPlan`.

Everything here is shape-static and traceable: ``pack_slot`` /
``unpack_into_slot`` take a *traced* slot index, so a plan containing these
legs still lowers to ONE jitted dispatch with donated buffers.

(This module is the substrate-level home of what used to live in
``repro.serve.paged_store``; the serving module now delegates here.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp


def _to_bytes(x: jax.Array) -> jax.Array:
    """Bitcast any leaf to a flat uint8 vector (dtype-preserving, bit-exact)."""
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


def _from_bytes(b: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize == 1:
        return jax.lax.bitcast_convert_type(b.reshape(shape), dtype)
    return jax.lax.bitcast_convert_type(b.reshape(shape + (itemsize,)), dtype)


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """Static byte layout of one snapshot (one slot slice of a pytree)."""
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    leaf_dtypes: Tuple[Any, ...]
    leaf_offsets: Tuple[int, ...]       # byte offset of each leaf
    total_bytes: int                    # sum of leaf bytes (true, not upcast)
    page_rows: int = 8
    page_lanes: int = 128

    @property
    def page_bytes(self) -> int:
        return self.page_rows * self.page_lanes

    @property
    def n_pages(self) -> int:
        return -(-self.total_bytes // self.page_bytes)

    @classmethod
    def for_cache(cls, cache, *, page_rows: int = 8,
                  page_lanes: int = 128) -> "PageSpec":
        """Layout for one slot of a batched cache (leaves (reps, slots, ...))."""
        leaves = jax.tree_util.tree_leaves(cache)
        shapes, dtypes, offsets = [], [], []
        off = 0
        for leaf in leaves:
            shape = leaf.shape[:1] + leaf.shape[2:]      # drop the slot dim
            shapes.append(shape)
            dtypes.append(leaf.dtype)
            offsets.append(off)
            off += math.prod(shape) * leaf.dtype.itemsize
        return cls(tuple(shapes), tuple(dtypes), tuple(offsets), off,
                   page_rows, page_lanes)


def pack_slot(spec: PageSpec, cache, slot) -> jax.Array:
    """Snapshot cache[:, slot] into (n_pages, P, d) uint8 pages (traceable)."""
    leaves = jax.tree_util.tree_leaves(cache)
    parts: List[jax.Array] = []
    for leaf in leaves:
        one = jax.lax.dynamic_index_in_dim(leaf, slot, axis=1, keepdims=False)
        parts.append(_to_bytes(one))
    flat = jnp.concatenate(parts)
    pad = spec.n_pages * spec.page_bytes - spec.total_bytes
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(spec.n_pages, spec.page_rows, spec.page_lanes)


def page_checksums(pages: jax.Array) -> jax.Array:
    """Per-page position-weighted byte checksum (uint32, traceable).

    ``pages`` is (..., P, d) uint8 — any leading batch dims, last two dims
    one page.  Each page's checksum is ``sum(byte[i] * (2*i + 1)) mod 2^32``.
    The weights are odd, hence units mod 2^32, so ANY single-byte change
    (delta in [-255, 255] \\ {0}) shifts the sum by ``delta * w_i != 0`` —
    every single-byte corruption is detected, by construction.  Computed
    in-graph: a pack leg pays a few uint32 FLOPs per byte and no host sync.
    Its operations carry the named scope ``movement.checksum``.
    """
    with jax.named_scope("movement.checksum"):
        pb = pages.shape[-2] * pages.shape[-1]
        flat = pages.reshape(pages.shape[:-2] + (pb,)).astype(jnp.uint32)
        w = 2 * jnp.arange(pb, dtype=jnp.uint32) + 1
        return jnp.sum(flat * w, axis=-1, dtype=jnp.uint32)


def verify_pages(pages: jax.Array, sums: jax.Array) -> jax.Array:
    """Count of pages whose recomputed checksum mismatches ``sums``.

    ``pages`` (..., n_pages, P, d) against ``sums`` (..., n_pages); returns
    an int32 scalar (traceable — the verdict rides whatever sync the caller
    already performs, never forcing one of its own).
    """
    return jnp.sum((page_checksums(pages) != sums).astype(jnp.int32))


def row_page_table(spec: PageSpec, row) -> jax.Array:
    """The flat-pool page table addressing one store row's pages.

    Pool rows are ``spec.n_pages`` consecutive pages once the pool is
    reshaped flat, so row ``r`` (a traced index is fine) is pages
    ``r * n_pages + [0, n_pages)``.  Fork-aware callers pass the PHYSICAL
    row a :class:`repro.fork.ForkPageTable` resolved, so every alias of a
    shared row gathers the same bytes.
    """
    return jnp.asarray(row, jnp.int32) * spec.n_pages + jnp.arange(
        spec.n_pages, dtype=jnp.int32)


def unpack_into_slot(spec: PageSpec, cache, slot, pages: jax.Array):
    """Restore pages into cache[:, slot]; inverse of :func:`pack_slot`."""
    flat = pages.reshape(-1)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    out = []
    for leaf, shape, dtype, off in zip(leaves, spec.leaf_shapes,
                                       spec.leaf_dtypes, spec.leaf_offsets):
        nbytes = math.prod(shape) * jnp.dtype(dtype).itemsize
        piece = _from_bytes(jax.lax.slice(flat, (off,), (off + nbytes,)),
                            shape, dtype)
        out.append(jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.expand_dims(piece, 1), slot, axis=1))
    return jax.tree_util.tree_unflatten(treedef, out)
