"""RBM hop primitives on a TPU mesh axis — the LISA substrate, adapted.

Every function here is meant to run *inside* ``jax.shard_map`` (or a manual
SPMD region) over a named mesh axis.  The mapping (DESIGN.md Sec. 2):

  DRAM subarray            ->  device position on the axis
  RBM (adjacent buffers)   ->  ``jax.lax.ppermute`` one-step shift
  RBM hop chain            ->  sequential single-pair ppermutes (linear cost)
  1-to-N via latching      ->  every intermediate device keeps a copy
  bank-level parallelism   ->  per-hop compute-overlap hook (``ring_scan``)

The ring collectives built from hop chains are what the training runtime uses
for FSDP weight gathering / gradient reduce-scatter and for ring attention
(sequence parallelism); XLA emits its own collectives for the pjit paths, and
these explicit schedules are the LISA-faithful alternative we hillclimb with.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp


def _shift_perm(n: int, step: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + step) % n) for i in range(n)]


def rbm_hop(x: jax.Array, axis_name: str, step: int = 1) -> jax.Array:
    """One RBM hop: every device's shard moves to its neighbor (+step)."""
    n = jax.lax.axis_size(axis_name)
    return jax.lax.ppermute(x, axis_name, _shift_perm(n, step))


def lisa_copy(x: jax.Array, src: int, dst: int, axis_name: str,
              wraparound: bool = True) -> jax.Array:
    """Point-to-point shard movement via a neighbor-hop chain (LISA-RISC).

    After the call, device ``dst`` holds device ``src``'s shard; all other
    devices keep their own.  The schedule is ``hops`` sequential single-pair
    ppermutes — each hop crosses exactly one ICI link, so cost is linear in
    hop count, exactly Table 1's structure.
    """
    n = jax.lax.axis_size(axis_name)
    if src == dst:
        return x
    fwd = (dst - src) % n
    if wraparound:
        # Ring: take the shorter direction.
        step, hops = ((-1, n - fwd) if (n - fwd) < fwd else (1, fwd))
    else:
        # Linear chain (no wrap links): the direct route is the only route.
        step, hops = ((1, dst - src) if dst >= src else (-1, src - dst))
    v = x
    cur = src
    for _ in range(hops):
        nxt = (cur + step) % n
        v = jax.lax.ppermute(v, axis_name, [(cur, nxt)])
        cur = nxt
    idx = jax.lax.axis_index(axis_name)
    return jnp.where(idx == dst, v, x)


def lisa_broadcast(x: jax.Array, src: int, axis_name: str,
                   dsts: Optional[Sequence[int]] = None) -> jax.Array:
    """1-to-N multicast with intermediate latching (paper Sec. 5.2).

    One hop chain from ``src`` to the farthest destination; *every* device the
    data passes through latches a copy — that is the free multicast the paper
    points out ("moving data ... latches the source row's data in all the
    intermediate subarrays' row buffers").  ``dsts=None`` broadcasts to all.

    Returns: on devices in ``dsts`` (and src) the source shard, elsewhere the
    device's own shard.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if dsts is None:
        dsts = [d for d in range(n) if d != src]
    want = jnp.zeros((n,), bool).at[jnp.array(list(dsts) + [src])].set(True)[idx]

    # Walk both directions to the farthest requested destination.
    fwd_hops = max(((d - src) % n) for d in dsts)
    bwd_hops = max(((src - d) % n) for d in dsts)
    if fwd_hops + bwd_hops >= n:          # full ring: one direction suffices
        fwd_hops, bwd_hops = n - 1, 0

    latched = x
    got = idx == src
    for direction, hops in ((1, fwd_hops), (-1, bwd_hops)):
        v = x
        cur = src
        for _ in range(hops):
            nxt = (cur + direction) % n
            v = jax.lax.ppermute(v, axis_name, [(cur, nxt)])
            cur = nxt
            here = idx == cur
            latched = jnp.where(here, v, latched)
            got = got | here
    return jnp.where(want & got, latched, x)


def ring_scan(x: jax.Array, axis_name: str,
              fn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
              init: jax.Array, reverse: bool = False) -> jax.Array:
    """The compute-overlap hook (bank-level-parallelism analogue).

    Runs ``n`` steps; at step ``k`` the device holds the shard originally on
    device ``(idx -+ k) mod n`` and calls ``acc = fn(acc, shard, src_index)``.
    The ppermute for step k+1 overlaps with fn's compute at step k (XLA
    schedules the collective-permute-start before the dot).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    step = -1 if reverse else 1
    perm = _shift_perm(n, step)
    # mark device-varying for scan
    init = jax.lax.pcast(init, (axis_name,), to="varying")

    def body(k, carry):
        acc, buf = carry
        src = (idx - step * k) % n
        nxt = jax.lax.ppermute(buf, axis_name, perm)   # overlaps with fn
        acc = fn(acc, buf, src)
        return acc, nxt

    acc, _ = jax.lax.fori_loop(0, n, body, (init, x))
    return acc


def ring_allgather(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    """All-gather via an RBM hop ring: n-1 hops, each carrying one shard."""
    n = jax.lax.axis_size(axis_name)
    shape = (n,) + x.shape

    def take(acc, shard, src):
        return jax.lax.dynamic_update_index_in_dim(acc, shard, src, 0)

    out = ring_scan(x, axis_name, take, jnp.zeros(shape, x.dtype))
    if axis != 0:
        out = jnp.moveaxis(out, 0, axis)
        return out.reshape(x.shape[:axis] + (n * x.shape[axis],) + x.shape[axis + 1:])
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Reduce-scatter via a hop ring.  ``x``: (n, chunk...) per device;
    returns chunk ``idx`` summed across devices (n-1 hops)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = _shift_perm(n, 1)

    def body(t, acc):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        return acc + x[(idx - t - 1) % n]

    acc = x[(idx - 1) % n]
    return jax.lax.fori_loop(1, n, body, acc)


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce = reduce-scatter + all-gather over the hop ring.

    2(n-1) hops each carrying 1/n of the payload — the bandwidth-optimal
    schedule, and structurally the paper's hop chain run twice.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    mine = ring_reduce_scatter(chunks, axis_name)
    full = ring_allgather(mine, axis_name)
    return full.reshape(-1)[:x.size].reshape(x.shape)


def ring_allgather_matmul(x: jax.Array, w: jax.Array, axis_name: str
                          ) -> jax.Array:
    """FSDP forward pattern with per-hop overlap: ``w`` is sharded on its
    *input* dim over the axis; computes ``x @ w_full`` without ever
    materialising ``w_full`` — each hop's shard is consumed by a partial
    matmul while the next hop is in flight (LISA's "other banks keep
    serving" property).

    x: (..., d) with d = n * d_shard;  w: (d_shard, f)  ->  (..., f)
    """
    n = jax.lax.axis_size(axis_name)
    d_shard = w.shape[0]

    def partial(acc, w_shard, src):
        x_slice = jax.lax.dynamic_slice_in_dim(x, src * d_shard, d_shard, -1)
        return acc + x_slice @ w_shard

    out_shape = x.shape[:-1] + (w.shape[1],)
    init = jnp.zeros(out_shape, jnp.result_type(x.dtype, w.dtype))
    return ring_scan(w, axis_name, partial, init)
