"""Default movement backends: the real-array layer of the substrate.

Registered on ``import repro.movement``.  Each backend is the thinnest
possible adapter from a typed leg to the underlying movement engine:

  pack_pages / unpack_pages  ->  repro.movement.paging (uint8 bitcast legs)
  page_gather / page_scatter ->  Pallas kernels (scalar-prefetched tables,
                                 LIP double buffering, input/output aliasing)
  tile_copy                  ->  Pallas rbm_copy (HBM->HBM through VMEM)
  hop_chain                  ->  ppermute hop chains over a mesh axis
                                 (rbm.rbm_hop shift / rbm.lisa_copy chain),
                                 or a cross-device transfer (``device``)
  host_stage                 ->  device_get / device_put across the channel

The VILLA tier legs (``tier_read`` / ``tier_write``) are registered by
:mod:`repro.core.lisa.villa_cache`, which owns the caching policy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lisa import rbm
from repro.kernels.rbm_copy import rbm_copy, villa_gather, villa_scatter
from repro.movement import paging
from repro.movement.plan import HopChainLeg, Leg, PackLeg, TileCopyLeg, \
    UnpackLeg
from repro.movement.registry import Env, register_backend


@register_backend("pack_pages")
def _pack_pages(leg: PackLeg, env: Env) -> Env:
    # Plural env keys declare a wave (see _unpack_pages): a fused suspend
    # wave packs every slot in one dispatch, one slot after another — a
    # vmapped pack would widen every slot's bytes at once (on TPU the byte
    # split runs in uint32), which at published widths outgrows the chip.
    env = dict(env)
    if leg.batch > 1 or "slots" in env:
        env["data"] = jax.lax.map(
            lambda s: paging.pack_slot(leg.page_spec, env["cache"], s),
            env["slots"])
    else:
        env["data"] = paging.pack_slot(leg.page_spec, env["cache"],
                                       env["slot"])
    # The detection sidecar: every pack leg emits per-page checksums
    # alongside the payload (ECC computed at the subarray boundary).  Pure
    # in-graph uint32 arithmetic — no extra dispatch, no host sync.
    env["sums"] = paging.page_checksums(env["data"])
    return env


@register_backend("unpack_pages")
def _unpack_pages(leg: UnpackLeg, env: Env) -> Env:
    # A wave is declared by the plural env keys, so a fused plan of batch 1
    # (a one-element resume wave) still takes the batched path.
    env = dict(env)
    expected = env.get("sums")
    if expected is not None:
        # Verify at unpack against the checksums the caller carried from
        # pack time.  ``verify_fail`` counts ITEMS with any corrupt page
        # (one incident per session) and stays on-device: the verdict rides
        # the caller's existing sync, never adding one.
        cs = paging.page_checksums(env["data"])
        mismatch = cs != jnp.asarray(expected, jnp.uint32)
        if mismatch.ndim > 1:          # wave: (k, n_pages) -> per-item any
            env["verify_fail"] = jnp.sum(
                jnp.any(mismatch, axis=-1).astype(jnp.int32))
        else:
            env["verify_fail"] = jnp.any(mismatch).astype(jnp.int32)
    if leg.batch > 1 or "slots" in env:
        def body(cache, xs):
            slot, pages = xs
            return paging.unpack_into_slot(leg.page_spec, cache, slot,
                                           pages), None
        env["cache"], _ = jax.lax.scan(body, env["cache"],
                                       (env["slots"], env["data"]))
    else:
        env["cache"] = paging.unpack_into_slot(leg.page_spec, env["cache"],
                                               env["slot"], env["data"])
    return env


@register_backend("page_gather")
def _page_gather(leg, env: Env) -> Env:
    env = dict(env)
    env["data"] = villa_gather(env[leg.pool_key], env[leg.table_key])
    return env


@register_backend("page_scatter")
def _page_scatter(leg, env: Env) -> Env:
    env = dict(env)
    env[leg.pool_key] = villa_scatter(env[leg.pool_key], env[leg.table_key],
                                      env["data"])
    return env


@register_backend("tile_copy")
def _tile_copy(leg: TileCopyLeg, env: Env) -> Env:
    env = dict(env)
    env["data"] = rbm_copy(env["data"], tile_rows=leg.tile_rows,
                           lanes=leg.lanes)
    return env


@register_backend("hop_chain")
def _hop_chain(leg: HopChainLeg, env: Env) -> Env:
    env = dict(env)
    if "device" in env:
        # Single-process replica fleet (serve/cluster.py), one replica per
        # device: the leg is the cross-device transfer of the staged pages
        # to the destination replica's device, executed between the route's
        # jitted gather and scatter.  Under shard_map the same leg runs the
        # ppermute chain below (pinned by the forced-host mesh tests).
        env["data"] = jax.device_put(env["data"], env["device"])
        return env
    if leg.src is None or leg.dst is None:
        env["data"] = rbm.rbm_hop(env["data"], leg.axis, leg.step)
    else:
        env["data"] = rbm.lisa_copy(env["data"], leg.src, leg.dst, leg.axis,
                                    wraparound=leg.wraparound)
    return env


@register_backend("page_alias")
def _page_alias(leg: Leg, env: Env) -> Env:
    # Zero-copy fork fast path (repro/fork): the physical bytes stay where
    # they are — the ForkPageTable repointed the child's logical row on the
    # host BEFORE this plan executed.  The leg exists so the alias is a
    # first-class priced movement (RowClone FPM on the lisa arm, the
    # avoided per-session copy on the memcpy arm); executing it dispatches
    # NOTHING (pinned by repro.analysis.testlib in tests/test_fork.py).
    return env


@register_backend("host_stage")
def _host_stage(leg: Leg, env: Env) -> Env:
    env = dict(env)
    leaves = env["data"]
    if leg.to_host:
        env["data"] = [None if l is None else np.asarray(jax.device_get(l))
                       for l in leaves]
    else:
        shardings = env.get("shardings") or [None] * len(leaves)
        env["data"] = [
            None if a is None else
            (jax.device_put(a, s) if s is not None else jnp.asarray(a))
            for a, s in zip(leaves, shardings)]
    return env
