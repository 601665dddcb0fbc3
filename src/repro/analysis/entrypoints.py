"""The registry of audited jitted entry points.

``default_targets()`` builds each registered hot-path program at reduced
audit geometry and pairs it with the contract its docstring promises:

========================  =========================================
entry point               contract
========================  =========================================
``decode``                donate cache (arg 1); zero host transfers
``prefill[bucket=k]``     donate cache (arg 1); one per bucket length
``suspend``               donate store+sums (args 1,2); uint8-preserving
``suspend_many``          donate store+sums (args 1,2); ONE dispatch/wave
``resume``                donate cache+store+fail (args 0,1,3); uint8-prsv
``resume_many``           donate cache+store+fail (args 0,1,3); ONE disp
``migrate_gather``        no donation; uint8-preserving
``migrate``               donate dst pool (arg 0); uint8-preserving
``simulate_params``       pure simulator: no donation, no host transfer
========================  =========================================

The suspend/resume signatures carry the checksum sidecar (PR 7): suspends
also emit per-page sums; resumes also consume them and fold the verify
verdict into a donated failure counter — still zero extra host transfers.
The migrate scatter leg takes the traced ``(mode, index, xor)`` fault operand
(NULL_FAULT on clean runs): one compilation serves clean and chaos runs.

Everything is traced/lowered statically — no engine loop runs, no tokens
decode.  The geometry is deliberately tiny (2 slots, max_len 32): the
contracts are shape-independent, so proving them at reduced geometry proves
the mechanism.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.dispatch import AuditTarget, EntryContract
from repro.faults.spec import NULL_FAULT

AUDIT_SLOTS = 2
AUDIT_MAX_LEN = 32
AUDIT_SESSIONS = 4
AUDIT_WAVE = 2          # wave width audited for *_many / migrate


def prefill_buckets(engine) -> List[int]:
    """The declared compile-key set: the image of ``_bucket_len`` over all
    admissible lengths."""
    return sorted({engine._bucket_len(n)
                   for n in range(1, engine.max_len + 1)})


def engine_targets(engine) -> List[AuditTarget]:
    """Audit targets for one constructed :class:`~repro.serve.engine.Engine`
    (its live jit objects — the audit sees exactly what serving runs)."""
    slots = engine.slots
    cache, sessions, params = engine.cache, engine.sessions, engine.params
    sums, failed = engine.session_sums, engine.verify_failed
    i32 = jnp.int32
    wave = min(AUDIT_WAVE, slots)
    targets = [
        AuditTarget(
            "decode", engine._decode,
            (params, cache, jnp.zeros(slots, i32), jnp.zeros(slots, i32),
             jnp.zeros(slots, bool)),
            EntryContract(donate=frozenset({1}), max_compiles=1)),
        AuditTarget(
            "suspend", engine._suspend,
            (cache, sessions, sums, i32(0), i32(0)),
            EntryContract(donate=frozenset({1, 2}), uint8_preserving=True)),
        AuditTarget(
            "suspend_many", engine._suspend_many,
            (cache, sessions, sums, jnp.arange(wave, dtype=i32),
             jnp.arange(wave, dtype=i32)),
            EntryContract(donate=frozenset({1, 2}), uint8_preserving=True)),
        AuditTarget(
            "resume", engine._resume,
            (cache, sessions, sums, failed, i32(0), i32(0)),
            EntryContract(donate=frozenset({0, 1, 3}),
                          uint8_preserving=True)),
        AuditTarget(
            "resume_many", engine._resume_many,
            (cache, sessions, sums, failed, jnp.arange(wave, dtype=i32),
             jnp.arange(wave, dtype=i32)),
            EntryContract(donate=frozenset({0, 1, 3}),
                          uint8_preserving=True)),
    ]
    buckets = prefill_buckets(engine)
    for lb in buckets:
        if engine.cfg.mrope:
            positions = None
        else:
            positions = jnp.zeros((1, lb), i32)
        targets.append(AuditTarget(
            f"prefill[bucket={lb}]", engine._prefill,
            (params, cache, jnp.zeros((1, lb), i32), positions,
             i32(lb), i32(0)),
            EntryContract(donate=frozenset({1}),
                          max_compiles=len(buckets))))
    return targets


def cluster_targets(cluster) -> List[AuditTarget]:
    """The migration route's two jitted legs of a constructed cluster (>= 2
    replicas), audited at wave width :data:`AUDIT_WAVE`: the gather leg that
    reads the source pool on the source device, and the donating scatter leg
    on the destination device."""
    if cluster.n_replicas < 2:
        return []
    if cluster._migrate_exec is None:
        cluster._build_migrate_exec()
    spp = cluster.page_spec.n_pages
    table = jnp.arange(AUDIT_WAVE * spp, dtype=jnp.int32)
    src = cluster.replicas[0].sessions.slow
    dst = cluster.replicas[1].sessions.slow
    pages = jnp.zeros((AUDIT_WAVE * spp,) + dst.shape[-2:], dst.dtype)
    fault = jnp.asarray(NULL_FAULT)
    return [
        AuditTarget(
            "migrate_gather", cluster._migrate_gather, (src, table),
            EntryContract(donate=frozenset(), uint8_preserving=True)),
        AuditTarget(
            "migrate", cluster._migrate_exec, (dst, table, pages, fault),
            EntryContract(donate=frozenset({0}), uint8_preserving=True))]


def controller_targets() -> List[AuditTarget]:
    """The DRAM controller simulator: ONE jit serves every copy-mechanism
    preset (mechanism parameters are traced data, never compile keys)."""
    from repro.core.dram import controller as DC
    from repro.core.dram import traces as DT
    from repro.core.dram.spec import DDR3_1600

    tcfg = DT.TraceConfig(n_requests=64)
    trace = DT.generate(jax.random.key(0), tcfg)
    mcfg = DC.MechanismConfig()
    p = DC.mechanism_params(mcfg, DDR3_1600)
    return [AuditTarget(
        "simulate_params", DC.simulate_params, (trace, p),
        EntryContract(donate=frozenset(), max_compiles=1),
        kwargs=dict(n_banks=tcfg.n_banks, n_cores=tcfg.n_cores,
                    villa_cfg=mcfg.villa, unroll=4))]


def default_targets(arch: str = "tinyllama-1.1b"):
    """(targets, engine) at reduced audit geometry — every registered
    jitted entry point in the serving stack plus the controller simulator."""
    from repro.configs import get_reduced
    from repro.models import lm
    from repro.serve.cluster import Cluster
    from repro.serve.engine import Engine

    cfg = get_reduced(arch)
    params = lm.init_lm(cfg, jax.random.key(0))
    engine = Engine(cfg, params, slots=AUDIT_SLOTS, max_len=AUDIT_MAX_LEN,
                    n_sessions=AUDIT_SESSIONS)
    cluster = Cluster(cfg, params, n_replicas=2, slots=1,
                      max_len=AUDIT_MAX_LEN, n_sessions=AUDIT_SESSIONS)
    targets = (engine_targets(engine) + cluster_targets(cluster)
               + controller_targets())
    return targets, engine
