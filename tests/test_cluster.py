"""Multi-replica cluster serving: live migration (bit-exact, loss-free),
placement scheduling, fused per-replica waves, and the mesh-executed
migration plan.

The decode parity test extends PR 2's suspend→resume equivalence across a
replica boundary: suspend on replica A, hop-chain migrate, resume on
replica B must be token-identical to the uninterrupted single-replica run.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _multidev import run_with_devices

from repro import sched
from repro.analysis import testlib as TL
from repro.configs import get_reduced
from repro.models import lm
from repro.serve.cluster import Cluster
from repro.serve.engine import Engine, Request, UnknownSession


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("tinyllama-1.1b")
    params = lm.init_lm(cfg, jax.random.key(0))
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new, max_len=96):
    cache = lm.init_cache(cfg, 1, max_len=max_len)
    logits, cache = lm.prefill(cfg, params, jnp.asarray(prompt)[None], cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(toks) < n_new:
        lg, cache = lm.decode_step(cfg, params, cache,
                                   jnp.asarray([[toks[-1]]]), jnp.int32(pos))
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return toks


def _drain_to_store(cl, uid, prompt, max_new, replica):
    """Submit on ``replica``, run to completion (auto-suspend), return the
    request."""
    req = Request(uid=uid, prompt=prompt, max_new=max_new)
    cl.submit(req, replica=replica)
    while cl.active:
        cl.step()
    return req


# ---------------------------------------------------------------------------
# live migration: bit-exactness and loss-freedom
# ---------------------------------------------------------------------------

def test_migrated_decode_matches_uninterrupted(setup):
    """suspend on replica A -> hop-chain migrate -> resume on replica B is
    token-identical to the uninterrupted single-replica decode (the PR 2
    parity test, extended across a replica boundary)."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    straight = _greedy_reference(cfg, params, prompt, 8)

    cl = Cluster(cfg, params, n_replicas=4, slots=2, max_len=96,
                 n_sessions=8)
    req = _drain_to_store(cl, 7, prompt, 4, replica=0)
    assert cl.residence[7] == 0
    cl.migrate(7, 2)
    assert cl.residence[7] == 2
    assert 7 not in cl.replicas[0].session_pos      # loss-free handoff:
    assert 7 in cl.replicas[2].session_pos          # exactly one snapshot
    slot = cl.resume(7, extra_new=5)                # seed + 4 new tokens
    assert cl.replica_of(slot) == 2
    r2 = cl.active[slot]
    while cl.active:
        cl.step()
    assert req.generated + r2.generated[1:] == straight
    assert cl.cluster_stats["migrations"] == 1


def test_migration_moves_the_exact_snapshot_bytes(setup):
    """The migrated page block lands in the destination pool bit-for-bit
    (uint8 pages, no re-encode), at the destination's store index."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    _drain_to_store(cl, 3, prompt, 3, replica=0)
    src_block = np.asarray(cl.replicas[0].sessions.slow[3]).copy()
    cl.migrate(3, 1)
    dst_block = np.asarray(cl.replicas[1].sessions.slow[3])
    assert src_block.dtype == np.uint8
    assert np.array_equal(src_block, dst_block)


def test_migrate_many_fuses_one_dispatch_per_route(setup):
    """A rebalance burst of k sessions sharing a route is ONE gather+
    scatter dispatch (one fused page table), not k dispatches."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    cl = Cluster(cfg, params, n_replicas=4, slots=2, max_len=96,
                 n_sessions=16)
    for uid in range(4):
        _drain_to_store(cl, uid,
                        rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                        3, replica=0)
    metas = {u: cl.replicas[0].session_meta(u) for u in range(4)}
    cl.migrate_many([(0, 1), (1, 1), (2, 1), (3, 3)])
    assert cl.cluster_stats["migrations"] == 4
    assert cl.cluster_stats["migration_waves"] == 2     # routes 0->1, 0->3
    TL.assert_compile_count(cl, "migrate", 2)           # one per wave width
    for u, dst in [(0, 1), (1, 1), (2, 1), (3, 3)]:
        assert cl.residence[u] == dst
        assert cl.replicas[dst].session_meta(u) == metas[u]   # loss-free
    # the 3-session wave is priced as 3x the single-session route plan
    assert cl.migration_plan(0, 1, 3).cost.ns_lisa == pytest.approx(
        3 * cl.migration_plan(0, 1).cost.ns_lisa)


def test_migration_errors_are_loud(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    with pytest.raises(UnknownSession):
        cl.migrate(9, 1)                       # never suspended anywhere
    req = Request(uid=0, prompt=prompt, max_new=10)
    cl.submit(req, replica=0)
    with pytest.raises(ValueError, match="active"):
        cl.migrate_many([(0, 1)])              # running sessions don't move
    while cl.active:
        cl.step()
    with pytest.raises(ValueError, match="real route"):
        cl.migrate(0, 0)                       # already home
    with pytest.raises(ValueError, match="duplicate"):
        cl.migrate_many([(0, 1), (0, 1)])
    with pytest.raises(ValueError, match="unknown destination"):
        cl.migrate(0, 5)
    assert cl.cluster_stats["migrations"] == 0  # failed waves mutate nothing


def test_migration_pricing_is_the_ici_hop_model(setup):
    """A route plan prices gather/scatter free and the hop chain at the
    ICI Table-1 analogue: ONE copy, linear in hop distance, with the PCIe
    host path as the memcpy alternative."""
    cfg, params = setup
    from repro.core.lisa.topology import ici_dram_spec
    cl = Cluster(cfg, params, n_replicas=4, slots=1, max_len=96,
                 n_sessions=4)
    nbytes = cl.snapshot_bytes
    for dst, hops in [(1, 1), (2, 2), (3, 1)]:          # ring of 4
        p = cl.migration_plan(0, dst)
        assert [l.kind for l in p.legs] == ["page_gather", "hop_chain",
                                            "page_scatter"]
        assert p.legs[1].hops == hops
        assert p.cost.bytes == nbytes
        assert p.cost.ns_lisa == pytest.approx(
            ici_dram_spec(nbytes).copy_latency("lisa", hops))
        assert p.cost.ns_memcpy == pytest.approx(
            ici_dram_spec(nbytes).copy_latency("memcpy"))
        assert p.cost.advantage > 1.0
    assert cl.hop_ns(0, 0) == 0.0                       # home is free


def test_migration_invalidates_stale_fast_residency(setup):
    """An inbound migration that evicts a colliding store index must also
    drop that index's fast-tier residency — otherwise the next resume
    would hit the OLD session's bytes in the fast pool."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=4)
    p1 = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    _drain_to_store(cl, 1, p1, 3, replica=1)
    # hammer uid 1 on replica 1 until the VILLA policy promotes it
    for _ in range(12):
        cl.resume(1, extra_new=2, replica=1)
        while cl.active:
            cl.step()
        if 1 in cl.replicas[1].fast_resident_uids():
            break
    assert 1 in cl.replicas[1].fast_resident_uids()

    # uid 5 aliases store index 1 (5 % 4); migrating it to replica 1
    # evicts uid 1 there AND must clear the stale fast-tier tag
    p5 = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    straight = _greedy_reference(cfg, params, p5, 6)
    req5 = _drain_to_store(cl, 5, p5, 3, replica=0)
    cl.migrate(5, 1)
    assert 1 not in cl.replicas[1].fast_resident_uids()
    slot = cl.resume(5, extra_new=4)
    r5 = cl.active[slot]
    while cl.active:
        cl.step()
    assert req5.generated + r5.generated[1:] == straight   # not uid 1's bytes


# ---------------------------------------------------------------------------
# fleet mechanics
# ---------------------------------------------------------------------------

def test_fleet_shares_one_compilation(setup):
    """N replicas adopt replica 0's jitted entry points: the whole fleet
    compiles decode/prefill/suspend once, and per-replica decode is still
    one dispatch per replica per step."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    cl = Cluster(cfg, params, n_replicas=3, slots=1, max_len=96,
                 n_sessions=8)
    for r in range(3):
        cl.submit(Request(uid=r, prompt=rng.integers(
            0, cfg.vocab_size, 5 + r).astype(np.int32), max_new=4),
            replica=r)
    before = TL.snapshot_stats(cl)
    cl.step()
    TL.assert_dispatch_delta(before, cl.stats, decode=3)   # one per replica
    while cl.active:
        cl.step()
    TL.assert_compile_count(cl, "decode", 1)            # fleet-shared jit
    TL.assert_compile_count(cl, "prefill", (1, 2))      # per bucket length

    eng_other = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    with pytest.raises(ValueError, match="identically-configured"):
        eng_other.adopt_jits(cl.replicas[0])            # slots differ


def test_cluster_engine_shaped_views(setup):
    cfg, params = setup
    rng = np.random.default_rng(6)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    assert cl.slots == 4 and len(cl.free_slots()) == 4
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    g = cl.submit(Request(uid=0, prompt=prompt, max_new=5), replica=1)
    assert cl.replica_of(g) == 1 and g in cl.active
    assert cl.free_by_replica() == [2, 1]
    cl.suspend(g)
    assert cl.residence[0] == 1 and cl.session_pos[0] == len(prompt)
    # default resume returns home; explicit replica placement migrates
    slot = cl.resume(0, extra_new=2)
    assert cl.replica_of(slot) == 1
    while cl.active:
        cl.step()


# ---------------------------------------------------------------------------
# cluster scheduling: placement + migration as policy decisions
# ---------------------------------------------------------------------------

def _run_crafted(cfg, params, migrate):
    """Drive the shared transient-imbalance scenario (the same arrival
    stream ``benchmarks/run.py cluster`` gates on)."""
    cl = Cluster(cfg, params, n_replicas=4, slots=1, max_len=96,
                 n_sessions=128)
    s = sched.ClusterScheduler(
        cl, arrivals=sched.skewed_residence_burst(cfg.vocab_size),
        cfg=sched.SchedConfig(age_every=64), migrate=migrate)
    summary = s.run()
    return s, cl, summary


def test_migration_on_beats_migration_off_on_slo(setup):
    """The A/B the cluster bench gates on, at test scale: under a skewed-
    residence burst, migration-enabled placement fans out (all SLOs met)
    while migration-off serializes on the home replica (misses)."""
    cfg, params = setup
    s_on, cl_on, sm_on = _run_crafted(cfg, params, migrate=True)
    s_off, cl_off, sm_off = _run_crafted(cfg, params, migrate=False)
    assert sm_on["jobs_completed"] == sm_off["jobs_completed"] == 11
    assert sm_on["slo_attainment"] > sm_off["slo_attainment"]
    assert sm_on["migration"]["sessions_migrated"] >= 2
    # migration-off means exactly that: no session ever crosses replicas
    assert sm_off["migration"]["sessions_migrated"] == 0
    assert cl_off.cluster_stats["migrations"] == 0
    assert all(j.migrations == 0 for j in s_off.metrics.jobs)
    # loss-free both ways: every job serves its exact budget
    for s in (s_on, s_off):
        assert all(j.state == "done" and j.done == j.target_new
                   for j in s.jobs())
    # the cross-replica latency split is reported
    assert sm_on["migration"]["p99_latency_ns_migrated"] is not None
    assert len(sm_on["per_replica_utilization"]) == 4


def test_cluster_scheduler_slot_conservation(setup):
    """The base scheduler's core invariant holds cluster-wide: the job map
    equals the engines' active maps, one slot per session, per-replica
    occupancy never exceeds slots_per_replica."""
    cfg, params = setup
    wl = sched.WorkloadConfig(n_fresh=6, n_followups=10, mean_gap_ns=900.0,
                              arrival="bursty", burst=3, zipf_s=1.4,
                              class_slo_ns=(25_000.0, 80_000.0, math.inf))
    arrivals = sched.generate_workload(wl, seed=2, vocab_size=cfg.vocab_size)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=sched.n_sessions_for(wl))
    s = sched.ClusterScheduler(cl, arrivals=arrivals)
    last_ns = 0.0
    while s.pending():
        s.tick()
        assert s.now_ns >= last_ns                     # clock monotone
        last_ns = s.now_ns
        active = s.active_jobs()
        assert set(active) == set(cl.active)
        uids = [j.uid for j in active.values()]
        assert len(uids) == len(set(uids))
        for eng in cl.replicas:
            assert len(eng.active) <= eng.slots
        assert s.tick_count < 3000
    assert all(j.state == "done" and j.done == j.target_new
               for j in s.jobs())
    # every suspended session's residence agrees with the engine that
    # actually holds its snapshot
    for uid, r in cl.residence.items():
        assert uid in cl.replicas[r].session_pos


def test_cluster_placement_spreads_fresh_load(setup):
    """A simultaneous burst of fresh requests lands one per replica (the
    free-slot axis of place_order), not all on replica 0."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    arrivals = [sched.Arrival(t_ns=0.0, uid=i, kind="fresh", priority=1,
                              slo_ns=math.inf, new_tokens=3,
                              prompt=rng.integers(0, cfg.vocab_size, 6)
                              .astype(np.int32)) for i in range(4)]
    cl = Cluster(cfg, params, n_replicas=4, slots=1, max_len=96,
                 n_sessions=8)
    s = sched.ClusterScheduler(cl, arrivals=arrivals)
    s.run()
    assert sorted(cl.residence.values()) == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# the migration plan on a REAL mesh (forced host devices)
# ---------------------------------------------------------------------------

MESH_MIGRATION_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import movement as MV
from repro.core.lisa.topology import MeshTopology

mesh = jax.make_mesh((4,), ("replica",))
SRC, DST = 0, 2
pool = jax.random.randint(jax.random.key(0), (4, 8, 8, 128), 0, 256,
                          jnp.int32).astype(jnp.uint8)
src_table = jnp.asarray([1, 4, 6], jnp.int32)
dst_table = jnp.asarray([0, 2, 5], jnp.int32)
plan = MV.plan(MV.Transfer(MV.Tier("slow", index=SRC, axis="replica"),
                           MV.Tier("slow", index=DST, axis="replica"),
                           MV.Layout.raw_pages(3, 8, 128, jnp.uint8)),
               topo=MeshTopology(4))
assert [l.kind for l in plan.legs] == ["page_gather", "hop_chain",
                                       "page_scatter"]
assert plan.legs[1].hops == 2

def body(shard):
    local = shard.reshape(8, 8, 128)
    env = MV.execute(plan, src_pool=local, src_table=src_table,
                     dst_pool=local, dst_table=dst_table)
    # every replica ran the scatter on its own shard, but only the
    # destination's result is the migration; others keep their pool
    out = jnp.where(jax.lax.axis_index("replica") == DST,
                    env["dst_pool"], local)
    return out.reshape(shard.shape)

out = np.asarray(jax.jit(jax.shard_map(
    body, mesh=mesh, in_specs=P("replica"), out_specs=P("replica"),
    check_vma=False))(pool))   # pallas_call has no replication rule yet
want = np.asarray(pool).copy()
want[DST][np.asarray(dst_table)] = want[SRC][np.asarray(src_table)]
assert (out == want).all(), "migrated pages did not land bit-exactly"
print("MESH_MIGRATION_OK")
"""


def test_migration_plan_executes_on_real_mesh():
    """The same slow->slow plan the cluster prices executes its hop-chain
    leg as a real ppermute chain on a 4-device mesh: the source replica's
    pages land bit-exactly in the destination replica's pool shard."""
    out = run_with_devices(MESH_MIGRATION_CODE, 4)
    assert "MESH_MIGRATION_OK" in out
