"""Zero-copy session forking (the RowClone analogue): the refcounted CoW
fork table, the engine's zero-dispatch fork fast path and deferred-copy
write-break, fork-aware eviction (demotion vs destruction), cluster
materialization, fault interplay (detect once, repair every alias), and
the RowClone FPM/PSM pricing the movement layer quotes for all of it.

The property test (hypothesis, with fixed-case fallback streams) drives
random fork/write/evict/release sequences and asserts refcount
conservation after every step: physical rows == unique alias targets,
zero leaks and zero double-frees at drain.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _multidev import run_with_devices

from repro.analysis import testlib as TL
from repro.configs import get_reduced
from repro.core.dram.spec import DDR3_1600
from repro.faults import repair_row, restore_session, snapshot_sessions
from repro.fork import ForkPageTable
from repro.models import lm
from repro.serve.cluster import Cluster
from repro.serve.engine import Engine, Request, UnknownSession


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("tinyllama-1.1b")
    params = lm.init_lm(cfg, jax.random.key(0))
    return cfg, params


def _drain(eng, toks=None):
    while eng.active:
        for _, req in eng.step():
            if toks is not None:
                toks[req.uid] = [int(t) for t in req.generated]


def _suspended_template(eng, uid, prompt):
    """Prefill ``prompt`` once and leave it suspended (max_new=1 completes
    at the prefill token)."""
    eng.submit(Request(uid=uid, prompt=prompt, max_new=1))
    assert uid in eng.session_pos and not eng.active


# ---------------------------------------------------------------------------
# ForkPageTable: the ledger in isolation
# ---------------------------------------------------------------------------

def test_table_bind_fork_release_lifecycle():
    ft = ForkPageTable()
    ft.bind(10, 3)
    assert ft.resolve(10) == 3 and ft.refcount(10) == 1 and not ft.shared(10)
    assert ft.fork_child(10, 11) == 3
    assert ft.fork_child(10, 12) == 3
    assert ft.refcount(11) == 3 and ft.shared(12)
    assert ft.aliases(3) == (10, 11, 12)
    assert ft.shared_rows() == {3: 3}
    assert ft.release(11) is None            # still shared: row survives
    assert ft.release(10) is None
    assert ft.release(12) == 3               # last alias frees the row
    assert len(ft) == 0 and not ft.refs


def test_table_bind_rejects_double_claims():
    ft = ForkPageTable()
    ft.bind(1, 0)
    with pytest.raises(ValueError, match="already mapped"):
        ft.bind(1, 2)
    with pytest.raises(ValueError, match="already owned"):
        ft.bind(2, 0)
    with pytest.raises(ValueError, match="already mapped"):
        ft.fork_child(1, 1)


def test_table_write_break_exclusive_is_a_noop():
    ft = ForkPageTable()
    ft.bind(1, 4)
    assert ft.write_break(1) == 4            # no alloc needed, no copy
    assert ft.refcount(1) == 1


def test_table_write_break_detaches_shared():
    ft = ForkPageTable()
    ft.bind(1, 4)
    ft.fork_child(1, 2)
    with pytest.raises(ValueError, match="alloc callback"):
        ft.write_break(2)
    assert ft.write_break(2, alloc=lambda uid: 7) == 7
    assert ft.resolve(1) == 4 and ft.resolve(2) == 7
    assert ft.refcount(1) == 1 and ft.refcount(2) == 1
    ft.check_conserved()


def test_table_write_break_follows_an_alloc_side_demotion():
    """The alloc callback may demote the very shared row the uid is
    detaching from (engine: uid's home index IS the shared row); the
    bookkeeping must follow the repoint, not the stale row."""
    ft = ForkPageTable()
    ft.bind(1, 0)
    ft.fork_child(1, 2)

    def alloc(uid):
        ft.repoint(0, 5)                     # demotion: bytes moved 0 -> 5
        return 0

    assert ft.write_break(2, alloc=alloc) == 0
    assert ft.resolve(1) == 5 and ft.refcount(1) == 1
    assert ft.resolve(2) == 0 and ft.refcount(2) == 1
    ft.check_conserved()


def test_table_repoint_moves_the_family_as_one_unit():
    ft = ForkPageTable()
    ft.bind(1, 2)
    ft.fork_child(1, 5)
    ft.fork_child(1, 9)
    assert ft.repoint(2, 6) == (1, 5, 9)
    assert ft.refs == {6: 3}
    with pytest.raises(ValueError, match="already owned"):
        ft.repoint(6, 6)
    with pytest.raises(KeyError):
        ft.repoint(2, 7)                     # old row no longer mapped
    ft.check_conserved()


# ---------------------------------------------------------------------------
# refcount conservation under random op streams (property test)
# ---------------------------------------------------------------------------

N_ROWS = 8


def _run_stream(ops):
    """Interpret a (op, arg) stream against a ForkPageTable plus a model
    free-list; assert the conservation invariants after EVERY step and
    zero leaks / zero double-frees at drain."""
    ft = ForkPageTable()
    free = set(range(N_ROWS))
    uids, next_uid = [], 0
    for op, arg in ops:
        if op == 0 and free:                           # admit a fresh uid
            row = min(free)
            free.remove(row)
            ft.bind(next_uid, row)
            uids.append(next_uid)
            next_uid += 1
        elif op == 1 and uids:                         # fork a child
            ft.fork_child(uids[arg % len(uids)], next_uid)
            uids.append(next_uid)
            next_uid += 1
        elif op == 2 and uids and free:                # CoW write-break
            uid = uids[arg % len(uids)]

            def alloc(u):
                row = min(free)
                free.remove(row)
                return row

            ft.write_break(uid, alloc=alloc)
        elif op == 3 and uids:                         # release/evict
            freed = ft.release(uids.pop(arg % len(uids)))
            if freed is not None:
                assert freed not in free, "double-free"
                free.add(freed)
        ft.check_conserved()
        # physical rows in use == unique alias targets, disjoint from free
        assert len(set(ft.phys_of.values())) == len(ft.refs)
        assert set(ft.refs).isdisjoint(free)
        assert len(ft.refs) + len(free) == N_ROWS      # no leaked rows
    for uid in list(uids):                             # drain
        freed = ft.release(uid)
        if freed is not None:
            assert freed not in free, "double-free at drain"
            free.add(freed)
    assert len(ft) == 0 and not ft.refs
    assert free == set(range(N_ROWS)), "leaked rows at drain"


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 63)),
                max_size=60))
def test_refcount_conservation_random_streams(ops):
    _run_stream(ops)


@pytest.mark.parametrize("ops", [
    [],
    [(0, 0)] * N_ROWS + [(3, 0)] * N_ROWS,             # fill then drain
    [(0, 0), (1, 0), (1, 0), (2, 1), (3, 0), (3, 0), (3, 0)],
    [(0, 0), (1, 0)] * 6 + [(2, i) for i in range(7)] + [(3, 0)] * 5,
    [(0, 0), (0, 0), (1, 1), (3, 1), (1, 0), (2, 0), (3, 2), (3, 0)],
], ids=["empty", "fill_drain", "fork_break", "deep_family", "interleaved"])
def test_refcount_conservation_fixed_streams(ops):
    """Fixed-case fallback for the hypothesis stream test (runs — and
    guards the same invariants — even where hypothesis is absent)."""
    _run_stream(ops)


# ---------------------------------------------------------------------------
# engine: zero-dispatch fork, CoW divergence, fork-aware eviction
# ---------------------------------------------------------------------------

def test_fork_fast_path_is_zero_dispatch(setup):
    """fork_many is pure host bookkeeping: zero fused dispatches and zero
    device->host transfers over the window (the RowClone-FPM analogue,
    pinned via the dispatch-delta asserter)."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=12)
    _suspended_template(eng, 0, rng.integers(0, cfg.vocab_size, 8)
                        .astype(np.int32))
    before = TL.snapshot_stats(eng)
    eng.fork_many(0, [5, 6, 7], seed_tokens=[11, 22, 33])
    TL.assert_dispatch_delta(before, eng.stats, decode=0, host=0)
    assert eng.stats["forks"] == 3
    assert eng.stats["bytes_not_copied"] == 3 * eng.snapshot_bytes
    phys = eng.forks.resolve(0)
    assert all(eng.forks.resolve(c) == phys for c in (5, 6, 7))
    assert eng.forks.refcount(0) == 4
    assert all(eng.session_pos[c] == eng.session_pos[0] for c in (5, 6, 7))
    assert eng.shared_uids() == frozenset({0, 5, 6, 7})


def test_fork_validation(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=12)
    with pytest.raises(UnknownSession):
        eng.fork(0, 1)                       # parent never suspended
    _suspended_template(eng, 0, rng.integers(0, cfg.vocab_size, 6)
                        .astype(np.int32))
    with pytest.raises(ValueError, match="already in use"):
        eng.fork(0, 0)
    eng.fork(0, 5)
    with pytest.raises(ValueError, match="already in use"):
        eng.fork(0, 5)
    slot = eng.resume(0, extra_new=3)
    with pytest.raises(ValueError, match="active"):
        eng.fork(0, 6)                       # parent must be quiescent
    eng.suspend(slot)


def test_forked_children_decode_bit_exactly(setup):
    """Fork-served children produce byte-identical tokens to independently
    prefilled sessions with the same seeds: aliasing (and the CoW detach on
    their first suspend) is invisible to the data path."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    seeds = [3, 1000, 3]                     # two children share a seed

    eng = Engine(cfg, params, slots=3, max_len=96, n_sessions=12)
    _suspended_template(eng, 0, prompt)
    eng.fork_many(0, [4, 5, 6], seed_tokens=seeds)
    toks_forked = {}
    eng.resume_many([4, 5, 6], extra_new=5)
    _drain(eng, toks_forked)

    ref = Engine(cfg, params, slots=3, max_len=96, n_sessions=12)
    ref.adopt_jits(eng)
    toks_ref = {}
    for uid, seed in zip((4, 5, 6), seeds):
        ref.submit(Request(uid=uid, prompt=prompt, max_new=1))
        ref.reseed(uid, seed)
    ref.resume_many([4, 5, 6], extra_new=5)
    _drain(ref, toks_ref)

    assert toks_forked == toks_ref
    assert toks_forked[4] == toks_forked[6]          # same seed, same path
    assert toks_forked[4] != toks_forked[5]          # divergence diverges
    # CoW happened: each child detached onto its own row at suspend; the
    # parent keeps the original snapshot, now exclusive again
    rows = {eng.forks.resolve(u) for u in (0, 4, 5, 6)}
    assert len(rows) == 4
    assert not eng.shared_uids()


def test_parent_snapshot_survives_child_divergence(setup):
    """After children diverge and write-break away, the parent resumes from
    its original snapshot bit-exactly (the deferred copy never touched the
    shared row)."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)

    ref = Engine(cfg, params, slots=2, max_len=96, n_sessions=12)
    _suspended_template(ref, 0, prompt)
    toks = {}
    ref.resume_many([0], extra_new=4)
    _drain(ref, toks)
    want = toks[0]

    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=12)
    eng.adopt_jits(ref)
    _suspended_template(eng, 0, prompt)
    eng.fork_many(0, [4, 5], seed_tokens=[9, 10])
    eng.resume_many([4, 5], extra_new=4)
    _drain(eng)
    got = {}
    eng.resume_many([0], extra_new=4)
    _drain(eng, got)
    assert got[0] == want


def test_collision_demotes_shared_rows_and_evicts_exclusive(setup):
    """Fork-aware eviction accounting: a store-index collision DESTROYS an
    exclusive snapshot (``evictions``) but MIGRATES a shared one
    (``demotions``) — every alias stays resumable, and the stats split the
    two outcomes."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    n = 12
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=n)
    _suspended_template(eng, 0, prompt)              # row 0, exclusive
    _suspended_template(eng, 1, prompt)              # row 1, then shared
    eng.fork_many(1, [7, 8])
    toks_before = {}
    eng.resume_many([7], extra_new=3)
    _drain(eng, toks_before)
    eng.fork(1, 9)                                   # re-share after 7 left

    # uid n collides with row 0 (exclusive): destroyed
    _suspended_template(eng, n, prompt)
    assert eng.stats["evictions"] == 1 and eng.stats["demotions"] == 0
    with pytest.raises(UnknownSession):
        eng.resume(0, extra_new=2)
    # uid n+1 collides with row 1 (shared by 1, 8, 9): demoted, not
    # destroyed — the family's bytes moved to a free row as one unit
    _suspended_template(eng, n + 1, prompt)
    assert eng.stats["demotions"] == 1 and eng.stats["evictions"] == 1
    new_row = eng.forks.resolve(1)
    assert new_row != 1 and eng.forks.refcount(1) == 3
    assert eng.verify_failure_count() == 0
    toks_after = {}
    eng.resume_many([8], extra_new=3)                # same seed as 7 had
    _drain(eng, toks_after)
    assert toks_after[8] == toks_before[7]           # bytes moved intact
    assert eng.verify_failure_count() == 0           # sidecar moved too


def test_verify_store_counts_shared_corruption_once(setup):
    """One corrupted physical row aliased by N sessions is ONE detection
    (the scrub walks physical rows, not logical sessions)."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=12)
    _suspended_template(eng, 0, rng.integers(0, cfg.vocab_size, 6)
                        .astype(np.int32))
    eng.fork_many(0, [4, 5, 6])
    assert int(eng.verify_store()) == 0
    eng.corrupt_stored(eng.forks.resolve(0), page=0, byte=3, xor=0x40)
    assert int(eng.verify_store()) == 1              # once, not 4x


# ---------------------------------------------------------------------------
# pricing: the rowclone mechanism and the fork plan
# ---------------------------------------------------------------------------

def test_rowclone_mechanism_prices_fpm_at_one_hop():
    """hops=1 (in-subarray alias) prices as RowClone FPM — the Table-1
    RC-IntraSA row: 83.75 ns, 2 activate-precharge pairs of energy — and
    materialization across h subarrays grows by the LISA hop rate."""
    s = DDR3_1600
    assert s.copy_latency("rowclone", 1) == pytest.approx(
        s.copy_latency("rc_intrasa"))
    assert s.copy_energy("rowclone", 1) == pytest.approx(
        2 * s.energy.e_act_pre)
    assert s.copy_latency("rowclone", 1) == pytest.approx(83.75)
    hop = s.copy_latency("rowclone", 5) - s.copy_latency("rowclone", 4)
    assert hop == pytest.approx(s.lisa.t_rbm_hop)
    # the serving gate: aliasing beats the channel copy by >= 10x
    assert s.copy_latency("memcpy") / s.copy_latency("rowclone", 1) >= 10


def test_engine_fork_plan_quotes_the_rowclone_gap(setup):
    cfg, params = setup
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    c = eng.plan_fork.cost
    assert c.bytes == eng.snapshot_bytes             # bytes NOT copied
    assert c.ns_memcpy / c.ns_lisa >= 10
    assert [leg.kind for leg in eng.plan_fork.legs] == ["page_alias"]


# ---------------------------------------------------------------------------
# cluster: same-replica alias vs cross-replica materialization
# ---------------------------------------------------------------------------

def test_cluster_fork_alias_and_materialization(setup):
    cfg, params = setup
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    cl.submit(Request(uid=0, prompt=prompt, max_new=1), replica=0)
    assert not cl.active

    cl.fork(0, 4)                                    # same replica: alias
    assert cl.residence[4] == 0
    assert cl.replicas[0].forks.refcount(0) == 2
    assert cl.cluster_stats["fork_materializations"] == 0

    cl.fork(0, 5, replica=1, seed_token=17)          # cross: materialize
    assert cl.residence[5] == 1
    assert cl.cluster_stats["fork_materializations"] == 1
    assert cl.cluster_stats["migrated_bytes"] > 0
    # the parent's refcount is untouched (the copy was an admission, not
    # an alias), and the child is an exclusive row on the destination
    assert cl.replicas[0].forks.refcount(0) == 2
    assert cl.replicas[1].forks.refcount(5) == 1

    # both children decode bit-exactly vs the alias child with same seed
    cl.replicas[0].reseed(4, 17)
    toks = {}
    for uid in (4, 5):
        slot = cl.resume(uid, extra_new=4)
        r = cl.active[slot]
        while cl.active:
            cl.step()
        toks[uid] = list(r.generated)
    assert toks[4] == toks[5]
    assert cl.verify_failure_count() == 0            # sidecar traveled


def test_fail_replica_clears_the_fork_table(setup):
    cfg, params = setup
    rng = np.random.default_rng(7)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    cl.submit(Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, 6)
                      .astype(np.int32), max_new=1), replica=0)
    cl.fork(0, 4)
    assert cl.shared_uids() == frozenset({0, 4})
    cl.fail_replica(0)
    assert len(cl.replicas[0].forks) == 0
    assert cl.shared_uids() == frozenset()


# ---------------------------------------------------------------------------
# faults: one snapshot per physical row; one repair heals the family
# ---------------------------------------------------------------------------

def test_snapshot_stores_shared_pages_once_and_repairs_all_aliases(setup):
    """A fork family snapshots its shared row ONCE (carrier + meta-only
    aliases); after the shared row corrupts AND the replica dies, restoring
    the carrier once re-attaches every alias — one staged copy, one repair,
    the whole family verify-clean and bit-exact."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    cl.submit(Request(uid=0, prompt=prompt, max_new=1), replica=0)
    cl.fork(0, 4, seed_token=21)
    cl.fork(0, 5, seed_token=22)

    # clean-run reference for child 4's continuation
    ref = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    ref.adopt_jits(cl.replicas[0])
    _suspended_template(ref, 4, prompt)
    ref.reseed(4, 21)
    want = {}
    ref.resume_many([4], extra_new=4)
    _drain(ref, want)

    snaps, cost = snapshot_sessions(cl)
    # ONE physical row staged for the 3-session family, not 3 (the cost
    # covers the carrier's pages + sidecar, under 2 rows' worth of bytes)
    assert cl.replicas[0].snapshot_bytes <= cost.bytes \
        < 2 * cl.replicas[0].snapshot_bytes
    assert snaps[0].pages is not None                # uid 0 carries
    for c in (4, 5):
        assert snaps[c].pages is None and snaps[c].alias_of == 0

    eng = cl.replicas[0]
    eng.corrupt_stored(eng.forks.resolve(0), page=0, byte=2, xor=0x08)
    assert int(eng.verify_store()) == 1              # detected ONCE
    cl.fail_replica(0)

    # owners first, aliases re-attach for free
    assert restore_session(cl, snaps[0], 1).bytes > 0
    assert restore_session(cl, snaps[4], 1).bytes == 0
    assert restore_session(cl, snaps[5], 1).bytes == 0
    eng1 = cl.replicas[1]
    assert eng1.forks.refcount(0) == 3
    assert int(eng1.verify_store()) == 0             # one repair healed all
    got = {}
    eng1.resume_many([4], extra_new=4)
    _drain(eng1, got)
    assert got[4] == want[4]
    assert cl.verify_failure_count() == 0


def test_repair_row_heals_a_live_shared_row_in_place(setup):
    """Pre-resume repair of a corrupt SHARED row on a LIVE replica: the
    carrier's snapshot overwrites the physical row in place — fork table,
    refcounts and per-alias seed tokens untouched — so one staged copy
    heals every alias.  (restore_session here would re-admit the carrier
    and demote the still-corrupt row to the siblings.)"""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    cl.submit(Request(uid=0, prompt=prompt, max_new=1), replica=0)
    cl.fork(0, 4, seed_token=31)
    cl.fork(0, 5, seed_token=32)
    snaps, _ = snapshot_sessions(cl)

    eng = cl.replicas[0]
    eng.corrupt_stored(eng.forks.resolve(0), page=1, byte=3, xor=0x11)
    assert int(eng.verify_store()) == 1
    cost = repair_row(cl, snaps[0], 0)
    assert cost is not None and cost.bytes > 0
    assert int(eng.verify_store()) == 0              # whole row healed
    assert eng.forks.refcount(0) == 3                # family untouched
    assert eng.session_tok[4] == 31 and eng.session_tok[5] == 32

    # an alias (meta-only) snapshot or a departed uid cannot repair
    assert repair_row(cl, snaps[4], 0) is None
    eng.resume_many([5], extra_new=4)
    got = {}
    _drain(eng, got)
    assert len(got[5]) == 4                          # serves clean post-heal
    assert cl.verify_failure_count() == 0


def test_alias_restore_without_carrier_reports_lost(setup):
    cfg, params = setup
    rng = np.random.default_rng(9)
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=96,
                 n_sessions=8)
    cl.submit(Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, 6)
                      .astype(np.int32), max_new=1), replica=0)
    cl.fork(0, 4)
    snaps, _ = snapshot_sessions(cl)
    cl.fail_replica(0)
    assert restore_session(cl, snaps[4], 1) is None  # carrier not resident
    assert 4 not in cl.replicas[1].session_pos


# ---------------------------------------------------------------------------
# the cross-replica fork plan on a real 4-device mesh
# ---------------------------------------------------------------------------

MESH_FORK_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import movement as MV
from repro.core.lisa.topology import MeshTopology

mesh = jax.make_mesh((4,), ("replica",))
SRC, DST = 1, 3
pool = jax.random.randint(jax.random.key(1), (4, 8, 8, 128), 0, 256,
                          jnp.int32).astype(jnp.uint8)
src_table = jnp.asarray([2, 3], jnp.int32)
dst_table = jnp.asarray([5, 6], jnp.int32)
plan = MV.plan(MV.Transfer(MV.Tier("slow", index=SRC, axis="replica"),
                           MV.Tier("slow", index=DST, axis="replica"),
                           MV.Layout.raw_pages(2, 8, 128, jnp.uint8),
                           kind="fork"),
               topo=MeshTopology(4))
# a cross-replica fork MATERIALIZES: the same gather -> hop chain ->
# scatter legs as a migration, not a page_alias
assert [l.kind for l in plan.legs] == ["page_gather", "hop_chain",
                                       "page_scatter"]

def body(shard):
    local = shard.reshape(8, 8, 128)
    env = MV.execute(plan, src_pool=local, src_table=src_table,
                     dst_pool=local, dst_table=dst_table)
    out = jnp.where(jax.lax.axis_index("replica") == DST,
                    env["dst_pool"], local)
    return out.reshape(shard.shape)

out = np.asarray(jax.jit(jax.shard_map(
    body, mesh=mesh, in_specs=P("replica"), out_specs=P("replica"),
    check_vma=False))(pool))
want = np.asarray(pool).copy()
want[DST][np.asarray(dst_table)] = want[SRC][np.asarray(src_table)]
assert (out == want).all(), "materialized fork pages did not land bit-exactly"
print("MESH_FORK_OK")
"""


def test_fork_materialization_plan_executes_on_real_mesh():
    """The cross-replica ``fork``-kind plan executes its hop chain as a
    real ppermute on a 4-device mesh — a materialized fork is a true copy
    over the fabric, landing bit-exactly in the destination pool."""
    out = run_with_devices(MESH_FORK_CODE, 4)
    assert "MESH_FORK_OK" in out
