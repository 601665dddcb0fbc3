"""Scheduler invariants: slot conservation, starvation freedom, fused
batched waves, queue-not-crash admission, and policy/queue units."""
import math

import jax
import numpy as np
import pytest

from repro import sched
from repro.analysis import testlib as TL
from repro.configs import get_reduced
from repro.models import lm
from repro.sched.policy import (AdmitCand, SchedContext, VictimCand,
                                get_policy)
from repro.sched.queue import AdmissionQueue, QueueEntry
from repro.serve.engine import Engine


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("tinyllama-1.1b")
    params = lm.init_lm(cfg, jax.random.key(0))
    return cfg, params


def _engine(cfg, params, *, slots=2, n_sessions=8):
    return Engine(cfg, params, slots=slots, max_len=96,
                  n_sessions=n_sessions)


def _fresh(t, uid, *, priority=1, slo=math.inf, tokens=3, plen=5, seed=0):
    rng = np.random.default_rng(seed + uid)
    return sched.Arrival(t_ns=t, uid=uid, kind="fresh", priority=priority,
                        slo_ns=slo, new_tokens=tokens,
                        prompt=rng.integers(0, 1000, plen).astype(np.int32))


def _followup(t, uid, *, priority=1, slo=math.inf, tokens=2):
    return sched.Arrival(t_ns=t, uid=uid, kind="resume", priority=priority,
                        slo_ns=slo, new_tokens=tokens, prompt=None)


# ---------------------------------------------------------------------------
# queue + policy units
# ---------------------------------------------------------------------------

def test_queue_aging_is_unbounded_below_zero():
    """Effective class drops one step per age_every ticks without a floor —
    the structural starvation-freedom mechanism: any entry eventually
    outranks every fresh class-0 arrival."""
    q = AdmissionQueue(age_every=4)
    e = q.push(job_id=0, uid=0, kind="resume", priority=2, arrival_ns=0.0,
               slo_ns=math.inf, tick=0, new_tokens=1)
    assert q.effective_class(e, 0) == 2
    assert q.effective_class(e, 4) == 1
    assert q.effective_class(e, 8) == 0
    assert q.effective_class(e, 12) == -1          # now beats fresh class 0
    assert q.bounded_wait_ticks(2) == 12


def test_queue_rejects_malformed_entries():
    q = AdmissionQueue(age_every=4)
    with pytest.raises(ValueError, match="prompt"):
        q.push(job_id=0, uid=0, kind="fresh", priority=0, arrival_ns=0.0,
               slo_ns=math.inf, tick=0, new_tokens=1)
    with pytest.raises(ValueError, match="kind"):
        q.push(job_id=0, uid=0, kind="bulk", priority=0, arrival_ns=0.0,
               slo_ns=math.inf, tick=0, new_tokens=1)


def test_policy_registry_contract():
    assert set(sched.policies()) >= {"fifo", "lru", "cost_aware"}
    with pytest.raises(ValueError, match="unknown scheduling policy"):
        get_policy("round_robin")
    # an instance passes through; a name resolves to the registered object
    p = get_policy("cost_aware")
    assert get_policy(p) is p


def test_cost_aware_prefers_cheap_suspend_victim():
    """Same class, same recency: the cost_aware victim is the session whose
    modeled suspend is cheapest (the non-fast-resident, cold one)."""
    ctx = SchedContext(tick=0, now_ns=0.0, mechanism="lisa")
    cands = [
        VictimCand(slot=0, uid=7, priority=1, last_active_tick=0,
                   suspend_ns=3000.0, fast_resident=True),
        VictimCand(slot=1, uid=8, priority=1, last_active_tick=0,
                   suspend_ns=1900.0, fast_resident=False),
    ]
    order = get_policy("cost_aware").victim_order(cands, ctx)
    assert [v.uid for v in order] == [8, 7]
    # ... but a lower-priority (larger class) job is always victimized first
    cands.append(VictimCand(slot=2, uid=9, priority=2, last_active_tick=0,
                            suspend_ns=9000.0, fast_resident=True))
    order = get_policy("cost_aware").victim_order(cands, ctx)
    assert order[0].uid == 9


def test_cost_aware_admission_deprioritizes_hopeless_jobs():
    """Within a class: still-saveable deadlines first (EDF), jobs whose
    deadline already passed last — a hopeless job must not starve a
    saveable one (the overload domino-miss fix)."""
    def entry(seq, arrival, slo):
        return QueueEntry(seq=seq, job_id=seq, uid=seq, kind="resume",
                          priority=1, arrival_ns=arrival, slo_ns=slo,
                          enq_tick=0, new_tokens=1)
    ctx = SchedContext(tick=0, now_ns=50_000.0, mechanism="lisa")
    cands = [
        AdmitCand(entry(0, 0.0, 10_000.0), 1, 100.0, False),    # hopeless
        AdmitCand(entry(1, 0.0, 90_000.0), 1, 100.0, False),    # saveable
        AdmitCand(entry(2, 0.0, 60_000.0), 1, 100.0, False),    # saveable, EDF
    ]
    order = get_policy("cost_aware").admit_order(cands, ctx)
    assert [c.entry.seq for c in order] == [2, 1, 0]


def test_empty_metric_buckets_report_none_not_zero():
    """Regression: an idle class must not read as a perfect p99/attainment.
    Empty latency buckets are ``None`` (strict-JSON ``null``), and a class
    whose only jobs have infinite SLOs has no attainment to report."""
    import json

    from repro.sched.metrics import JobRecord, Metrics, percentile_ns

    assert percentile_ns([], 99) is None
    assert percentile_ns([5.0], 99) == 5.0

    m = Metrics()
    s = m.summary()                              # no jobs at all
    assert s["p50_latency_ns"] is None and s["p99_latency_ns"] is None
    assert s["slo_attainment"] is None
    json.dumps(s, allow_nan=False)               # strict JSON round-trips

    # one batch-class job (inf SLO): latency exists, attainment does not
    m.record_job(JobRecord(job_id=0, uid=0, kind="fresh", priority=2,
                           arrival_ns=0.0, done_ns=100.0, slo_ns=math.inf,
                           tokens=3))
    s = m.summary()
    assert s["per_class"]["2"]["p99_latency_ns"] == 100.0
    assert s["per_class"]["2"]["slo_attainment"] is None
    json.dumps(s, allow_nan=False)


def test_workload_generator_is_deterministic_and_well_formed():
    wl = sched.WorkloadConfig(n_fresh=5, n_followups=9, arrival="bursty",
                              burst=3)
    a1 = sched.generate_workload(wl, seed=3, vocab_size=128)
    a2 = sched.generate_workload(wl, seed=3, vocab_size=128)
    assert len(a1) == 14
    for x, y in zip(a1, a2):
        assert x.t_ns == y.t_ns and x.uid == y.uid and x.kind == y.kind
        if x.kind == "fresh":
            assert np.array_equal(x.prompt, y.prompt)
    # every follow-up targets a session that arrived fresh earlier
    seen = set()
    for a in a1:
        if a.kind == "fresh":
            seen.add(a.uid)
        else:
            assert a.uid in seen
    assert all(a.t_ns <= b.t_ns for a, b in zip(a1, a1[1:]))


# ---------------------------------------------------------------------------
# scheduler invariants (engine-backed)
# ---------------------------------------------------------------------------

def test_slot_conservation_across_ticks(setup):
    """No slot is ever double-booked and no session runs in two slots:
    after every tick the scheduler's job map is exactly the engine's active
    map, one job per slot, one slot per session."""
    cfg, params = setup
    wl = sched.WorkloadConfig(n_fresh=5, n_followups=8, mean_gap_ns=900.0,
                              arrival="bursty", burst=3, zipf_s=1.5,
                              class_slo_ns=(20_000.0, 60_000.0, math.inf))
    arrivals = sched.generate_workload(wl, seed=1, vocab_size=cfg.vocab_size)
    eng = _engine(cfg, params, slots=2, n_sessions=sched.n_sessions_for(wl))
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
    while s.pending():
        s.tick()
        active = s.active_jobs()
        assert set(active) == set(eng.active)          # same slots
        assert len(active) <= eng.slots
        uids = [j.uid for j in active.values()]
        assert len(uids) == len(set(uids))             # one slot per session
        for slot, job in active.items():
            assert job.slot == slot and job.state == "active"
            assert eng.active[slot].uid == job.uid
        assert s.tick_count < 3000
    # every job ran to its exact token budget
    assert all(j.state == "done" and j.done == j.target_new
               for j in s.jobs())


def test_no_starvation_under_sustained_high_priority_load(setup):
    """A class-2 request queued behind a sustained class-0 stream is
    promoted by aging and completes within a bounded number of ticks —
    with aging effectively disabled it is served dead last."""
    cfg, params = setup
    # the class-2 job arrives just after a sustained class-0 stream starts
    # (the slot is already taken and the queue always holds class-0 work)
    arrivals = [_fresh(5.0, 0, priority=2, tokens=2)] + [
        _fresh(3_000.0 * i, 1 + i, priority=0, slo=30_000.0, tokens=2)
        for i in range(14)]
    eng = _engine(cfg, params, slots=1, n_sessions=16)
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals,
                        cfg=sched.SchedConfig(age_every=4))
    bound = s.queue.bounded_wait_ticks(2) + 12      # aging + service slack
    done_tick = None
    while s.pending():
        s.tick()
        job0 = next((j for j in s.jobs() if j.uid == 0), None)
        if job0 is not None and job0.state == "done" and done_tick is None:
            done_tick = s.tick_count
    assert done_tick is not None and done_tick <= bound, (done_tick, bound)
    order = [r.uid for r in s.metrics.jobs]
    assert order.index(0) < len(order) - 4          # well before the tail

    # aging effectively off: the class-2 job drops to the very end
    eng = _engine(cfg, params, slots=1, n_sessions=16)
    s2 = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals,
                         cfg=sched.SchedConfig(age_every=10_000))
    s2.run()
    assert [r.uid for r in s2.metrics.jobs].index(0) == len(order) - 1


def test_batched_wave_equivalence(setup):
    """A burst offered as one arrival list and the same burst offered as
    singleton offer() calls schedule identically — and the burst's resumes
    drain as ONE fused wave, not per-session dispatches."""
    cfg, params = setup
    arrivals = [_fresh(float(i), i, tokens=2) for i in range(3)]
    arrivals += [_followup(9_000.0, i, tokens=2) for i in range(3)]  # burst

    def run(as_singletons):
        eng = _engine(cfg, params, slots=3, n_sessions=8)
        if as_singletons:
            s = sched.Scheduler(eng, policy="cost_aware")
            for a in arrivals:
                s.offer(a)
        else:
            s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
        s.run()
        return s, eng

    s_list, eng_list = run(False)
    s_one, eng_one = run(True)
    assert s_list.metrics.decisions == s_one.metrics.decisions
    assert ([(r.job_id, r.uid, r.done_ns) for r in s_list.metrics.jobs]
            == [(r.job_id, r.uid, r.done_ns) for r in s_one.metrics.jobs])
    # the follow-up burst resumed as one fused three-session wave
    assert 3 in s_list.metrics.wave_widths("resume_wave")
    assert eng_list.stats["resumes"] == 3
    TL.assert_compile_count(eng_list, "resume_many", 1)


def test_admission_overflow_queues_instead_of_crashing(setup):
    """Regression for the launcher's old ``n_sessions=max(requests, 8)``
    hand-rolled loop: offering far more simultaneous requests than slots
    must queue the overflow — the engine never sees EngineFull — and every
    job must still complete."""
    cfg, params = setup
    arrivals = [_fresh(0.0, i, tokens=2) for i in range(7)]   # 7 jobs, 2 slots
    eng = _engine(cfg, params, slots=2, n_sessions=8)
    s = sched.Scheduler(eng, policy="fifo", arrivals=arrivals)
    s.tick()
    assert len(eng.active) == 2 and len(s.queue) == 5         # queued, alive
    summary = s.run()
    assert summary["jobs_completed"] == 7
    assert all(j.state == "done" for j in s.jobs())


def test_launch_serve_routes_through_scheduler(setup):
    """The launcher admits from the scheduler queue: requests beyond the
    slot count queue (no EngineFull crash), and the output carries the
    scheduler's metrics."""
    from repro.launch import serve as launch_serve
    out = launch_serve.main([
        "--arch", "tinyllama-1.1b", "--reduced", "--slots", "2",
        "--requests", "6", "--followups", "4", "--max-new", "2",
        "--mean-gap-ns", "500"])
    assert out["jobs_completed"] == 10
    assert out["decode_compile_count"] == 1
    assert "p99_latency_ns" in out and "slot_utilization" in out
    assert out["decisions"].get("resume_wave", 0) >= 1


@pytest.mark.parametrize("replicas", [1, 2])
def test_launch_serve_frees_device_buffers_on_return(replicas):
    """Once ``serve.main`` returns, none of the device buffers it built
    (params, KV cache, session store, checksum sidecar) is still alive —
    without help from the cyclic garbage collector.  An engine whose jits
    held it in a reference cycle would keep them on the device until the
    collector happened to run, and a second serve window in the same
    process would then run out of device memory."""
    import gc
    from repro.launch import serve as launch_serve
    before = list(jax.live_arrays())     # held: their ids stay unique
    seen = {id(a) for a in before}
    gc.collect()
    gc.disable()
    try:
        launch_serve.main([
            "--arch", "tinyllama-1.1b", "--reduced", "--requests", "4",
            "--followups", "6", "--replicas", str(replicas), "--slots", "2"])
        left = [(a.shape, a.dtype) for a in jax.live_arrays()
                if id(a) not in seen]
    finally:
        gc.enable()
    assert left == []


def test_followup_ahead_of_fresh_does_not_livelock(setup):
    """Regression: a queued follow-up whose session does not exist yet must
    not block the idle-clock fast-forward — the fresh arrival behind it
    still gets admitted and both jobs complete (the old gate on an *empty*
    queue span to the max-tick guard here)."""
    cfg, params = setup
    arrivals = [_followup(0.0, 0, tokens=2), _fresh(1_000.0, 0, tokens=2)]
    eng = _engine(cfg, params, slots=2, n_sessions=4)
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
    summary = s.run(max_ticks=500)
    assert summary["jobs_completed"] == 2
    assert all(j.state == "done" for j in s.jobs())


def test_preempted_job_resumes_and_finishes_exactly(setup):
    """Preemption is loss-free: a class-1 job displaced by class-0 traffic
    is re-queued, resumed, and still emits exactly its token budget."""
    cfg, params = setup
    arrivals = [_fresh(0.0, 0, priority=1, tokens=6)]
    arrivals += [_fresh(2_000.0 + 100.0 * i, 1 + i, priority=0,
                        slo=30_000.0, tokens=2) for i in range(3)]
    eng = _engine(cfg, params, slots=1, n_sessions=8)
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
    s.run()
    job0 = next(j for j in s.jobs() if j.uid == 0)
    assert job0.state == "done" and job0.done == 6
    assert s.metrics.decision_counts().get("preempt_suspend", 0) >= 1
    assert all(j.done == j.target_new for j in s.jobs())


def test_scheduler_charges_movement_under_both_mechanisms(setup):
    """Every suspend/resume decision carries its Table-1 bill under lisa AND
    memcpy: the totals reproduce the engine-plan advantage at serving
    scale, and fast-tier hits are charged at the fast-subarray fraction."""
    cfg, params = setup
    arrivals = [_fresh(0.0, 0, tokens=2), _followup(4_000.0, 0, tokens=2),
                _followup(8_000.0, 0, tokens=2)]
    eng = _engine(cfg, params, slots=2, n_sessions=4)
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
    s.run()
    mv = s.metrics.movement_totals()
    assert mv["ns_lisa"] > 0 and mv["uj_memcpy"] > 0
    assert mv["advantage"] == pytest.approx(
        eng.plan_resume.cost.ns_memcpy / eng.plan_resume.cost.ns_lisa,
        rel=1e-6)
    moves = [d for d in s.metrics.decisions
             if d.kind in ("resume_wave", "complete_suspend")]
    assert moves and all(d.ns_memcpy > d.ns_lisa for d in moves)


def test_single_token_job_completes_on_exact_budget(setup):
    """A fresh job owing exactly one token is completed by its prefill
    token: the engine suspends it at submit (no overshoot decode), the
    scheduler records done == 1, and the session is resumable."""
    cfg, params = setup
    rng = np.random.default_rng(21)
    from repro.serve.engine import Request
    eng = _engine(cfg, params, slots=2, n_sessions=4)
    slot = eng.submit(Request(uid=0, max_new=1,
                              prompt=rng.integers(0, cfg.vocab_size, 5)
                              .astype(np.int32)))
    assert slot not in eng.active                # completed at prefill
    assert eng.stats["suspends"] == 1 and 0 in eng.session_pos

    arrivals = [_fresh(0.0, 0, tokens=1), _followup(2_000.0, 0, tokens=2)]
    eng = _engine(cfg, params, slots=2, n_sessions=4)
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
    summary = s.run(max_ticks=500)
    assert summary["jobs_completed"] == 2
    assert [j.done for j in s.jobs()] == [1, 2]  # exact budgets, no extras


def test_followups_truncate_to_the_context_envelope(setup):
    """A session cannot decode past max_len: the engine refuses an
    out-of-envelope resume (silent OOB cache writes were the old failure
    mode), and the scheduler truncates follow-ups to the remaining room —
    a context-exhausted follow-up completes instead of corrupting."""
    cfg, params = setup
    rng = np.random.default_rng(23)
    from repro.serve.engine import Engine, Request
    eng = Engine(cfg, params, slots=2, max_len=16, n_sessions=4)
    eng.submit(Request(uid=0, max_new=4,
                       prompt=rng.integers(0, cfg.vocab_size, 8)
                       .astype(np.int32)))
    while eng.active:
        eng.step()
    assert eng.session_pos[0] == 11
    with pytest.raises(ValueError, match="max_len"):
        eng.resume(0, extra_new=8)           # 11 + 7 decodes > 16
    eng.resume(0, extra_new=6)               # exactly fills the envelope
    while eng.active:
        eng.step()
    assert eng.session_pos[0] == 16

    # scheduler: follow-ups beyond the room truncate, at the wall complete
    arrivals = [_fresh(0.0, 0, tokens=4, plen=8),
                _followup(3_000.0, 0, tokens=9),    # room for only 5
                _followup(6_000.0, 0, tokens=3)]    # context exhausted: 0
    eng = Engine(cfg, params, slots=2, max_len=16, n_sessions=4)
    s = sched.Scheduler(eng, policy="cost_aware", arrivals=arrivals)
    summary = s.run(max_ticks=500)
    assert summary["jobs_completed"] == 3
    assert [j.done for j in s.jobs()] == [4, 5, 0]
    assert all(j.done == j.target_new for j in s.jobs())
    assert eng.session_pos[0] == 16          # pinned at the envelope


def test_submit_request_reads_request_metadata(setup):
    """`Scheduler.submit_request` admits a hand-built engine Request by its
    own scheduling metadata (arrival/priority/SLO), equivalently to the
    same Arrival."""
    cfg, params = setup
    rng = np.random.default_rng(22)
    from repro.serve.engine import Request
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    eng = _engine(cfg, params, slots=2, n_sessions=4)
    s = sched.Scheduler(eng, policy="cost_aware")
    s.submit_request(Request(uid=0, prompt=prompt, max_new=3,
                             arrival_ns=500.0, priority=2, slo_ns=40_000.0))
    s.run(max_ticks=200)
    rec = s.metrics.jobs[0]
    assert (rec.uid, rec.priority, rec.slo_ns) == (0, 2, 40_000.0)
    assert rec.arrival_ns == 500.0 and rec.tokens == 3


def test_engine_resume_many_per_uid_extra_new(setup):
    """One fused wave can hand each session a different remaining-token
    budget (host bookkeeping only — still ONE dispatch)."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    eng = _engine(cfg, params, slots=3, n_sessions=8)
    from repro.serve.engine import Request
    for uid in range(3):
        eng.submit(Request(uid=uid, max_new=2,
                           prompt=rng.integers(0, cfg.vocab_size, 5)
                           .astype(np.int32)))
    while eng.active:
        eng.step()
    slots = eng.resume_many([0, 1, 2], extra_new=[2, 3, 4])
    budgets = {eng.active[s].uid: eng.active[s].max_new for s in slots}
    assert budgets == {0: 2, 1: 3, 2: 4}
    with pytest.raises(ValueError, match="extra_new"):
        eng.resume_many([0], extra_new=[1, 2])
    while eng.active:
        eng.step()
    TL.assert_compile_count(eng, "resume_many", 1)
