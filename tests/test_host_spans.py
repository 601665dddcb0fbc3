"""Host spans on the profiler's clock (repro.obs.host): the scheduler's tick
and the engine's entry points, dispatches and device→host reads, recorded
under ``jax.profiler`` on the CPU; the engine's jits under their own names;
the movement legs' named scopes in the lowered programs."""
import collections
import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sched
from repro.configs import get_reduced
from repro.models import lm
from repro.obs.host import span
from repro.serve.engine import Engine

READS = {"tokens": "host_transfers", "tags": "tag_reads",
         "prefill_token": "prefill_reads"}
# the tick's phases in the order they run (step_begin and step_end only
# when a slot decodes)
PHASES = ("repro.admit", "repro.step_begin", "repro.plan", "repro.step_end",
          "repro.wave")
ENTRY = ("repro.step_begin", "repro.submit", "repro.suspend",
         "repro.suspend_many", "repro.resume_many")


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("tinyllama-1.1b")
    return cfg, lm.init_lm(cfg, jax.random.key(0))


def _arrivals():
    """Four fresh requests in two classes and two follow-ups on two slots:
    prefills, decode, completion suspends and a resume wave."""
    out = []
    for uid in range(4):
        rng = np.random.default_rng(uid)
        out.append(sched.Arrival(
            t_ns=uid * 1500.0, uid=uid, kind="fresh", priority=1 + uid % 2,
            slo_ns=math.inf, new_tokens=3 + uid,
            prompt=rng.integers(0, 1000, 5 + uid).astype(np.int32)))
    for uid in range(2):
        out.append(sched.Arrival(t_ns=20_000.0 + uid, uid=uid, kind="resume",
                                 priority=0, slo_ns=math.inf, new_tokens=2,
                                 prompt=None))
    return out


def _serve(cfg, params, trace_dir=None):
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    s = sched.Scheduler(eng, arrivals=_arrivals())
    if trace_dir is None:
        s.run()
    else:
        with jax.profiler.trace(trace_dir):
            s.run()
    return eng, s


def _spans(trace_dir):
    """Every ``repro.*`` event of the trace: (name, start, end, attrs),
    in start order (an enclosing span before the spans it holds)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(e, outer):
    return outer[1] <= e[1] and e[2] <= outer[2]


def _uids(attrs):
    v = attrs.get("uids", "")
    return {int(u) for u in str(v).split(";") if u != ""}


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    eng, s = _serve(*setup, trace_dir=d)
    return eng, s, _spans(d)


def test_tick_phases_nest_in_order(traced):
    eng, s, ev = traced
    ticks = [e for e in ev if e[0] == "repro.tick"]
    assert [e[3]["tick"] for e in ticks] == list(range(1, s.tick_count + 1))
    for t in ticks:
        inner = [e for e in ev if e is not t and _inside(e, t)]
        order = [e[0] for e in inner if e[0] in PHASES]
        assert order == [p for p in PHASES if p in order], order
        assert order[0] == "repro.admit" and order[-1] == "repro.wave"
    # every phase and entry point lies inside a tick; the token loop inside
    # the step's end; a placement inside the wave
    for e in ev:
        if e[0] != "repro.tick":
            assert any(_inside(e, t) for t in ticks), e
    enclosing = lambda e, names: any(
        _inside(e, o) for o in ev if o[0] in names and o is not e)
    for e in ev:
        if e[0] == "repro.bookkeep":
            assert enclosing(e, ("repro.step_end",))
        if e[0] in ("repro.submit", "repro.resume_many"):
            assert enclosing(e, ("repro.wave",))
        if e[0] in ("repro.suspend", "repro.suspend_many"):
            assert enclosing(e, ("repro.step_end", "repro.wave"))
        if e[0] == "repro.dispatch":
            assert enclosing(e, ENTRY), e
    names = collections.Counter(e[0] for e in ev)
    assert names["repro.submit"] == eng.stats["prefills"] == 4
    assert names["repro.resume_many"] >= 1 and names["repro.suspend"] >= 1
    assert names["repro.step_begin"] == eng.stats["decode_dispatches"]


def test_one_read_span_per_counted_read(traced):
    eng, _, ev = traced
    reads = collections.Counter(e[3]["what"] for e in ev
                                if e[0] == "repro.read")
    assert reads == {what: eng.stats[k] for what, k in READS.items()}
    assert all(reads.values())
    # a decode read waits inside its step's end, a tag read inside the plan
    for e in ev:
        if e[0] == "repro.read":
            home = {"tokens": "repro.step_end", "tags": "repro.plan",
                    "prefill_token": "repro.submit"}[e[3]["what"]]
            assert any(_inside(e, o) for o in ev if o[0] == home), e
    programs = collections.Counter(e[3]["program"] for e in ev
                                   if e[0] == "repro.dispatch")
    assert programs["decode_step_batched"] == eng.stats["decode_dispatches"]
    assert programs["prefill_insert"] == eng.stats["prefills"]


def test_a_requests_spans_share_its_uid(traced):
    _, s, ev = traced
    admitted = set().union(*(_uids(e[3]) for e in ev
                             if e[0] == "repro.admit"))
    assert admitted == set(range(4))
    placed = {e[3]["uid"] for e in ev if e[0] == "repro.submit"}
    resumed = set().union(*(_uids(e[3]) for e in ev
                            if e[0] == "repro.resume_many"))
    # a wave's suspends are preemptions: the class-0 follow-ups preempt a
    # class-2 request, which comes back through a later resume wave
    waves = [e for e in ev if e[0] == "repro.wave"]
    preempted = [e for e in ev
                 if e[0] in ("repro.suspend", "repro.suspend_many")
                 and any(_inside(e, w) for w in waves)]
    assert len(preempted) == s.metrics.decision_counts()["preempt_suspend"]
    bumped = set().union(*(_uids(e[3]) for e in preempted))
    assert placed == admitted and bumped
    assert resumed == {0, 1} | bumped
    for e in ev:                # a wave names as many uids as it is wide
        if e[0] in ("repro.suspend", "repro.suspend_many",
                    "repro.resume_many"):
            assert len(_uids(e[3])) == e[3]["width"]


def test_profiler_off_serves_the_same(setup, traced):
    eng, s, _ = traced
    eng0, s0 = _serve(*setup)
    assert eng0.stats == eng.stats
    assert (eng0.session_tok, eng0.session_pos) == (eng.session_tok,
                                                    eng.session_pos)
    np.testing.assert_array_equal(np.asarray(eng0.sessions.slow),
                                  np.asarray(eng.sessions.slow))
    assert s0.metrics.summary() == s.metrics.summary()


def test_span_outside_the_profiler_is_one_shared_no_op():
    a, b = span("repro.x", n=1), span("repro.y")
    assert a is b and not a
    with a as sp:
        sp.set(uids=[1, 2])


def _lowering_args(eng):
    i32, two = jnp.int32, jnp.asarray([0, 1], jnp.int32)
    moves = (eng.cache, eng.sessions, eng.session_sums)
    return {
        "_decode": (eng.params, eng.cache, jnp.zeros(2, i32),
                    jnp.zeros(2, i32), jnp.ones(2, bool)),
        "_prefill": (eng.params, eng.cache, jnp.zeros((1, 16), i32),
                     jnp.zeros((1, 16), i32), i32(3), i32(0)),
        "_suspend": moves + (i32(0), i32(1)),
        "_suspend_many": moves + (two, two),
        "_resume": moves + (eng.verify_failed, i32(0), i32(1)),
        "_resume_many": moves + (eng.verify_failed, two, two),
        "_clone": (eng.sessions, eng.session_sums, i32(0), i32(1)),
    }


NAMES = {"_decode": "jit_decode_step_batched",
         "_prefill": "jit_prefill_insert",
         "_suspend": "jit__suspend_body",
         "_suspend_many": "jit__suspend_many_body",
         "_resume": "jit__resume_body",
         "_resume_many": "jit__resume_many_body",
         "_clone": "jit__clone_body"}
# the movement legs each program runs, by their named scopes
SCOPES = {"_suspend": {"pack_pages", "checksum", "tier_write",
                       "page_scatter"},
          "_resume": {"tier_read", "page_gather", "page_scatter",
                      "unpack_pages", "checksum"},
          "_clone": {"page_gather", "page_scatter"}}


def test_engine_jits_lower_under_their_names_with_movement_scopes(setup):
    eng = Engine(*setup, slots=2, max_len=32, n_sessions=4)
    # the clone jit wraps a module-level function, so its cache is shared
    # with every engine this process built before: lowering adds nothing
    compiled = eng.compile_counts()
    for attr, args in _lowering_args(eng).items():
        low = getattr(eng, attr).lower(*args)
        assert re.match(r"module @(\S+)", low.as_text()).group(1) == \
            NAMES[attr]
        scopes = set(re.findall(r"movement\.([a-z_]+)",
                                low.as_text(debug_info=True)))
        want = SCOPES.get(attr.replace("_many", ""), set())
        assert scopes == want, (attr, scopes)
    assert eng.compile_counts() == compiled
