"""The timed window: ``repro.sched.Scheduler.tick`` over
``repro.serve.engine.Engine``, driven on the host's wall clock.

Arrivals are offered to the scheduler when they fall due (``offer`` clamps
their virtual time, so each is admitted at the next tick).  Every request
is in one priority class with no deadline: the scheduler's virtual clock
prices movement in modeled DRAM nanoseconds, which mean nothing on the wall
clock.  After each tick every token is stamped on the host by diffing
``Request.generated`` of the requests that were active before and after it.
A session is dropped from the store after its last turn, so store rows are
reused.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import generator
from repro.sched import Arrival, SchedConfig, Scheduler
from repro.serve.engine import Request

INF = float("inf")
LONG_TICK_S = 1.0


@dataclasses.dataclass
class Turn:
    """One request: a session's first turn (a prefill) or a follow-up (a
    resume of its suspended snapshot with more decode budget)."""
    session: int
    index: int
    target: int
    due: float
    placed: Optional[float] = None      # end of the tick that gave it a slot
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def kind(self) -> str:
        return "fresh" if self.index == 0 else "resume"

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.target


@dataclasses.dataclass
class Live:
    """A session that holds a store row."""
    spec: generator.Session
    uid: int
    prompt: np.ndarray
    turns: List[Turn] = dataclasses.field(default_factory=list)


class Window:
    """Drives one run's traffic through a scheduler and records every
    request's times and tokens."""

    def __init__(self, sched: Scheduler, traffic: dict, plan, *, seed: int,
                 vocab: int, rec: Optional[Dict[str, list]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.s, self.eng = sched, sched.eng
        self.traffic, self.plan, self.seed, self.vocab = (
            traffic, plan, seed, vocab)
        self.clock = clock
        self.n_rows = self.eng.n_sessions
        self.free_rows = list(range(self.n_rows))
        self.generation = 0
        self.live: Dict[int, Live] = {}          # uid -> session
        self.inflight: Dict[int, Turn] = {}      # uid -> its turn in flight
        self.followups: List[tuple] = []         # (due, uid) waiting to offer
        # due sessions with no store row yet, each with its first turn
        self.waiting: List[tuple] = []
        self.next_session = 0
        self.turns: List[Turn] = []
        self.sessions: List[Live] = []
        self.late: List[float] = []              # seen due - due time
        self.rec = rec if rec is not None else new_record()
        self.ticks = 0
        self.longest_tick = 0.0
        # ticks over LONG_TICK_S: (start after t0, seconds, host seconds of
        # each instrumented call inside the tick)
        self.long_ticks: List[tuple] = []
        self.row_waits = 0

    # ---- traffic ---------------------------------------------------------
    def _due(self, turn: Turn, now: float) -> None:
        """A turn falls due: it counts from now on, offered or not."""
        self.turns.append(turn)
        self.late.append(now - turn.due)

    def _offer(self, a: Arrival, turn: Turn) -> None:
        self.s.offer(a)
        self.inflight[a.uid] = turn

    def _start_session(self, spec: generator.Session, turn: Turn) -> None:
        row = self.free_rows.pop(0)
        uid = row + self.n_rows * self.generation
        self.generation += 1
        live = Live(spec=spec, uid=uid, prompt=generator.prompt_tokens(
            self.seed, spec.index, spec.prompt_len, self.vocab))
        self.live[uid] = live
        self.sessions.append(live)
        live.turns.append(turn)
        self._offer(Arrival(t_ns=0.0, uid=uid, kind="fresh", priority=0,
                            slo_ns=INF, new_tokens=spec.outputs[0],
                            prompt=live.prompt), turn)

    def _backlog_full(self) -> bool:
        if "concurrent" in self.traffic:
            alive = len(self.live) + len(self.waiting)
            return alive >= self.traffic["concurrent"]
        queued = sum(1 for t in self.inflight.values()
                     if t.placed is None) + len(self.waiting)
        return queued >= self.traffic["queued"]

    def _due_sessions(self, now: float) -> None:
        backlog = self.traffic["loop"] == "backlog"
        while backlog or self.next_session < len(self.plan):
            if backlog:
                if self._backlog_full():
                    break
                spec = dataclasses.replace(
                    self.plan[self.next_session % len(self.plan)],
                    index=self.next_session, due_s=now - self.t0)
            else:
                spec = self.plan[self.next_session]
                if self.t0 + spec.due_s > now:
                    break
            turn = Turn(session=spec.index, index=0, target=spec.outputs[0],
                        due=self.t0 + spec.due_s)
            self._due(turn, now)
            self.waiting.append((spec, turn))
            self.next_session += 1
        while self.waiting and self.free_rows:
            self._start_session(*self.waiting.pop(0))
        if self.waiting:
            self.row_waits += 1

    def _due_followups(self, now: float) -> None:
        keep = []
        for due, uid in self.followups:
            if due > now:
                keep.append((due, uid))
                continue
            live = self.live[uid]
            i = len(live.turns)
            turn = Turn(session=live.spec.index, index=i,
                        target=live.spec.outputs[i], due=due)
            live.turns.append(turn)
            self._due(turn, now)
            self._offer(Arrival(t_ns=0.0, uid=uid, kind="resume", priority=0,
                                slo_ns=INF, new_tokens=turn.target,
                                prompt=None), turn)
        self.followups = keep

    def next_due(self) -> float:
        """The host time at which the next arrival falls due."""
        t = min((d for d, _ in self.followups), default=INF)
        if (self.traffic["loop"] == "open"
                and self.next_session < len(self.plan)):
            t = min(t, self.t0 + self.plan[self.next_session].due_s)
        return t

    # ---- the tick --------------------------------------------------------
    def _stamp(self, reqs: List[Request], known: Dict[int, tuple],
               now: float) -> None:
        for r in reqs:
            turn = self.inflight.get(r.uid)
            if turn is None:
                continue
            # a resumed request starts with its seed token, already served
            _, n = known.get(id(r), (r, 0 if len(r.prompt) else 1))
            new = r.generated[n:]
            if turn.placed is None:
                turn.placed = now
            turn.tokens.extend(int(t) for t in new)
            turn.stamps.extend([now] * len(new))
            known[id(r)] = (r, len(r.generated))

    def _complete(self, now: float) -> None:
        for uid, turn in list(self.inflight.items()):
            if not turn.done:
                continue
            del self.inflight[uid]
            live = self.live[uid]
            if len(live.turns) < len(live.spec.outputs):
                think = live.spec.think_s[len(live.turns) - 1]
                self.followups.append((now + think, uid))
            else:
                self.eng.drop_session(uid)
                del self.live[uid]
                self.free_rows.append(uid % self.n_rows)

    def run(self, seconds: float) -> None:
        self.t0 = self.clock()
        self.known: Dict[int, tuple] = {}
        end = self.t0 + seconds
        while True:
            now = self.clock()
            if now >= end:
                break
            self._due_sessions(now)
            self._due_followups(now)
            if not self.s.pending():
                wait = min(self.next_due(), end) - self.clock()
                if wait > 0:
                    time.sleep(min(wait, 0.002))
                continue
            before = list(self.eng.active.values())
            self.rec["parts"].clear()
            start = self.clock()
            self.s.tick()
            self.ticks += 1
            now = self.clock()
            self.longest_tick = max(self.longest_tick, now - start)
            if now - start > LONG_TICK_S:
                self.long_ticks.append((start - self.t0, now - start,
                                        dict(self.rec["parts"])))
            after = list(self.eng.active.values())
            self._stamp(before + after, self.known, now)
            self.known = {id(r): self.known[id(r)] for r in after
                          if id(r) in self.known}
            self._complete(now)
        self.t1 = self.clock()


def scheduler(eng, traffic: dict) -> Scheduler:
    sc = traffic["scheduler"]
    return Scheduler(eng, policy=sc["policy"],
                     cfg=SchedConfig(preempt=sc["preempt"],
                                     age_every=sc["age_every"]))


# ---- what the window measured ------------------------------------------
def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) if len(
        values) else None


def first_token_ms(w: Window, kind: str) -> List[float]:
    """First new token minus due time of every ``kind`` request due in the
    window; a request still waiting at the close counts at its age then."""
    out = []
    for t in w.turns:
        if t.kind != kind or not (w.t0 <= t.due <= w.t1):
            continue
        first = t.stamps[0] if t.stamps else w.t1
        out.append((min(first, w.t1) - t.due) * 1e3)
    return out


def token_gaps_ms(w: Window) -> List[float]:
    """Every gap between consecutive tokens of one request inside the
    window, over all requests."""
    out = []
    for t in w.turns:
        s = np.asarray([x for x in t.stamps if w.t0 <= x <= w.t1])
        out.extend(np.diff(s) * 1e3)
    return out


def window_tokens(w: Window) -> int:
    return sum(1 for t in w.turns for x in t.stamps if w.t0 <= x <= w.t1)


def queue_waits_ms(w: Window) -> List[float]:
    """Due time to the tick that gave the request a slot, for requests due
    in the window; one still waiting counts at its age at the close."""
    return [((t.placed if t.placed is not None and t.placed <= w.t1
              else w.t1) - t.due) * 1e3
            for t in w.turns if w.t0 <= t.due <= w.t1]


# ---- what each layer was asked to do, and spans for the trace -----------
def instrument(eng, sched, rec: Dict[str, list], annotate: bool) -> None:
    """Wrap the engine's entry points and the scheduler's tick on these
    instances: each call records its work in ``rec``, adds its host seconds
    to ``rec["parts"]`` (which the window clears before each tick) and,
    when ``annotate``, runs inside a ``bench.<name>`` profiler
    annotation."""
    from jax.profiler import TraceAnnotation
    parts = rec["parts"]
    clock = time.perf_counter

    def wrap(obj, name, note=None):
        fn = getattr(obj, name)

        def timed(*a, **k):
            t = clock()
            try:
                return fn(*a, **k)
            finally:
                parts[name] = parts.get(name, 0.0) + clock() - t

        def call(*a, **k):
            if note is not None:
                note(*a, **k)
            if not annotate:
                return timed(*a, **k)
            with TraceAnnotation(f"bench.{name}"):
                return timed(*a, **k)
        setattr(obj, name, call)

    held = lambda slots: [int(eng.pos[s]) for s in slots]
    wrap(eng, "step_begin", lambda: eng.active and rec["decode"].append(
        (len(eng.active), sum(held(eng.active)))))
    wrap(eng, "step_end")
    wrap(eng, "submit", lambda req: rec["prefill"].append(len(req.prompt)))
    wrap(eng, "suspend", lambda slot: rec["suspend"].append(held([slot])))
    wrap(eng, "suspend_many",
         lambda slots: rec["suspend"].append(held(slots)))
    wrap(eng, "resume_many", lambda uids, extra: rec["resume"].append(
        [eng.session_pos[u] for u in uids]))
    wrap(eng, "fast_resident_uids")
    wrap(sched, "tick")


def new_record() -> Dict[str, list]:
    return {"decode": [], "prefill": [], "suspend": [], "resume": [],
            "parts": {}}


def resumes(traffic: dict) -> bool:
    """Whether the mix can resume a session: a follow-up turn, or a
    preempted request coming back."""
    return traffic["followups"]["max"] > 0 or traffic["scheduler"]["preempt"]


def warm(eng, prompt_lens, with_resumes: bool) -> List[int]:
    """Compile (or load from the compile cache) every program the window
    can call, and no others: the prefill bucket of each prompt length,
    a single suspend, suspend waves of 2..slots, the decode step and, where
    the mix resumes sessions, resume waves of 1..slots.  Leaves the engine
    with no active slot and no stored session.  Returns the buckets
    warmed."""
    buckets = sorted({eng._bucket_len(int(n)) for n in prompt_lens})
    one = lambda n: np.ones(n, np.int32)
    for b in buckets:
        slot = eng.submit(Request(uid=0, prompt=one(b), max_new=1 << 20))
        eng.suspend(slot)
        eng.drop_session(0)
    uids = list(range(eng.slots))

    def suspend_all():
        slots = sorted(eng.active)
        if len(slots) == 1:
            eng.suspend(slots[0])
        else:
            eng.suspend_many(slots)

    for k in range(eng.slots, 0, -1):
        for u in uids[:k]:
            eng.submit(Request(uid=u, prompt=one(buckets[0]),
                               max_new=1 << 20))
        eng.step()
        suspend_all()
        if with_resumes:
            eng.resume_many(uids[:k], 4)
            eng.step()
            suspend_all()
        for u in uids[:k]:
            eng.drop_session(u)
    return buckets
