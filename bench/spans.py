"""The program's own spans in a benchmark trace: where the host's time goes.

The program marks its phases on the profiler's clock (``repro.obs.host``):
``repro.tick`` and, nested in it, ``repro.admit``, ``repro.step_begin``,
``repro.plan``, ``repro.step_end`` (with ``repro.bookkeep``) and
``repro.wave``; the engine's ``repro.submit``, ``repro.suspend``,
``repro.suspend_many`` and ``repro.resume_many``; ``repro.dispatch`` around
each jitted call (attr ``program``) and ``repro.read`` around each
device→host read (attr ``what``: ``tokens``, ``tags``, ``prefill_token``).
Its movement legs run under the named scopes ``movement.<leg>``
(``pack_pages``, ``checksum``, ``tier_read``/``tier_write``,
``page_gather``/``page_scatter``, ``unpack_pages``), which a TPU trace
carries in each operation's ``tf_op`` stat.

``bench/trace.py`` keeps the harness's ``bench.*`` annotations and the
operations' names alone, so its readers see none of this.  This module
reduces it from the same ``.xplane.pb``, and

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--keep <file.xplane.pb.gz>]

runs one cell once with ``--trace 1`` through ``bench/run.py``'s own
``run_cell``, prints its result line, then one more JSON line: the numbers
below (``program``) and the cell's end-to-end metrics as the traced window
read them (``traced_end_to_end``).  ``--keep`` keeps the trace, gzipped.
Without a TPU it prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import gzip
import json
import os
import pathlib
import re
import shutil
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as T  # noqa: E402

Event = T.Event
Span = Tuple[str, float, float, dict]       # (name, start_ns, end_ns, attrs)
PROGRAM = "repro."
SCOPE = re.compile(r"movement\.(\w+)")
DECODE = "decode_step_batched"


@dataclasses.dataclass
class Program:
    trace: T.Trace                      # the harness's reduction
    spans: List[Span]                   # repro.* host events, by start
    modules: List[Event]                # chip 0's program executions
    moves: List[Event]                  # chip 0's operations under a
    #                                     movement scope, named by the
    #                                     innermost scope; clipped

    @property
    def on_chip(self) -> bool:
        return bool(self.trace.ops or self.trace.modules)

    def in_window(self, name: str) -> List[Span]:
        lo, hi = self.trace.window
        return [s for s in self.spans if s[0] == name and lo <= s[1] < hi]


# ---- reading the trace ---------------------------------------------------
def _xspace_class():
    """A protobuf class for the parts of ``XSpace`` read here (the
    profiler's own schema, ``tsl/profiler/protobuf/xplane.proto``); every
    other field is skipped when parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package="bench_xspace")

    def message(name, *fields):
        # (name, number, type, message type for a message field, repeated)
        m = fd.message_type.add(name=name)
        for fname, number, kind, ref, rep in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_xspace." + ref

    I64, U64, STR, MSG = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                          F.TYPE_MESSAGE)
    message("Stat", ("metadata_id", 1, I64, None, False),
            ("str_value", 5, STR, None, False),
            ("ref_value", 7, U64, None, False))
    message("EventMeta", ("id", 1, I64, None, False),
            ("name", 2, STR, None, False), ("stats", 5, MSG, "Stat", True))
    message("StatMeta", ("id", 1, I64, None, False),
            ("name", 2, STR, None, False))
    # a map field is a repeated message of (key = 1, value = 2)
    message("EventMetaEntry", ("key", 1, I64, None, False),
            ("value", 2, MSG, "EventMeta", False))
    message("StatMetaEntry", ("key", 1, I64, None, False),
            ("value", 2, MSG, "StatMeta", False))
    message("Event", ("metadata_id", 1, I64, None, False),
            ("offset_ps", 2, I64, None, False),
            ("duration_ps", 3, I64, None, False))
    message("Line", ("name", 2, STR, None, False),
            ("timestamp_ns", 3, I64, None, False),
            ("events", 4, MSG, "Event", True))
    message("Plane", ("name", 2, STR, None, False),
            ("lines", 3, MSG, "Line", True),
            ("event_metadata", 4, MSG, "EventMetaEntry", True),
            ("stat_metadata", 5, MSG, "StatMetaEntry", True))
    message("Space", ("planes", 1, MSG, "Plane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.Space"))


def _raw(path: str) -> bytes:
    if os.path.isdir(path):
        path = T.find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return f.read()
    with open(path, "rb") as f:
        return f.read()


def _movement_ops(raw: bytes) -> List[Event]:
    """Chip 0's operations whose ``tf_op`` names a movement scope, each
    named by its innermost one."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    chips = sorted((p for p in space.planes if p.name.startswith(T.CHIP)),
                   key=lambda p: p.name)
    if not chips:
        return []
    plane = chips[0]
    tf_op = {e.key for e in plane.stat_metadata if e.value.name == "tf_op"}
    leg = {}
    for e in plane.event_metadata:
        for st in e.value.stats:
            if st.metadata_id in tf_op:
                scopes = SCOPE.findall(st.str_value)
                if scopes:
                    leg[e.key] = scopes[-1]
    out = []
    for line in plane.lines:
        if line.name != T.OPS:
            continue
        for ev in line.events:
            name = leg.get(ev.metadata_id)
            if name is not None:
                a = line.timestamp_ns + ev.offset_ps * 1e-3
                out.append((name, a, a + ev.duration_ps * 1e-3))
    return out


def load(path: str, tr: Optional[T.Trace] = None) -> Program:
    """The program's spans, chip 0's executions and its movement
    operations from an ``.xplane.pb`` (gzipped or not, or the directory
    that holds one); ``tr`` is the harness's reduction of the same file
    when it is at hand."""
    from jax.profiler import ProfileData
    tr = T.load(path) if tr is None else tr
    raw = _raw(path)
    pd = ProfileData.from_serialized_xspace(raw)
    spans, modules = [], []
    chip0 = min((p.name for p in pd.planes if p.name.startswith(T.CHIP)),
                default=None)
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(PROGRAM)]
            elif plane.name == chip0 and line.name == T.MODULES:
                modules = sorted(
                    ((e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events), key=lambda e: e[1])
    lo, hi = tr.window
    return Program(trace=tr, spans=sorted(spans, key=lambda s: (s[1], -s[2])),
                   modules=modules,
                   moves=T._clip(_movement_ops(raw), lo, hi))


# ---- intervals -------------------------------------------------------------
def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                    float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap_ns(xs, ys) -> float:
    """Length of the intersection of two unions of intervals."""
    xs, ys = _union(xs), _union(ys)
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _uids(attrs: dict) -> List[int]:
    v = attrs.get("uids", attrs.get("uid", ""))
    return [int(u) for u in str(v).split(";") if u != ""]


# ---- the numbers -------------------------------------------------------------
def host_idle_share(p: Program) -> Optional[float]:
    """The part of chip 0's idle time that falls inside a ``repro.tick``
    span, over the window, in %: idle that the program's own host work
    caused (arrival waits and the harness between ticks stay out)."""
    if not p.on_chip:
        return None
    ticks = [(a, b) for n, a, b, _ in p.spans if n == "repro.tick"]
    if not ticks:
        return None
    lo, hi = p.trace.window
    return 100.0 * _overlap_ns(T.idle_gaps(p.trace), ticks) / (hi - lo)


def tick_host_ms(p: Program) -> Optional[float]:
    """Mean, over the ticks that start in the window, of the tick's span
    less the union of the ``repro.read`` spans inside it, in ms: the
    host's own work per tick, apart from its waits on the device."""
    ticks = p.in_window("repro.tick")
    if not ticks:
        return None
    reads = [s for s in p.spans if s[0] == "repro.read"]
    starts = [s[1] for s in reads]
    own = []
    for _, a, b, _ in ticks:
        i, j = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
        waits = [(ra, rb) for _, ra, rb, _ in reads[i:j] if rb <= b]
        own.append(b - a - sum(y - x for x, y in _union(waits)))
    return sum(own) / len(own) * 1e-6


def sched_waits_ms(p: Program) -> List[float]:
    """For each uid a ``repro.admit`` span in the window queued: from that
    span's start to the start of the ``repro.submit`` or
    ``repro.resume_many`` span that next gave the uid a slot; one still
    queued counts up to the close."""
    lo, hi = p.trace.window
    placed: Dict[int, List[float]] = {}
    for n, a, _, attrs in p.spans:
        if n in ("repro.submit", "repro.resume_many"):
            for u in _uids(attrs):
                placed.setdefault(u, []).append(a)
    out = []
    for _, a, _, attrs in p.in_window("repro.admit"):
        for u in _uids(attrs):
            later = placed.get(u, [])
            k = bisect.bisect_left(later, a)
            end = later[k] if k < len(later) else hi
            out.append((min(end, hi) - a) * 1e-6)
    return out


def sched_wait_p75_ms(p: Program) -> Optional[float]:
    from bench import driver
    return driver.percentile(sched_waits_ms(p), 75)


def movement_device_share(p: Program) -> Optional[float]:
    """Chip 0's device time under the movement scopes over its busy
    time, in %."""
    if not p.on_chip or not p.moves:
        return None
    busy = T.union_ns(p.trace.ops[0] or p.trace.modules[0])
    return 100.0 * T.union_ns(p.moves) / busy if busy else None


def movement_legs_s(p: Program) -> Dict[str, float]:
    """Device seconds of each movement leg's operations (the checksum
    apart, wherever it runs)."""
    acc: Dict[str, float] = {}
    for leg, a, b in p.moves:
        acc[leg] = acc.get(leg, 0.0) + (b - a) * 1e-9
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def dispatch_first_share(p: Program) -> Optional[float]:
    """Of the decode programs that start in the window, the share (in %)
    whose device execution starts after its ``repro.dispatch`` span
    started: the host spans and the device share one clock.  The i-th
    decode dispatch launches the i-th decode execution."""
    runs = [m for m in p.modules if DECODE in m[0]]
    sent = [s for s in p.spans if s[0] == "repro.dispatch"
            and s[3].get("program") == DECODE]
    if not runs or len(runs) != len(sent):
        return None
    lo, hi = p.trace.window
    pairs = [(r[1], s[1]) for r, s in zip(runs, sent) if lo <= r[1] < hi]
    if not pairs:
        return None
    return 100.0 * sum(r >= s for r, s in pairs) / len(pairs)


def _nested(spans: Sequence[Span]):
    """(span, its parent or None) for spans that nest, in start order."""
    stack: List[Span] = []
    for s in spans:
        while stack and stack[-1][2] <= s[1]:
            stack.pop()
        yield s, (stack[-1] if stack else None)
        stack.append(s)


def self_time_s(p: Program) -> Dict[str, float]:
    """Seconds of the window spent in each program span and not in a span
    nested in it (a read or a dispatch by its ``what`` or ``program``)."""
    lo, hi = p.trace.window
    inside = [s for s in p.spans if s[2] > lo and s[1] < hi]
    child: Dict[int, float] = {}
    for s, parent in _nested(inside):
        if parent is not None:
            child[id(parent)] = child.get(id(parent), 0.0) + (
                min(s[2], hi) - max(s[1], lo))
    acc: Dict[str, float] = {}
    for s in inside:
        k = _label(s)
        own = min(s[2], hi) - max(s[1], lo) - child.get(id(s), 0.0)
        acc[k] = acc.get(k, 0.0) + own * 1e-9
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def _label(s: Span) -> str:
    """A span's name, with what a read reads or a dispatch runs."""
    extra = s[3].get("what", s[3].get("program"))
    return f"{s[0]}:{extra}" if extra is not None else s[0]


def top_idle(p: Program, n: int = 10) -> List[List]:
    """Chip 0's idle seconds grouped by the innermost span, the program's
    or the harness's, that the host was in at the middle of each gap; the
    ``n`` largest groups.  ``bench.idle``: in no span."""
    spans = sorted([(nm, a, b, {}) for nm, a, b in p.trace.host
                    if nm != T.WINDOW] + p.spans, key=lambda s: (s[1], -s[2]))
    acc: Dict[str, float] = {}
    stack: List[Span] = []
    i = 0
    for a, b in T.idle_gaps(p.trace):
        t = (a + b) / 2
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        k = _label(stack[-1]) if stack else "bench.idle"
        acc[k] = acc.get(k, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def long_ticks(p: Program, over_s: float = 1.0, n: int = 3) -> List[List]:
    """Each tick of the window longer than ``over_s``: its start after the
    window opened, its seconds, and the ``n`` spans inside it that held
    most of its time by their own seconds."""
    lo, _ = p.trace.window
    out = []
    for t in p.in_window("repro.tick"):
        if (t[2] - t[1]) * 1e-9 <= over_s:
            continue
        inner = [s for s in p.spans if t[1] <= s[1] and s[2] <= t[2]]
        sub = Program(trace=dataclasses.replace(p.trace, window=(t[1], t[2])),
                      spans=inner, modules=[], moves=[])
        top = list(self_time_s(sub).items())[:n]
        out.append([(t[1] - lo) * 1e-9, (t[2] - t[1]) * 1e-9, top])
    return out


def preemptions(p: Program) -> int:
    """Preemption waves in the window: the suspends the scheduler's wave
    issues itself (a completion suspends inside ``repro.step_end``, a
    one-token prefill inside ``repro.submit``)."""
    lo, hi = p.trace.window
    return sum(1 for s, parent in _nested(p.spans)
               if s[0] in ("repro.suspend", "repro.suspend_many")
               and parent is not None and parent[0] == "repro.wave"
               and lo <= s[1] < hi)


def reduce(path: str, tr: Optional[T.Trace] = None) -> dict:
    """Every number of this module for one trace."""
    p = load(path, tr)
    reads: Dict[str, int] = {}
    for s in p.in_window("repro.read"):
        reads[s[3].get("what")] = reads.get(s[3].get("what"), 0) + 1
    ticks = len(p.in_window("repro.tick"))
    return {
        "host_idle_share": host_idle_share(p),
        "tick_host_ms": tick_host_ms(p),
        "sched_wait_p75_ms": sched_wait_p75_ms(p),
        "movement_device_share": movement_device_share(p),
        "dispatch_first_share": dispatch_first_share(p),
        "device_idle_share": (100.0 * (1.0 - T.busy_s(p.trace)
                                       / p.trace.window_s)
                              if p.on_chip else None),
        "ticks": ticks,
        "reads_per_tick": {k: v / ticks for k, v in reads.items()}
        if ticks else {},
        "preempt_suspend": preemptions(p),
        "movement_legs_s": movement_legs_s(p),
        "idle_gaps": top_idle(p),
        "host_self_s": self_time_s(p),
        "long_ticks": long_ticks(p),
    }


# ---- one traced run that keeps what bench/trace.py leaves out ------------------
def traced_run(bench: dict, cell: dict, conf: dict, traffic: dict, *,
               seed: int, seconds: float, keep: Optional[str] = None,
               **kw) -> Tuple[dict, dict]:
    """``bench/run.py``'s ``run_cell`` with ``--trace 1``, reducing this
    module's numbers from its trace before the harness deletes it; returns
    (the result line, the program's numbers and the traced end-to-end
    metrics)."""
    from bench import run as R
    got: dict = {}
    harness_load = T.load

    def load_and_reduce(path):
        tr = harness_load(path)
        got["program"] = reduce(path, tr)
        if keep:
            src = T.find_xplane(path) if os.path.isdir(path) else path
            with open(src, "rb") as f, gzip.open(keep, "wb") as g:
                shutil.copyfileobj(f, g)
        return tr

    seen: dict = {}
    T.load = load_and_reduce
    try:
        out = R.run_cell(bench, cell, conf, traffic, seed=seed,
                         seconds=seconds, trace=True,
                         also=lambda ns: seen.update(window=ns.window), **kw)
    finally:
        T.load = harness_load
    run = SimpleNamespace(window=seen["window"])
    got["traced_end_to_end"] = {
        m["name"]: R.reader(m["name"])(run)
        for m in R.cell_metrics(bench, cell["name"], False)
        if m["name"] != "setup_s"}
    return out, got


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep", help="write the trace here, gzipped")
    args = p.parse_args(argv)
    from bench import run as R
    bench, cell, conf, traffic = R.load_cell(ROOT, args.workload)
    try:
        out, got = traced_run(bench, cell, conf, traffic, seed=args.seed,
                              seconds=args.seconds, keep=args.keep,
                              chips=cell["chips"])
    except R.NoChip as e:
        print(f"bench/spans.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main())
