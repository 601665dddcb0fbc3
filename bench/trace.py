"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A TPU trace (``.xplane.pb``) has one plane per chip, ``/device:TPU:<i>``,
whose line ``XLA Modules`` holds one event per program execution and whose
line ``XLA Ops`` holds one event per operation, named by its HLO text
(``%fusion.148 = f32[...] fusion(...)``).  The host plane ``/host:CPU``
holds the harness's ``bench.*`` annotations
(``jax.profiler.TraceAnnotation``) on the same clock; the traced window is
the ``bench.window`` annotation.

Two names are not in the trace as the program writes them:

- The engine jits ``functools.partial`` objects, which have no
  ``__name__``, so their executions read ``jit__unknown(<fingerprint>)``.
  The host's Python thread names each dispatch
  (``PjitFunction(decode_step_batched)``) and marks each launch with one
  ``PJRT_LoadedExecutable_Execute linkage`` event; one chip executes the
  launches in order, so the i-th launch names the i-th execution.
- The Pallas page kernels are lowered through ``lax.platform_dependent``,
  so their operations read ``%branch_0_fun.<n> = ... custom-call(...),
  custom_call_target="tpu_custom_call"``.  ``villa_scatter`` aliases its
  pool into its output (``output_to_operand_aliasing``); ``villa_gather``
  does not.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
from typing import Dict, List, Optional, Sequence, Tuple

CHIP, MODULES, OPS = "/device:TPU:", "XLA Modules", "XLA Ops"
WINDOW = "bench.window"

Event = Tuple[str, float, float]        # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    modules: List[List[Event]]          # per device plane
    ops: List[List[Event]]
    host: List[Event]                   # bench.* annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


PALLAS = 'custom_call_target="tpu_custom_call"'
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"


def op_name(text: str) -> str:
    """An operation's event name, with the page kernels named."""
    if PALLAS in text:
        return ("villa_scatter" if "output_to_operand_aliasing" in text
                else "villa_gather")
    return text


def launch_names(events: Sequence[Event]) -> List[str]:
    """For each launch marker of the host's Python thread, in time order,
    the function whose dispatch (``PjitFunction(<fn>)``) encloses it."""
    calls = sorted((e for e in events if e[0].startswith("PjitFunction(")),
                   key=lambda e: e[1])
    starts = [e[1] for e in calls]
    names = []
    for _, a, b in sorted((e for e in events if e[0] == LAUNCH),
                          key=lambda e: e[1]):
        i = bisect.bisect_right(starts, a)
        inner = [c for c in calls[max(0, i - 8):i] if c[2] >= b]
        fn = min(inner, key=lambda c: c[2] - c[1])[0] if inner else "?"
        names.append("jit_" + fn[len("PjitFunction("):-1])
    return names


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (gzipped or not, or the directory that holds
    one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    modules, ops, host, python = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith(CHIP):
            lines = {ln.name: [(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in ln.events] for ln in plane.lines}
            modules.append(sorted(lines.get(MODULES, []),
                                  key=lambda e: e[1]))
            ops.append([(op_name(n), a, b) for n, a, b in lines.get(OPS, [])])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in ln.events]
                host += [e for e in ev if e[0].startswith("bench.")]
                if ln.name.startswith("python"):
                    python += ev
    names = launch_names(python)
    if len(modules) == 1 and len(names) == len(modules[0]):
        modules = [[(nm if m[0].startswith("jit__unknown") else m[0], m[1],
                     m[2]) for nm, m in zip(names, modules[0])]]
    wins = [(a, b) for n, a, b in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    lo, hi = wins[0]
    return Trace(window=(lo, hi),
                 modules=[_clip(m, lo, hi) for m in modules],
                 ops=[_clip(o, lo, hi) for o in ops],
                 host=_clip(host, lo, hi))


def union_ns(events: Sequence[Event]) -> float:
    total, end = 0.0, -float("inf")
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips traced
    (a chip with no op line counts by its program executions)."""
    per = [union_ns(o or m) for o, m in zip(tr.ops, tr.modules)]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def program_time(tr: Trace, name: str) -> Tuple[float, int]:
    """(seconds, executions) of the programs whose name contains
    ``name``, summed over chips."""
    ev = [e for m in tr.modules for e in m if name in e[0]]
    return sum(b - a for _, a, b in ev) * 1e-9, len(ev)


def op_time(tr: Trace, name: str) -> Tuple[float, int]:
    """(seconds, events) of the operations whose name contains ``name``."""
    ev = [e for o in tr.ops for e in o if name in e[0]]
    return sum(b - a for _, a, b in ev) * 1e-9, len(ev)


def _short(name: str) -> str:
    """A program's or an operation's name without its instance number:
    ``jit_decode_step_batched(123)`` -> ``jit_decode_step_batched``, and
    ``%fusion.148 = f32[...] fusion(...)`` -> ``fusion``."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
        head, _, tail = name.rpartition(".")
        return head if tail.isdigit() else name
    return name.split("(")[0]


def longest_ops(tr: Trace, n: int = 5) -> List[List]:
    """The ``n`` longest single device operations: name, start after the
    window opened and seconds (chip 0).  A host stall that the device spent
    busy shows here."""
    ev = (tr.ops[0] or tr.modules[0]) if (tr.ops or tr.modules) else []
    ev = sorted(ev, key=lambda e: e[1] - e[2])[:n]
    return [[_short(name), (a - tr.window[0]) * 1e-9, (b - a) * 1e-9]
            for name, a, b in ev]


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time (by name), in seconds."""
    acc: Dict[str, float] = {}
    for o, m in zip(tr.ops, tr.modules):
        for name, a, b in (o or m):
            acc[_short(name)] = acc.get(_short(name), 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace) -> List[Tuple[float, float]]:
    """Intervals of the window (chip 0) in which no operation ran."""
    ev = sorted(tr.ops[0] or tr.modules[0], key=lambda e: e[1]) if (
        tr.ops or tr.modules) else []
    gaps, t = [], tr.window[0]
    for _, a, b in ev:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if tr.window[1] > t:
        gaps.append((t, tr.window[1]))
    return gaps


def _host_span_at(spans: List[Event], starts: List[float], t: float) -> str:
    """The innermost ``bench.*`` annotation (other than the window) that
    covers host time ``t``; spans nest, so the covering ones start shortly
    before ``t``."""
    best: Optional[Event] = None
    i = bisect.bisect_right(starts, t)
    for e in spans[max(0, i - 64):i]:
        if e[2] >= t and (best is None or e[2] - e[1] < best[2] - best[1]):
            best = e
    return best[0] if best else "bench.idle"


def top_idle(tr: Trace, n: int = 10) -> List[List]:
    """Idle seconds of chip 0 grouped by what the host was doing at the
    middle of each gap; the ``n`` largest groups."""
    spans = sorted((e for e in tr.host if e[0] != WINDOW),
                   key=lambda e: e[1])
    starts = [e[1] for e in spans]
    acc: Dict[str, float] = {}
    for a, b in idle_gaps(tr):
        k = _host_span_at(spans, starts, (a + b) / 2)
        acc[k] = acc.get(k, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
