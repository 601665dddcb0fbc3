"""Observability on two clocks: virtual-clock span tracing, and host spans
on the profiler's clock.

Public surface::

    from repro import obs

    tr = obs.Tracer(mechanism="lisa")
    s = Scheduler(engine, cfg, tracer=tr)   # spans in modeled ns
    s.run()                                  # summary() gains a trace block
    obs.write_chrome_trace(tr, "trace.json") # open in Perfetto

Spans record the SAME numbers the Decision ledger charges (per-leg
movement splits, fault retries, recovery restores), on per-replica lanes —
see DESIGN.md Sec. 14 for the span <-> DRAM-command-timeline mapping and
:mod:`repro.obs.tracer` for the lane/cursor model.  Everything here is
host bookkeeping over the virtual clock: zero device dispatches, no
wall-clock reads (repro-lint enforced).

Where the host's real time goes is the other clock:
:func:`repro.obs.host.span` marks the tick's phases, dispatches and
device→host reads as ``jax.profiler`` annotations, stamped by the profiler
on its device trace's clock (import it from :mod:`repro.obs.host`, which
needs JAX; this package does not).
"""
from repro.obs.tracer import NULL_TRACER, NullTracer, Span, Tracer
from repro.obs.export import chrome_trace, trace_events, write_chrome_trace

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span",
    "chrome_trace", "trace_events", "write_chrome_trace",
]
