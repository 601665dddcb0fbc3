"""Host spans on the profiler's clock: where the host's time goes.

One package, two clocks.  :class:`repro.obs.Tracer` records MODELED
nanoseconds on the scheduler's virtual clock: that timeline prices movement
(Table 1's mechanisms) and is byte-stable.  The spans here mark what the
host is doing in real time — a tick, its admissions, the wave it plans, a
dispatch, a device→host read — as ``jax.profiler`` annotations.  The
profiler stamps them in C++ on the clock of its device trace, so each span
lines up with the device operations it launched or waited for, and no
Python code reads a clock (repro-lint's wallclock rule covers this package).

Names are ``repro.<phase>``; nesting gives each span its cause, and the
spans of one request share its uid.  Outside a profiler session
:func:`span` returns one shared no-op context and formats no attrs, so an
untraced run pays for the call and the profiler's ``is_enabled`` check.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]

_SEQUENCE = (list, tuple, set, frozenset)


def _encode(attrs: dict) -> dict:
    # a sequence becomes one ';'-joined string: the profiler encodes attrs
    # as "name#k=v,k=v#", so a comma inside a value would split it
    return {k: ";".join(map(str, v)) if isinstance(v, _SEQUENCE) else v
            for k, v in attrs.items()}


class _Off:
    """The span outside a profiler session: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def __bool__(self) -> bool:
        # falsy, so a caller can skip building attrs nothing will record
        return False

    def set(self, **attrs) -> None:
        """Attrs known only once the work is done (see :class:`_On`)."""


_OFF = _Off()


class _On(TraceAnnotation):
    """A span recorded by the running profiler."""

    def set(self, **attrs) -> None:
        """Add attrs known only once the work is done (what a tick
        admitted, how many requests a step completed)."""
        self.set_metadata(**_encode(attrs))


def span(name: str, **attrs):
    """A context manager that records ``name`` with ``attrs`` (ints and
    strings; a sequence is ``;``-joined) while the profiler runs, and
    nothing otherwise.  The object it yields takes late attrs through
    ``.set(**attrs)`` and is falsy when nothing records them."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _On(name, **_encode(attrs))
