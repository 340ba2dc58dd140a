"""Host spans of the program: named, nested stretches of host time, off
by default.

    from repro.core import spans

    with spans.span("repro.engine.decode", step=3, active=24):
        ...

With recording on (``enable()``), a span does two things. It enters a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so a
running profiler places it on its host plane, on the clock of the
device's ops. On exit it appends a ``Span`` stamped with
``time.perf_counter_ns()`` to a bounded in-memory ring, which
``snapshot()`` reads out when the run is over. With recording off,
``span()`` returns one shared no-op context and reads no clock.

``timed()`` is the form for a caller that needs the stamps itself, such
as a counter fed from the same clock reads as the span: it stamps
whether recording is on or off, and records only when it is on.
``record()`` adds a span whose start was stamped earlier, such as a
request's wait in a queue; it reaches the ring only, since a profiler
cannot be handed a past start.

Every name starts with ``repro.``; spans scoped to one request carry
``rid=``. The recorder keeps one stack of open spans for the process,
so spans are opened and closed by one thread.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

#: Spans kept in the ring; older ones drop out first.
RING_SIZE = 65_536


class Span(NamedTuple):
    name: str
    start_ns: int                # time.perf_counter_ns()
    end_ns: int
    parent: Optional[str]        # the innermost span open at its start
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_on = False
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_open: List[str] = []            # names of the open recorded spans


class Timed:
    """One span's stamps; recorded on exit if recording was on when it
    was entered."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_parent", "_tm")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._tm = None

    def __enter__(self) -> "Timed":
        if _on:
            self._parent = _open[-1] if _open else None
            _open.append(self.name)
            self._tm = jax.profiler.TraceAnnotation(self.name, **self.attrs)
            self._tm.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._tm is not None:
            self._tm.__exit__(*exc)
            _open.pop()
            _ring.append(Span(self.name, self.start_ns, self.end_ns,
                              self._parent, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class _Off:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


def span(name: str, **attrs):
    """A span, or the shared no-op when recording is off."""
    return Timed(name, attrs) if _on else _OFF


def timed(name: str, **attrs) -> Timed:
    """A span that is stamped even when recording is off."""
    return Timed(name, attrs)


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """Add a finished span stamped by the caller on the same clock."""
    if _on:
        _ring.append(Span(name, start_ns, end_ns,
                          _open[-1] if _open else None, attrs))


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def snapshot() -> List[Span]:
    """The ring's spans, oldest first (each added when it closed)."""
    return list(_ring)


def reset() -> None:
    _ring.clear()
