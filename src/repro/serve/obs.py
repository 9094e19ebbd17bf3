"""Spans and counters of the served path, switched by the profiler itself.

``span(name, **attrs)`` marks one stage of serving.  While a profiler
session records host events (``jax.profiler.trace`` around serving, or the
benchmark's traced run) it does two things:

* it opens a ``jax.profiler.TraceAnnotation``, so the span lands in the
  trace on the same clock as the device operations, with its attrs as the
  event's stats;
* on exit it appends ``(name, t_start, t_end, attrs)`` to a bounded
  in-process ring, stamped with ``time.perf_counter``, which ``events``
  reads back.

With no session, ``span`` records nothing and costs one
``TraceAnnotation.is_enabled()`` check: it hands back a span whose ``set``
does nothing, so attrs computed by a callable (``sp.set(rows=lambda: ...)``)
are never computed.  There is no other switch.

``add(**counts)`` adds to a counter of every span open around the call, so
a count made in a stage also counts toward the call and the step that hold
it.  Every span name begins ``serve.``: the benchmark keeps its own host
spans (``window``, ``arrival_wait``, ``submit``, ``step``) by exact name.
The stack of open spans assumes one serving thread, as ``ServeRuntime`` is.
"""

from __future__ import annotations

import collections
import time

from jax.profiler import TraceAnnotation

#: events the ring keeps: a 20 s window of about ten spans a step at 1,500
#: steps holds about 15,000
RING_SIZE = 1 << 16


def enabled() -> bool:
    """Whether a profiler session records host events now."""
    return TraceAnnotation.is_enabled()


def _resolve(attrs: dict) -> dict:
    return {k: v() if callable(v) else v for k, v in attrs.items()}


def _flat(attrs: dict) -> dict:
    """Trace-event stats: scalars only, a dict attr as ``key.sub``."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, dict):
            out.update({f"{k}.{sub}": x for sub, x in v.items()})
        else:
            out[k] = v
    return out


class _Off:
    """The span handed out while nothing records."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("ring", "name", "attrs", "t0", "_annotation")

    def __init__(self, ring: Ring, name: str, attrs: dict):
        self.ring, self.name, self.attrs = ring, name, _resolve(attrs)

    def set(self, **attrs) -> None:
        self.attrs.update(_resolve(attrs))

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.ring._open.append(self)
        self.t0 = self.ring.clock()
        return self

    def __exit__(self, *exc):
        t1 = self.ring.clock()
        self.ring._open.pop()
        if self.attrs:
            self._annotation.set_metadata(**_flat(self.attrs))
        self._annotation.__exit__(*exc)
        self.ring._append((self.name, self.t0, t1, self.attrs))
        return False


class Ring:
    """Bounded record of the spans that closed while a profiler recorded.

    Events arrive in the order their spans close, so the oldest event
    dropped for room always ended last among those dropped.  ``clock`` is
    the benchmark's, so that its window selects events directly."""

    def __init__(self, maxlen: int = RING_SIZE, clock=time.perf_counter):
        self.clock = clock
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._open: list[_Span] = []
        #: events dropped for room, and the end of the newest of them
        self.dropped = 0
        self._lost_until = float("-inf")

    def span(self, name: str, **attrs):
        if not TraceAnnotation.is_enabled():
            return _OFF
        return _Span(self, name, attrs)

    def add(self, **counts) -> None:
        if not self._open or not TraceAnnotation.is_enabled():
            return
        for sp in self._open:
            for k, n in counts.items():
                sp.attrs[k] = sp.attrs.get(k, 0) + n

    def _append(self, event) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
            self._lost_until = self._events[0][2]
        self._events.append(event)

    def events(self, t0: float, t1: float) -> tuple[list, bool]:
        """Events that overlap ``[t0, t1]`` (times on the ring's clock),
        sorted by start, and whether any such event was dropped."""
        inside = [e for e in self._events if e[1] <= t1 and e[2] >= t0]
        inside.sort(key=lambda e: (e[1], -e[2]))
        return inside, self._lost_until >= t0


_RING = Ring()
span = _RING.span
add = _RING.add
events = _RING.events
