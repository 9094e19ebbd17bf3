"""The program's own spans of a run's window, step by step.

While a profiler session records, ``repro.serve.obs`` keeps every
``serve.*`` span of the served path as ``(name, start, end, attrs)`` on the
benchmark's clock (``time.perf_counter``).  This module reads them back for
``run.window`` and groups them under the ``serve.step`` that holds them;
the per-layer readers ``metrics/<stage>_ms_per_step.py`` and their kin
reduce the groups.

Stages are the spans that split a call: a stage inside another stage
counts toward the outer one only.  Steps are those that executed a batch
and lie wholly inside the window.  Everything returns ``None`` when the
program recorded nothing there (no ``repro.serve.obs``, or no profiler
session) or dropped any event of the window, so a reader never reports a
partial count.
"""

from __future__ import annotations

import dataclasses

STAGES = ("serve.pack", "serve.run", "serve.unpack", "serve.compile")


@dataclasses.dataclass
class Step:
    start: float
    end: float
    attrs: dict
    #: the events inside the step, sorted by start (itself excluded)
    events: list

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def _top_level(self):
        stages = [e for e in self.events if e[0] in STAGES]
        for e in stages:
            if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                       for o in stages):
                yield e

    def stage(self, name: str) -> float:
        """Seconds in stage ``name`` outside any other stage."""
        return sum(e[2] - e[1] for e in self._top_level() if e[0] == name)

    def calls(self) -> float:
        return sum(e[2] - e[1] for e in self.events if e[0] == "serve.call")


def group(events, window) -> list[Step]:
    """``serve.step`` events that executed a batch inside ``window``, each
    with the events it holds."""
    t0, t1 = window
    out = []
    for name, a, b, attrs in events:
        if name == "serve.step" and t0 <= a and b <= t1 and attrs.get("rows"):
            inner = [e for e in events
                     if e[0] != "serve.step" and a <= e[1] and e[2] <= b]
            out.append(Step(a, b, attrs, inner))
    return out


def steps(run) -> list[Step] | None:
    """The steps of ``run``'s window, or ``None`` where the program
    recorded none or dropped any."""
    try:
        from repro.serve import obs
    except ImportError:
        return None
    events, dropped = obs.events(*run.window)
    if dropped:
        return None
    return group(events, run.window) or None


def mean_per_step(run, per_step) -> float | None:
    """Mean of ``per_step(step)`` over the run's steps."""
    found = steps(run)
    if found is None:
        return None
    return sum(per_step(s) for s in found) / len(found)
