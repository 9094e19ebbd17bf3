"""Reduction of a profiler trace to device busy time, time per device
operation and the host spans around them.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it.  Device
operations are the events of each device plane's ``XLA Ops`` line; host
spans are the benchmark's own ``TraceAnnotation`` events (``window``,
``arrival_wait``, ``submit``, ``step``).  Times are nanoseconds on the
trace's one clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
from collections import defaultdict

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
SPANS = ("window", "arrival_wait", "submit", "step")


@dataclasses.dataclass
class Trace:
    #: per device plane, its operations as (name, start, end)
    ops: dict[str, list[tuple[str, float, float]]]
    #: the benchmark's host spans as (name, start, end)
    spans: list[tuple[str, float, float]]
    #: every plane's line names, to say what a trace without ops held
    lines: dict[str, list[str]] = dataclasses.field(default_factory=dict)

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[0] == "window"]
        if not w:
            raise ValueError("trace holds no 'window' span")
        return w[0][1], w[0][2]

    def device_names(self) -> list[str]:
        return sorted(self.ops)


def load(path) -> Trace:
    """Read one ``.xplane.pb`` file, or the newest under a directory."""
    path = pathlib.Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    return parse_xspace(path.read_bytes())


def parse_xspace(xspace: bytes) -> Trace:
    """The device ops and host spans of a serialized profiler trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(xspace)
    ops: dict[str, list] = {}
    spans = []
    lines = {}
    for plane in data.planes:
        lines[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS)
    spans.sort(key=lambda s: s[1])
    return Trace(ops=ops, spans=spans, lines=lines)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged, a: float, b: float) -> float:
    """Length of [a, b) that sorted, disjoint ``merged`` intervals cover."""
    lo = bisect.bisect_right(merged, (a, float("inf"))) - 1
    total = 0.0
    for x, y in merged[max(lo, 0):]:
        if x >= b:
            break
        total += max(0.0, min(b, y) - max(a, x))
    return total


@dataclasses.dataclass
class Summary:
    """What the per-layer readers read: all times in seconds."""
    window_s: float
    busy_s: float                       # mean over the devices used
    steps: list[tuple[float, float]]    # per step span: (length, device busy in it)
    op_seconds: dict[str, float]        # device time by operation name
    gaps: dict[str, float]              # device idle time by host span around it


def summarize(trace: Trace, devices: int = 1) -> Summary:
    t0, t1 = trace.window()
    planes = trace.device_names()[:devices]
    if not planes:
        raise ValueError(f"trace holds no {OPS_LINE!r} line on a {DEVICE_PREFIX} "
                         f"plane; its planes and lines: {trace.lines}")
    merged = {p: union((a, b) for _, a, b in trace.ops[p]) for p in planes}
    busy = sum(covered(m, t0, t1) for m in merged.values()) / len(planes)
    first = merged[planes[0]]
    steps = [((b - a) / 1e9, covered(first, a, b) / 1e9)
             for name, a, b in trace.spans if name == "step" and t0 <= a and b <= t1]
    op_seconds: dict[str, float] = defaultdict(float)
    for p in planes:
        for name, a, b in trace.ops[p]:
            if t0 <= a < t1:
                op_seconds[name] += (b - a) / 1e9 / len(planes)
    gaps: dict[str, float] = defaultdict(float)
    host = [s for s in trace.spans if s[0] != "window"]     # sorted, disjoint
    edges = [t0] + [x for ab in first for x in ab] + [t1]
    i = 0
    for a, b in zip(edges[::2], edges[1::2]):
        a, b = max(a, t0), min(b, t1)
        if b <= a:
            continue
        # each part of the gap is named by the host span over it
        while i < len(host) and host[i][2] <= a:
            i += 1
        rest = b - a
        j = i
        while j < len(host) and host[j][1] < b:
            name, x, y = host[j]
            part = max(0.0, min(b, y) - max(a, x))
            gaps[name] += part / 1e9
            rest -= part
            j += 1
        if rest > 0:
            gaps["other"] += rest / 1e9
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=busy / 1e9, steps=steps,
                   op_seconds=dict(op_seconds), gaps=dict(gaps))


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
