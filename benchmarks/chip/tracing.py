"""Reduction of a profiler trace to what the per-layer metrics read.

A trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) has one
plane per device, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds every
operation the device ran and whose line ``XLA Modules`` holds every
program, named ``jit_<function>(<fingerprint>)``; and a host plane,
``/host:CPU``, whose threads carry the harness's ``TraceAnnotation``
spans.  Both are on one clock, in nanoseconds.

``TraceView`` keeps, for the first device only: the operations, the
programs, the union of the busy intervals, and the host spans by name.
Everything here is plain data so that the reduction can be checked on a
small recorded trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]  # (start, end) in ns

MODULE = re.compile(r"^jit_(.*)\(\d+\)$")


def union(intervals) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: List[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` covered by the merged intervals."""
    if hi <= lo:
        return 0.0
    i = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` between busy ones."""
    out, cur = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def module_name(event_name: str) -> str:
    m = MODULE.match(event_name)
    return m.group(1) if m else event_name


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class TraceView:
    ops: List[Tuple[str, float, float]]       # (name, start, end)
    modules: List[Tuple[str, float, float]]   # (function, start, end)
    spans: Dict[str, List[Interval]]          # host spans by name, sorted
    busy: List[Interval] = dataclasses.field(init=False)

    def __post_init__(self):
        self.ops.sort(key=lambda o: o[1])
        self.modules.sort(key=lambda m: m[1])
        self.busy = union((s, e) for _, s, e in self.ops)
        self._starts = {k: [s for s, _ in v] for k, v in self.spans.items()}

    @property
    def window(self) -> Interval:
        w = self.spans.get("window")
        if not w:
            raise ValueError("the trace holds no 'window' span")
        return w[0]

    def busy_in(self, lo: float, hi: float) -> float:
        return covered(self.busy, lo, hi)

    def module_calls(self, function: str) -> List[Interval]:
        return [(s, e) for f, s, e in self.modules if f == function]

    def label_at(self, t: float, labels) -> str:
        """The innermost of ``labels`` (host span names, innermost first)
        whose span holds time ``t``; '' if none."""
        for name in labels:
            i = bisect.bisect_right(self._starts.get(name, []), t) - 1
            if i >= 0 and t < self.spans[name][i][1]:
                return name
        return ""

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device time by ``<program>:<op>``, most first, in seconds.  An
        op that holds the next one (a ``while`` around its body's ops) is
        a container, and only what it holds is counted."""
        starts = [s for _, s, _ in self.modules]
        total: Dict[str, float] = {}
        for k, (name, s, e) in enumerate(self.ops):
            if k + 1 < len(self.ops) and self.ops[k + 1][1] < e \
                    and self.ops[k + 1][2] <= e:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = self.modules[i][0] if i >= 0 and s < self.modules[i][2] \
                else "?"
            key = f"{mod}:{op_name(name)}"
            total[key] = total.get(key, 0.0) + (e - s) * 1e-9
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def load(path: str, span_names) -> TraceView:
    """Read an ``.xplane.pb`` file: the first TPU's ops and programs and
    the host spans named in ``span_names``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], {n: [] for n in span_names}
    devices = sorted(p.name for p in pd.planes
                     if p.name.startswith("/device:TPU:"))
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    for plane in pd.planes:
        if plane.name == devices[0]:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(module_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return TraceView(ops, modules, {k: sorted(v) for k, v in spans.items()})
