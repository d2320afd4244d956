"""Reduce a JAX profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX: the device planes' operations, the programs (XLA
modules) they ran in, and the host spans the benchmark wrote as
``jax.profiler.TraceAnnotation`` (all names that start with ``SPAN``).
Every time is in seconds on the trace's own clock, which host and device
events share.

The rest are plain functions of intervals: the union of busy time, a
kernel's device time, the idle gaps, and the host span each gap falls in.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    stats: Tuple[str, ...] = ()       # the names of the event's stats

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Profile:
    ops: Dict[str, List[Event]]       # device plane -> operations
    modules: Dict[str, List[Event]]   # device plane -> program executions
    spans: List[Event]                # the benchmark's host spans


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        out.append(Event(e.name, start, start + e.duration_ns * 1e-9,
                         tuple(k for k, _ in e.stats)))
    return out


def load(path: str) -> Profile:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                spans.extend(e for e in events if e.name.startswith(SPAN))
                if line.name.startswith(CPU_CLIENT_LINE):
                    cpu_ops.extend(e for e in events if "hlo_op" in e.stats)
    if not ops and cpu_ops:
        # the CPU backend runs its programs on host threads: with no device
        # plane (a rehearsal off the chip) those operations stand in
        ops["/host:CPU"] = cpu_ops
    spans.sort(key=lambda e: e.start)
    return Profile(ops=ops, modules=modules, spans=spans)


# -- intervals -----------------------------------------------------------------

def shift(events: Iterable[Event], dt: float) -> List[Event]:
    return [dataclasses.replace(e, start=e.start + dt, end=e.end + dt)
            for e in events]


def host_lag(modules: Sequence[Event], spans: Sequence[Event]
             ) -> Optional[float]:
    """How far the device clock runs behind the host's, from the block
    program's executions and the host spans that launched them, paired in
    order: the shift that puts the earliest-starting program at the start
    of its span (a program cannot start before it is launched).  None
    where the two do not pair up."""
    if not modules or len(modules) != len(spans):
        return None
    return max(s.start - m.start for m, s in zip(modules, spans))


def clip(events: Iterable[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """The union of intervals as sorted, disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge(intervals))


def busy_s(events: Iterable[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran."""
    return union_length(clip(events, lo, hi))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no operation ran."""
    gaps, cur = [], lo
    for a, b in merge(clip(events, lo, hi)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def op_name(event: Event) -> str:
    """An operation's own name: a device event is named by its HLO
    instruction (``%flash_attention.5 = f32[...] custom-call(...)``), so
    that is the text before `` = `` without its ``%`` and its ``.N``."""
    head = event.name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def is_container(event: Event) -> bool:
    """A loop or call whose body's operations are events of their own."""
    return any(f" {op}(" in event.name for op in ("while", "conditional",
                                                     "call"))


def matches(event: Event, names: Sequence[str]) -> bool:
    return op_name(event) in names


def kernel_s(events: Iterable[Event], names: Sequence[str], lo: float,
             hi: float) -> Tuple[float, int]:
    """Device seconds and count of the operations named by ``names``
    inside [lo, hi] (an operation is counted where it starts)."""
    total, n = 0.0, 0
    for e in events:
        if lo <= e.start < hi and matches(e, names):
            total += e.dur
            n += 1
    return total, n


class SpanIndex:
    """Finds the innermost host span that holds a time.  The spans of one
    thread nest, so among the spans that hold ``t`` the innermost is the
    one that started last."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda e: (e.start, -e.end))
        self.starts = [e.start for e in self.spans]
        self.reach, top = [], float("-inf")
        for e in self.spans:
            top = max(top, e.end)
            self.reach.append(top)

    def innermost(self, t: float) -> Optional[Event]:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] > t:
            if self.spans[i].end > t:
                return self.spans[i]
            i -= 1
        return None


def attribute_gaps(gaps: Iterable[Tuple[float, float]],
                   spans: Sequence[Event], outside: str = "outside spans"
                   ) -> Dict[str, float]:
    """Idle seconds by the innermost host span that holds each gap's
    midpoint (``outside`` where none does)."""
    index = SpanIndex(spans)
    out: Dict[str, float] = {}
    for a, b in gaps:
        s = index.innermost(0.5 * (a + b))
        name = s.name[len(SPAN):] if s is not None else outside
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top_ops(events: Iterable[Event], lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` operation names with the most device seconds (loops and
    calls left out: their bodies' operations are counted)."""
    by: Dict[str, float] = {}
    for e in events:
        if lo <= e.start < hi and not is_container(e):
            by[op_name(e)] = by.get(op_name(e), 0.0) + e.dur
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
