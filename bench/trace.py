"""Reduce one profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy time and idle share of a window, each kernel's device
time and launches, the longest idle gaps with what the host was doing.

A TPU trace has one plane per chip named `/device:TPU:<k>`; its line
"XLA Ops" holds one event per executed HLO op, named by the op's HLO text
(`%ga_epoch_kernel.1 = (u32[1,16,20,256]{...}, ...) custom-call(...)`).
A Pallas kernel's op carries the kernel's name.  Control-flow ops (a
`while` around a scan) enclose the ops of their body on the same line, so
busy time is the union of intervals and an op's self time excludes the
events nested inside it.  Host threads are lines of the plane `/host:CPU`;
their events share the device planes' clock.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")
_SHAPE = re.compile(r"^%?[^=]*=\s*\(?\s*[a-z]+\d*\[([\d,]*)\]")

Interval = Tuple[float, float]      # (start_ns, end_ns)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """What the reduction keeps of a trace: device ops per chip and host
    events per thread, as plain intervals."""

    device_ops: Dict[int, List[Event]]
    host: Dict[str, List[Event]]


def op_name(hlo_text: str) -> str:
    """`%ga_epoch_kernel.1 = (...)` -> `ga_epoch_kernel`."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def first_output_elements(hlo_text: str) -> Optional[int]:
    """Element count of an op's first output, from its HLO text."""
    m = _SHAPE.match(hlo_text)
    if not m:
        return None
    dims = [int(d) for d in m.group(1).split(",") if d]
    return math.prod(dims)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    device_ops: Dict[int, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(m.group(1))] = sorted(
                        (Event(e.name, e.start_ns, e.end_ns)
                         for e in line.events), key=lambda e: e.start_ns)
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                host[f"{line.name}#{i}"] = [
                    Event(e.name, e.start_ns, e.end_ns) for e in line.events]
    return Trace(device_ops=device_ops, host=host)


def clip(events: Sequence[Event], window: Interval) -> List[Event]:
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(events: Sequence[Event], window: Interval) -> float:
    return sum(t - s for s, t in union(
        [(e.start_ns, e.end_ns) for e in clip(events, window)]))


def idle_gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    """The idle intervals of one chip inside the window."""
    gaps, cursor = [], window[0]
    for s, t in union([(e.start_ns, e.end_ns)
                       for e in clip(events, window)]):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, t)
    if window[1] > cursor:
        gaps.append((cursor, window[1]))
    return gaps


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time (ns) per op name: an op's duration less that of the ops
    nested inside it on the same line."""
    totals: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [end_ns, name, child_ns]

    def pop():
        end, name, child, dur = stack.pop()
        totals[name] += dur - child

    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            pop()
        if stack:
            stack[-1][2] += e.dur_ns
        stack.append([e.end_ns, op_name(e.name), 0.0, e.dur_ns])
    while stack:
        pop()
    return dict(totals)


def host_activity(trace: Trace, gap: Interval,
                  skip: Sequence[str] = ()) -> str:
    """The host event that best explains a device gap: the shortest host
    event covering at least half of it, else the one overlapping it most."""
    lo, hi = gap
    span = hi - lo
    best, best_key = None, None
    for events in trace.host.values():
        for e in events:
            if e.name in skip:
                continue
            ov = min(e.end_ns, hi) - max(e.start_ns, lo)
            if ov <= 0:
                continue
            covers = ov >= 0.5 * span
            key = (covers, -e.dur_ns if covers else ov)
            if best_key is None or key > best_key:
                best, best_key = e.name, key
    return best if best is not None else "no host event"


def host_span(trace: Trace, name: str) -> Optional[Interval]:
    """(start, end) of the first host event with this name."""
    for events in trace.host.values():
        for e in events:
            if e.name == name:
                return (e.start_ns, e.end_ns)
    return None


@dataclasses.dataclass
class Reduction:
    window: Interval
    busy_ns: float                       # mean over chips
    ops: List[Event]                     # every chip's ops inside the window
    device_ops: List[Tuple[str, float]]  # (name, self seconds), top first
    gaps: List[Tuple[str, float]]        # (host activity, seconds), longest

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def kernel(self, name: str) -> List[Event]:
        """The launches of one kernel that lie wholly inside the window."""
        lo, hi = self.window
        return [e for e in self.ops if op_name(e.name) == name
                and e.start_ns >= lo and e.end_ns <= hi]


def reduce(trace: Trace, window: Interval, chips: Sequence[int],
           top: int = 10, skip_host: Sequence[str] = ()) -> Reduction:
    """Everything the per-layer metrics read from one traced window."""
    missing = [c for c in chips if c not in trace.device_ops]
    if missing:
        raise ValueError(f"trace has no '{OPS_LINE}' line for chips "
                         f"{missing}; planes hold {sorted(trace.device_ops)}")
    inside = {c: [e for e in trace.device_ops[c]
                  if e.end_ns > window[0] and e.start_ns < window[1]]
              for c in chips}
    per_chip = {c: clip(ev, window) for c, ev in inside.items()}
    busy = sum(busy_ns(ev, window) for ev in per_chip.values()) / len(chips)
    selfs: Dict[str, float] = defaultdict(float)
    for ev in per_chip.values():
        for name, ns in self_times(ev).items():
            selfs[name] += ns
    ops = sorted(((n, ns / 1e9) for n, ns in selfs.items()),
                 key=lambda x: -x[1])[:top]
    gaps = sorted(idle_gaps(per_chip[chips[0]], window),
                  key=lambda g: g[0] - g[1])[:top]
    labelled = [(host_activity(trace, g, skip_host), (g[1] - g[0]) / 1e9)
                for g in gaps]
    return Reduction(window=window, busy_ns=busy,
                     ops=[e for c in chips for e in inside[c]],
                     device_ops=ops, gaps=labelled)
