"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Three things come out of a trace, per device: the intervals in which an
operation ran (``ops``), the executions of whole compiled programs
(``programs``), and from them the busy time, the union of the op
intervals within a window. The host's own annotations (``host``), which
the harness writes with ``jax.profiler.TraceAnnotation``, share the
trace's clock and mark the window and the steps.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "chipbench."


@dataclasses.dataclass
class Event:
    name: str            # for a device op: its HLO instruction's text
    start_ns: int
    end_ns: int

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]
    programs: Dict[int, List[Event]]
    host: List[Event]

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def host_spans(self, name: str) -> List[Event]:
        return [e for e in self.host if e.name == HOST_PREFIX + name]

    def window(self) -> Tuple[int, int]:
        """The traced window, as the harness marked it."""
        spans = self.host_spans("window")
        if len(spans) != 1:
            raise ValueError(f"expected one window span, found {len(spans)}")
        return spans[0].start_ns, spans[0].end_ns

    def busy_ns(self, device: int, lo: int, hi: int) -> int:
        return covered_ns(clip(self.ops[device], lo, hi))

    def ops_in(self, device: int, lo: int, hi: int) -> List[Event]:
        return [e for e in self.ops[device] if within(e, lo, hi)]

    def programs_in(self, device: int, lo: int, hi: int) -> List[Event]:
        return [e for e in self.programs[device] if within(e, lo, hi)]


def within(e: Event, lo: int, hi: int) -> bool:
    """An event belongs to the window its midpoint lies in: the device's
    clock and the host's, which marks the window, may differ by a little."""
    return lo <= (e.start_ns + e.end_ns) // 2 < hi


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    programs: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            ops[dev], programs[dev] = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [Event(e.name, int(e.start_ns), int(e.end_ns))
                                for e in line.events]
                elif line.name == PROGRAMS_LINE:
                    programs[dev] = [Event(e.name, int(e.start_ns),
                                           int(e.end_ns))
                                     for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    for d in (ops, programs):
        for evs in d.values():
            evs.sort(key=lambda e: e.start_ns)
    host.sort(key=lambda e: e.start_ns)
    if not ops:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    return Trace(ops, programs, host)


def find(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {paths}")
    return paths[0]


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def covered_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(t - s for s, t in merge(intervals))


def gaps(intervals: Sequence[Tuple[int, int]], lo: int,
         hi: int) -> List[Tuple[int, int]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, t in merge(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, t)
    if at < hi:
        out.append((at, hi))
    return [(s, t) for s, t in out if t > s]

