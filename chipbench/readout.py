"""What a per-layer metric reads: the traced sub-window of one run.

``Readout`` joins the profiler trace (``trace.py``) with the harness's own
record of the same steps: which step calls fell in the traced window, what
kind each was, and how many tokens it trained. Every reader in
``metrics/<name>.py`` is a function ``read(readout) -> float | None``; one
that finds nothing to read returns None and its metric is left out.
"""
from __future__ import annotations

import collections
import importlib.util
import re
import statistics
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import trace as tr
from chipbench import work

METRICS_DIR = Path(__file__).resolve().parent / "metrics"
_NAME = re.compile(r"^%?([^ =]+) = ")
_SHAPE = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")


def op_name(event: tr.Event) -> str:
    """HLO instruction name of a trace op ('fusion.164', 'all-reduce.3',
    'flat_fused_update.1')."""
    m = _NAME.match(event.name)
    return m.group(1) if m else event.name


def op_base(event: tr.Event) -> str:
    """The instruction name without its '.N' suffix: a Pallas kernel's own
    name ('flat_fused_update'), or the HLO opcode of a plain op."""
    return re.sub(r"\.\d+$", "", op_name(event))


def op_label(event: tr.Event) -> str:
    m = _SHAPE.search(event.name)
    return op_name(event) + (" " + m.group(1) if m else "")


def top_level(events: List[tr.Event]) -> List[tr.Event]:
    """Events not nested inside an earlier one (the body ops of a loop lie
    inside the loop's own event)."""
    out, end = [], -1
    for e in events:
        if e.start_ns >= end:
            out.append(e)
            end = e.end_ns
        elif e.end_ns > end:
            out.append(e)
            end = e.end_ns
    return out


class Readout:
    def __init__(self, trace: tr.Trace, kinds: List[str], tokens: List[int],
                 batch_ms: List[float], interval_ms: List[float],
                 model: dict, traffic: dict, chips: int, device_kind: str):
        self.trace = trace
        self.lo, self.hi = trace.window()
        self.devices = trace.devices[:chips]
        if len(self.devices) != chips:
            raise ValueError(f"trace holds {len(trace.devices)} devices, "
                             f"the cell uses {chips}")
        self.kinds, self.tokens = kinds, tokens
        self.batch_ms = batch_ms
        self.interval_ms = interval_ms     # the whole window's step intervals
        self.model, self.traffic = model, traffic
        self.chips = chips
        self.workers = int(traffic["workers"])
        self.peaks = work.peaks(device_kind)
        self.flops_per_token = work.flops_per_token(model)
        self.programs = {d: self._programs(d) for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _programs(self, dev: int) -> List[Tuple[str, tr.Event]]:
        """The step programs that ran in the window, each with the kind of
        step call that launched it (the n-th program is the n-th call)."""
        progs = self.trace.programs_in(dev, self.lo, self.hi)
        if len(progs) != len(self.kinds):
            raise ValueError(f"device {dev}: {len(progs)} programs in the "
                             f"traced window for {len(self.kinds)} steps")
        return list(zip(self.kinds, progs))

    def spans_ms(self, kind: str) -> List[float]:
        return [e.dur_ns / 1e6 for d in self.devices
                for k, e in self.programs[d] if k == kind]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        return statistics.fmean(self.trace.busy_ns(d, self.lo, self.hi)
                                for d in self.devices) / 1e9

    def ops(self, dev: int, pred: Callable[[tr.Event], bool]
            ) -> List[tr.Event]:
        return [e for e in self.trace.ops_in(dev, self.lo, self.hi)
                if pred(e)]

    def kernel_ms(self, name: str) -> Optional[float]:
        """Mean device time of one launch of the named kernel, or None
        when it did not run in the window."""
        times = [e.dur_ns for d in self.devices
                 for e in self.ops(d, lambda e: op_base(e) == name)]
        return statistics.fmean(times) / 1e6 if times else None

    def n_calls(self, kind: str) -> int:
        return sum(1 for k in self.kinds if k == kind)

    def roofline(self, kernel: str, bytes_per_launch: float,
                 flops_per_launch: float = 0.0) -> Optional[float]:
        """Share (%) of the least time the chip needs for one launch's work
        over the launch's measured time."""
        ms = self.kernel_ms(kernel)
        if ms is None:
            return None
        least = max(bytes_per_launch / self.peaks["hbm_bytes_per_s"],
                    flops_per_launch / self.peaks["bf16_flops_per_s"])
        return 100.0 * least / (ms / 1e3)

    # -- the breakdown the ledger keeps ----------------------------------- #
    def breakdown(self, n: int = 10) -> dict:
        dev = self.devices[0]
        by_op: Dict[str, float] = collections.Counter()
        for e in top_level(self.trace.ops_in(dev, self.lo, self.hi)):
            by_op[op_label(e)] += e.dur_ns / 1e9
        ops = [[k, v] for k, v in by_op.most_common(n)]
        busy = tr.clip(self.trace.ops[dev], self.lo, self.hi)
        host = [(e.name[len(tr.HOST_PREFIX):], e.start_ns, e.end_ns)
                for e in self.trace.host if e.name != tr.HOST_PREFIX + "window"]
        gaps = []
        for s, t in tr.gaps(busy, self.lo, self.hi):
            cover = collections.Counter()
            for name, a, b in host:
                if b > s and a < t:
                    cover[name] += min(b, t) - max(a, s)
            label = (cover.most_common(1)[0][0] if cover
                     else "host loop (loss read, transfer)")
            gaps.append([label, (t - s) / 1e9])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": ops, "idle_gaps": gaps[:n]}


def reader(name: str):
    """The reader of one per-layer metric, found by the metric's name."""
    path = METRICS_DIR / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

