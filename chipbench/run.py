"""Benchmark entry point: one run of one cell on the chips of this host.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) is a model configuration
(``configs/<config>.json``) trained under a job (``traffic/<traffic>.json``)
through ``repro.launch.train.train_loop``. The run builds everything from
the seed, warms the cell's two step programs up, measures for ``seconds``,
then checks what the warm-up steps produced against the float32 reference
(``check.py``). The last line of stdout is one JSON object; the numbers
compared are also the last lines of stderr. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache lives at ``.jax_cache/`` in the
checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileCount:
    """Programs compiled, or loaded from the persistent cache, from JAX's
    own monitoring events (one event covers both)."""

    def __init__(self):
        from jax import monitoring
        self.events = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration_secs))

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t, _ in self.events if lo <= t <= hi)


class GcPauses:
    """Python's garbage collections, each with its start and length."""

    def __init__(self):
        self.done = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.done.append((self._t0, time.perf_counter() - self._t0,
                              info["generation"]))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def between(self, lo: float, hi: float) -> str:
        ds = [(d, g) for t, d, g in self.done if lo <= t <= hi]
        if not ds:
            return "no garbage collection"
        d, g = max(ds)
        return (f"{len(ds)} garbage collections, {sum(x for x, _ in ds):.3f} "
                f"s in all, the longest {d * 1e3:.1f} ms (generation {g})")


def device_info(devices, used: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": used}


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float, fault=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line.

    ``fault`` (``check.FAULTS``) breaks the timed path underneath: it is
    for the tests only."""
    import jax
    import numpy as np

    from chipbench import check
    from chipbench.harness import Session
    from chipbench.reference import Reference

    compiles = CompileCount()
    pauses = GcPauses()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        s = Session(cell, seed, seconds, trace_dir=trace_dir, fault=fault)
        s.run()
        print(f"set-up: programs built {s.t_built - t_start:.2f} s, step 1 "
              f"{s.calls[0].t - t_start:.2f} s, window opens "
              f"{s.t_open - t_start:.2f} s; {compiles.between(0, s.t_open)} "
              f"programs compiled or loaded; in the window "
              f"{pauses.between(s.t_open, s.t_close)}",
              file=sys.stderr)
        window = s.t_close - s.t_open
        used = devices[:cell.chips]
        stats = [d.memory_stats() or {} for d in used]
        peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
        dev = {**device_info(devices, cell.chips), "memory_peak_bytes": peak}
        steps = s.window_calls()
        losses = s.program_losses()
        out = {"attempted": len(steps),
               "failed": int(np.sum(~np.isfinite(losses[cell.H:])))}
        in_window = compiles.between(s.t_open, s.t_close)
        if trace:
            metrics, extra = per_layer(cell, s, trace_dir, devices[0])
            dev.update(extra.pop("device"))
            out.update(extra)
        else:
            iv = s.intervals_s()
            metrics = {
                "tokens_per_s": sum(c.tokens for c in steps) / window
                / cell.chips,
                "step_ms_p95": float(np.percentile(iv, 95)) * 1e3,
                "setup_s": s.t_open - t_start,
            }
            # a metric whose runs do not repeat in a cell is that cell's
            # per-layer metric instead (BENCHMARK.json's ``workloads``)
            metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
            k = int(np.argmax(iv))
            print(f"window: {len(iv)} steps in {window:.3f} s, median "
                  f"{np.median(iv) * 1e3:.2f} ms, longest {iv[k] * 1e3:.2f} ms "
                  f"(step {k + 1})", file=sys.stderr)
        units = {m["name"]: m["unit"]
                 for m in cell.end_to_end + cell.per_layer}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items() if v is not None}
        out["device"] = dev

        # the program's state is gone with train_loop's frame; the
        # reference runs after the peak was read, in the memory freed
        prog = {"loss": losses[:cell.H], "leaves": s.leaves, **s.readings}
        batches = s.batches
        del s
        gc.collect()
        used = list(devices[:cell.chips])
        ref = Reference(cell.job(), used).run(seed, batches)
        numbers = check.compare(prog, ref)
    finally:
        pauses.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    correct = check.verdict(numbers, cell.limits) and out["failed"] == 0 \
        and in_window == 0
    result = {"correct": correct, **out,
              "checks": {**check.report(numbers, cell.limits),
                         "window_compiles": {"value": in_window,
                                             "limit": 0}}}
    return result


def per_layer(cell, s, trace_dir: str, device):
    """The cell's per-layer metrics from the traced sub-window."""
    from chipbench import trace as tr
    from chipbench.readout import Readout, reader

    calls = s.calls[s.trace_calls.start:s.trace_calls.stop]
    window = s.calls[cell.H:]
    batch_ms = [1e3 * b for b in s.batch_s[cell.H:cell.H + len(window)]]
    r = Readout(tr.load(tr.find(trace_dir)), [c.kind for c in calls],
                [c.tokens for c in calls], batch_ms,
                [1e3 * x for x in s.intervals_s()], cell.model,
                cell.traffic, cell.chips, device.device_kind)
    metrics = {m["name"]: reader(m["name"])(r) for m in cell.per_layer}
    extra = {"device": {"busy_s": r.busy_s(), "window_s": r.window_s},
             "breakdown": r.breakdown()}
    return metrics, extra


def main(argv=None) -> int:
    args = parse_args(argv)
    # the checkout's own cache, at a fixed path, whatever the environment
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench.harness import Cell
    from repro.launch.compile_cache import enable_compile_cache

    cell = Cell.load(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"{cell.name}: {device_info(devices, cell.chips)}", file=sys.stderr)
    enable_compile_cache()
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices, T_START)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
