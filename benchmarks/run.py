"""Benchmark harness — one module per paper table/figure.

  python -m benchmarks.run [--only convergence,kernels,...] [--csv out.csv]

  bench_epoch_time   Fig. 1 (epoch time vs workers) + Fig. 2 (throughput)
  bench_convergence  Fig. 3 + Table 2 (PPL per algorithm at equal epochs)
  bench_kernels      fused AdaAlter update vs unfused lowering
  bench_sync_compression  int8+error-feedback sync vs fp32 payload
  bench_adaptive_sync     CADA-style adaptive sync policy vs fixed H=4
  bench_flat_step    flat parameter plane vs per-leaf hot path
  bench_trace_replay trace-driven what-if replay vs measured walls
  bench_roofline     §Roofline table from the dry-run artifacts

Every module is also runnable standalone with a uniform ``--out`` JSON path
defaulting to ``BENCH_<name>.json`` at the repo root; this harness writes
the same per-bench files (plus the merged CSV), so one ``benchmarks.run``
invocation refreshes the whole ``BENCH_*.json`` trajectory.
"""
from __future__ import annotations

import argparse
import csv
import io
import json
import os
import sys
import time

ALL = ["epoch_time", "convergence", "kernels", "sync_compression",
       "adaptive_sync", "flat_step", "trace_replay", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help=f"comma-separated subset of {ALL}")
    ap.add_argument("--csv", default="", help="also write rows to this CSV")
    ap.add_argument("--json-dir", default=".",
                    help="write per-bench rows as BENCH_<name>.json here "
                         "('' disables)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller step counts (CI mode)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    which = [w for w in (args.only.split(",") if args.only else ALL) if w]

    rows = []
    for name in which:
        t0 = time.time()
        print(f"== bench_{name}", flush=True)
        if name == "epoch_time":
            from benchmarks.bench_epoch_time import run as r
            new = r()
        elif name == "convergence":
            from benchmarks.bench_convergence import run as r
            new = r(steps=30 if args.quick else 120)
        elif name == "kernels":
            from benchmarks.bench_kernels import run as r
            new = r(n=(1 << 18) if args.quick else (1 << 22))
        elif name == "sync_compression":
            from benchmarks.bench_sync_compression import run as r
            new = r(steps=60 if args.quick else 200,
                    n=(1 << 18) if args.quick else (1 << 22))
        elif name == "adaptive_sync":
            from benchmarks.bench_adaptive_sync import run as r
            new = r(steps=60 if args.quick else 120)
        elif name == "flat_step":
            from benchmarks.bench_flat_step import run as r
            new = r(steps=12 if args.quick else 30)
        elif name == "trace_replay":
            from benchmarks.bench_trace_replay import run as r
            # traces land next to the BENCH json so the paths its rows
            # reference survive as artifacts
            new = r(steps=24 if args.quick else 40,
                    trace_dir=args.json_dir or ".")
        elif name == "roofline":
            from benchmarks.bench_roofline import run as r
            new = r()
        else:
            print(f"   unknown bench {name!r}", file=sys.stderr)
            continue
        rows += new
        if args.json_dir:
            # the artifact name is the module's contract (DEFAULT_OUT where
            # it differs from the BENCH_<name>.json convention), so the
            # harness can never drift from the standalone CLI
            import importlib
            mod = importlib.import_module(f"benchmarks.bench_{name}")
            os.makedirs(args.json_dir, exist_ok=True)
            out = os.path.join(args.json_dir,
                               getattr(mod, "DEFAULT_OUT",
                                       f"BENCH_{name}.json"))
            with open(out, "w") as f:
                json.dump(new, f, indent=1)
        print(f"   done in {time.time() - t0:.1f}s ({len(rows)} rows total)",
              flush=True)

    # union of keys, stable order
    keys = []
    for row in rows:
        for k in row:
            if k not in keys:
                keys.append(k)
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=keys)
    w.writeheader()
    w.writerows(rows)
    print(buf.getvalue())
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(buf.getvalue())
        print(f"wrote {args.csv}")


if __name__ == "__main__":
    main()
