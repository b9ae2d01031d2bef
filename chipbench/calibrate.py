"""Readings that set a cell's correctness limits (``limits/<cell>.json``).

  python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
      [--control-seeds a,b,c] [--faults half_batch,no_exchange] \
      [--fault-seeds a,b,c] [--out readings.jsonl]

For each seed, in one process: the program's first sync window through
``train_loop`` (no measured window), the float32 reference on the same
seed and batches, and the four numbers of ``check.compare``. With
``--control-seeds`` the control, the reference computed with float8
products (``precision.fp8``), is put in the program's place and compared
the same way; with ``--faults`` the program runs with each planted fault
(``check.FAULTS``). One JSON line per reading, with each leaf's norms on
both sides (``per_leaf``) for reading a number by hand. The benchmark's own runs
never run this; the tests drive the same ``program``, ``reference`` and
``control`` at a reduced size.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def program(cell, seed: int, fault=None):
    """The timed path's readings of steps 1..H, and the batches it trained."""
    from chipbench.harness import Session
    s = Session(cell, seed, 0.0, fault=fault, warm_only=True)
    s.run()
    prog = {"loss": s.program_losses()[:cell.H], "leaves": s.leaves,
            **s.readings}
    batches = s.batches
    del s
    gc.collect()
    return prog, batches


def reference(cell, devices, seed: int, batches):
    from chipbench.reference import Reference
    return Reference(cell.job(), list(devices)).run(seed, batches)


def control(cell, devices, seed: int, batches):
    """The control: the reference with float8 products, in the program's
    place."""
    from chipbench import precision
    from chipbench.reference import Reference
    return Reference(cell.job(), list(devices), mm=precision.fp8).run(
        seed, batches)


def per_leaf(prog, ref) -> dict:
    """Each leaf's norms on both sides, for reading a number by hand."""
    import numpy as np
    row = {"loss": {"program": np.asarray(prog["loss"]).tolist(),
                    "reference": np.asarray(ref["loss"]).tolist()},
           "leaves": list(ref["leaves"]),
           "grad_true": np.asarray(ref["grad_true"]).tolist()}
    for k in ("grad", "update", "sync"):
        row[k] = {"program": np.asarray(prog[k]).tolist(),
                  "reference": np.asarray(ref[k]).tolist()}
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import check
    from chipbench.harness import Cell

    cell = Cell.load(args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibration needs the cell's chips", file=sys.stderr)
        return 2
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    controls = set(_seeds(args.control_seeds))
    faults = [f for f in args.faults.split(",") if f]
    fault_seeds = _seeds(args.fault_seeds)
    for seed in sorted(set(_seeds(args.seeds)) | controls | set(fault_seeds)):
        t0 = time.perf_counter()
        prog, batches = program(cell, seed)
        ref = reference(cell, devices, seed, batches)
        emit({"cell": cell.name, "seed": seed, "what": "program",
              **check.compare(prog, ref, diagnostics=True),
              "s": time.perf_counter() - t0, "per_leaf": per_leaf(prog, ref)})
        if seed in controls:
            ctl = control(cell, devices, seed, batches)
            emit({"cell": cell.name, "seed": seed, "what": "control_fp8",
                  **check.compare(ctl, ref, diagnostics=True),
                  "per_leaf": per_leaf(ctl, ref)})
        if seed in fault_seeds:
            for f in faults:
                fp, _ = program(cell, seed, fault=f)
                emit({"cell": cell.name, "seed": seed, "what": f,
                      **check.compare(fp, ref, diagnostics=True),
                      "per_leaf": per_leaf(fp, ref)})
        del ref
        gc.collect()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
