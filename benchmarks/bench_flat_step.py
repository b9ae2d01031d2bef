"""Flat parameter plane vs per-leaf hot path: launches, padding, collectives.

Four measurements for ``OptimizerConfig.flat`` (core/flatspace.py) on the
paper's Big LSTM config:

  launches     Pallas kernel launches per compiled step, counted directly
               in the traced jaxpr: the per-leaf path pays one
               ``pallas_call`` per parameter leaf for the AdaAlter update
               (plus one per payload leaf for the fused EF sync encode on
               sync steps); the flat plane pays ONE of each — the L -> 1
               claim of the ISSUE, measured, not asserted;
  padding      pad-to-tile elements: the per-leaf path re-pads every leaf
               to the kernel tile on EVERY launch, the plane pays its slot
               padding once at pack time;
  collectives  sync-round collectives (per-leaf: one small all-reduce per
               payload leaf; flat: ONE flat wire array) and the alpha-beta
               ``comm.collective_time`` launch/latency model at paper scale;
  wall         measured wall time per train step of the jnp fallback path
               (use_pallas=False — interpret-mode Pallas timing tracks
               emulation overhead, not dispatch cost) for both layouts on
               the reduced config, plus their final losses (the two paths
               are bitwise identical in state; tests/test_flat_step.py).

  sharded      the same flat step on a 4-device (2 workers x 2-way shard)
               CPU mesh (subprocess — the forced host-device count must
               not perturb the single-device sections): kernel launches
               sharded vs replicated, and per-device plane bytes, which
               ~halve under 2-way sharding.

  PYTHONPATH=src python -m benchmarks.bench_flat_step \
      [--steps 20] [--out BENCH_flat_step.json]
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.core import comm
from repro.core.flatspace import FlatSpace
from repro.data import SyntheticLM, make_train_batch
from repro.kernels.quantize import TILE_BLOCKS
from repro.kernels.tiling import padded_size
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs
from repro.models.counting import count_params


def count_pallas_calls(jaxpr) -> int:
    """Recursively count ``pallas_call`` eqns in a (closed) jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                n += count_pallas_calls(v)
    return n


def _mk_opt(flat: bool, use_pallas: bool) -> OptimizerConfig:
    return OptimizerConfig.from_sync(
        SyncConfig(compression="int8", fused=True),
        name="local_adaalter", lr=0.5, H=4, warmup_steps=10,
        use_pallas=use_pallas, flat=flat)


def run(steps: int = 20, seq: int = 64, batch: int = 8) -> List[Dict]:
    rows = []
    cfg = reduced(get_arch("biglstm"), vocab=512)
    shape = ShapeConfig(name="bench", seq_len=seq, global_batch=batch,
                        kind="train")
    mesh = worker_mesh()
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer="local_adaalter")

        # ---- kernel launches per compiled step (traced, not modeled) ---- #
        launches = {}
        programs = {}
        for mode, flat in (("per_leaf", False), ("flat", True)):
            p = build_train_programs(cfg, shape, _mk_opt(flat, True), mesh,
                                     plan)
            programs[mode] = p
            state_abs = jax.eval_shape(p.init_fn, jax.random.PRNGKey(0))
            from repro.launch.steps import train_batch_specs
            batch_abs = train_batch_specs(cfg, shape, p.n_workers)
            launches[mode] = {
                v: count_pallas_calls(jax.make_jaxpr(
                    lambda a, b, c, fn=fn: fn(a, b, c))(
                        *state_abs, batch_abs))
                for v, fn in (("local_step", p.local_step),
                              ("sync_step", p.sync_step))}
        fs: FlatSpace = programs["flat"].flatspace
        rows.append({
            "bench": "flat_step(launches)",
            "n_param_leaves": fs.n_leaves,
            "per_leaf": launches["per_leaf"],
            "flat": launches["flat"],
            "local_step_shrink": (launches["per_leaf"]["local_step"]
                                  / max(launches["flat"]["local_step"], 1)),
        })

        # ---- padded elements: per launch (per-leaf) vs once (flat) ------ #
        upd_pad_per_step = sum(s.padded - s.size for s in fs.slots)
        sync_block = 256
        # per-leaf fused EF: each payload leaf padded to the quantization
        # block, then its row count to the kernel tile — every sync round
        per_leaf_sync_pad = sum(
            padded_size(padded_size(s.size, sync_block) // sync_block,
                        TILE_BLOCKS) * sync_block - s.size
            for s in fs.slots) * 2                       # params + B²
        flat_sync_pad = (padded_size(2 * fs.plane_size // sync_block,
                                     TILE_BLOCKS) * sync_block
                         - 2 * fs.n_real)
        rows.append({
            "bench": "flat_step(padding)",
            "real_elems": fs.n_real,
            "per_leaf_update_pad_elems_per_step": upd_pad_per_step,
            "flat_plane_pad_elems_once": fs.pad_elems,
            "per_leaf_sync_pad_elems_per_round": per_leaf_sync_pad,
            "flat_sync_pad_elems_per_round": flat_sync_pad,
            "note": "per-leaf pays its pads on EVERY launch; the plane "
                    "pays slot padding once at pack time",
        })

        # ---- collectives per sync round + alpha-beta time at paper scale - #
        n_params = count_params(get_arch("biglstm"))
        round_bytes = comm.sync_payload_bytes("local_adaalter", n_params,
                                              compression="int8")
        n_coll = int(fs.n_leaves
                     * comm.sync_round_multiplier("local_adaalter"))
        workers = 8                                     # paper's cluster
        t_leaf = comm.collective_time(round_bytes, n_coll, workers)
        t_flat = comm.collective_time(round_bytes, 1, workers)
        rows.append({
            "bench": "flat_step(collectives)",
            "collectives_per_round_per_leaf": n_coll,
            "collectives_per_round_flat": 1,
            "round_mb": round(round_bytes / 1e6, 2),
            "alpha_beta_per_leaf_ms": round(t_leaf * 1e3, 4),
            "alpha_beta_flat_ms": round(t_flat * 1e3, 4),
            "latency_overhead_shrink": round(t_leaf / t_flat, 2),
        })

        # ---- measured wall time, jnp fallback path ---------------------- #
        walls = {}
        finals = {}
        for mode, flat in (("per_leaf", False), ("flat", True)):
            p = build_train_programs(cfg, shape, _mk_opt(flat, False), mesh,
                                     plan)
            R = p.n_workers
            ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                             n_workers=R, seed=0, non_iid=True)
            params, state = p.init_fn(jax.random.PRNGKey(0))
            batches = [jax.tree_util.tree_map(
                jnp.asarray, make_train_batch(cfg, shape, ds, s,
                                              n_workers=R))
                for s in range(steps)]
            loss = None
            for s in range(2):                          # warmup/compile
                fn = p.sync_step if (s + 1) % 4 == 0 else p.local_step
                params, state, m = fn(params, state, batches[s])
            jax.block_until_ready(params)
            t0 = time.perf_counter()
            for s in range(2, steps):
                fn = p.sync_step if (s + 1) % 4 == 0 else p.local_step
                params, state, m = fn(params, state, batches[s])
                loss = m["loss"]
            jax.block_until_ready(params)
            walls[mode] = (time.perf_counter() - t0) / max(steps - 2, 1)
            finals[mode] = float(loss)
            rows.append({
                "bench": "flat_step(wall)",
                "mode": mode, "steps": steps - 2,
                "ms_per_step": round(walls[mode] * 1e3, 2),
                "final_loss": round(finals[mode], 5),
            })
        rows[-1]["speedup_vs_per_leaf"] = round(
            walls["per_leaf"] / walls["flat"], 3)
    rows.extend(run_sharded())
    return rows


_SHARDED_SCRIPT = r"""
import dataclasses, json
import jax
from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs, train_batch_specs
from benchmarks.bench_flat_step import count_pallas_calls, _mk_opt

cfg = reduced(get_arch("biglstm"), vocab=512)
shape = ShapeConfig(name="bench", seq_len=64, global_batch=8, kind="train")
mesh = worker_mesh(2)
out = {}
with mesh:
    plan = resolve_plan(cfg, mesh, optimizer="local_adaalter")
    for mode, pl in (("sharded", plan),
                     ("replicated", dataclasses.replace(plan, tp_axis=""))):
        p = build_train_programs(cfg, shape, _mk_opt(True, True), mesh, pl)
        state_abs = jax.eval_shape(p.init_fn, jax.random.PRNGKey(0))
        batch_abs = train_batch_specs(cfg, shape, p.n_workers)
        fs = p.flatspace
        plane, _ = p.init_fn(jax.random.PRNGKey(0))
        shard = plane.sharding.shard_shape(plane.shape)
        out[mode] = {
            "n_shards": p.n_shards,
            "launches": {v: count_pallas_calls(jax.make_jaxpr(
                lambda a, b, c, fn=fn: fn(a, b, c))(*state_abs, batch_abs))
                for v, fn in (("local_step", p.local_step),
                              ("sync_step", p.sync_step))},
            "plane_size": fs.plane_size,
            "per_device_plane_bytes": 4 * shard[0] * shard[1],
        }
print("BENCH-SHARDED " + json.dumps(out))
"""


def run_sharded() -> List[Dict]:
    """Sharded-flat vs replicated-flat on a (2 workers x 2-way) mesh.

    Runs in a subprocess: the XLA host-device count must be forced to 4
    BEFORE the backend initialises, and doing so here would perturb the
    single-device numbers of the sections above."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [repo, os.path.join(repo, "src")])}
    try:
        proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                              env=env, capture_output=True, text=True,
                              timeout=900)
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith("BENCH-SHARDED "))
        data = json.loads(line[len("BENCH-SHARDED "):])
    except Exception as e:                       # keep the bench usable
        return [{"bench": "flat_step(sharded)",
                 "note": f"4-device subprocess failed: {e!r}"}]
    sh, re_ = data["sharded"], data["replicated"]
    return [{
        "bench": "flat_step(sharded)",
        "mesh": "2 workers x 2 shards",
        "n_shards": sh["n_shards"],
        "launches_sharded": sh["launches"],
        "launches_replicated": re_["launches"],
        "per_device_plane_bytes_sharded": sh["per_device_plane_bytes"],
        "per_device_plane_bytes_replicated": re_["per_device_plane_bytes"],
        "per_device_bytes_shrink": round(
            re_["per_device_plane_bytes"] / sh["per_device_plane_bytes"], 3),
        "note": "per-device bytes ~halve under 2-way sharding (tail pad "
                "rounds the plane to shards*ALIGN, so not exactly 2x)",
    }]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=20,
                    help="wall-time section train steps")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--out", default="BENCH_flat_step.json",
                    help="write rows as JSON here ('' skips)")
    args = ap.parse_args()
    rows = run(steps=args.steps, seq=args.seq, batch=args.batch)
    from benchmarks._cli import emit
    emit(rows, args.out)


if __name__ == "__main__":
    main()
