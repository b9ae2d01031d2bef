"""End-to-end training driver over a mesh of all the devices JAX sees.

Trains any architecture config (a ``--reduced`` variant on the CPU; the
published widths on a TPU chip, see ``chip_smoke.py``) with any of the
paper's optimizers on the synthetic non-IID LM stream, logging loss/PPL and
the communication volume each algorithm actually moved.

The whole sync round is owned by one ``SyncEngine``
(``core/sync_engine.py``) composing the schedule, the wire format, and the
device-side encode: ``--sync-policy fixed_h`` is the paper's every-H-steps
schedule (bit-identical to the historical modulo loop, including across
checkpoint restores), ``--sync-policy adaptive`` triggers the sync round on
the accumulated divergence statistic the compiled steps emit (CADA-style,
``--drift-metric update_norm|grad_staleness``), bounded by
``--h-min``/``--h-max``. ``--compress bf16`` halves the payload,
``--compress int8`` shrinks it ~4x with error feedback — fused into a
single-HBM-pass Pallas kernel unless ``--unfused-sync``. Checkpoints carry
the engine's ``SyncState`` (drift accumulator + window position) next to
``(params, opt_state)``, so a mid-window restore resumes the exact adaptive
schedule. ``TrainResult`` reports the *measured* sync count/steps and the
comm bytes they moved, not the static ``2P/H`` formula. The loop's host
spans (``train.step`` and its children) land in any ``jax.profiler`` trace
of a run, beside the step programs ``jit_local_step``/``jit_sync_step``
and their named scopes (``repro.trace.scopes``). ``--trace out.json``
additionally records the run as a per-worker span timeline (``repro.trace``)
— the engine's actual sync decisions plus modeled device/wire round costs —
for Perfetto viewing and trace-driven what-if replay. ``--metrics out.jsonl``
streams per-step sync-health metrics (``repro.obs``: grad norm, drift, B²
quantiles, EF residual norms, int8 quantization MSE, wire compression
ratio) as JSONL plus a Prometheus textfile snapshot.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
      --optimizer local_adaalter --H 4 --steps 200 --batch 16 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch biglstm --reduced \
      --optimizer local_adaalter --sync-policy adaptive --sync-threshold \
      0.05 --h-min 2 --h-max 16 --compress bf16 --steps 200
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCHS, OptimizerConfig, ShapeConfig, get_arch,
                           get_shape, reduced)
from repro.configs.base import ModelConfig, ParallelismPlan, TrainConfig
from repro.core import comm
from repro.core.codecs import CODEC_NAMES
from repro.core.sync_engine import DRIFT_METRICS, make_sync_engine
from repro.core.sync_policy import POLICY_NAMES
from repro.data import SyntheticLM, make_train_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs
from repro.models.counting import count_params


@dataclasses.dataclass
class TrainResult:
    losses: List[float]                    # this run only (post-restore)
    ppl: List[float]
    steps: int                             # steps executed THIS run
    n_workers: int
    comm_bytes_per_step: float             # MEASURED: moved bytes / steps run
    wall_s: float
    final_loss: float
    start_step: int = 0                    # checkpoint-restore point (0 = fresh)
    sync_count: int = 0                    # sync rounds the policy triggered
    sync_steps: List[int] = dataclasses.field(default_factory=list)
    comm_bytes_total: float = 0.0          # measured wire bytes, WHOLE run
    comm_bytes_modeled: float = 0.0        # static fixed-H formula, PER STEP
                                           # (compare with comm_bytes_per_step,
                                           # not comm_bytes_total)
    sync_policy: str = "fixed_h"


def train_loop(cfg: ModelConfig, shape: ShapeConfig, opt_cfg: OptimizerConfig,
               *, steps: int = 100, seed: int = 0, log_every: int = 10,
               mesh=None, plan: Optional[ParallelismPlan] = None,
               non_iid: bool = True, checkpoint_dir: str = "",
               checkpoint_every: int = 0, verbose: bool = True,
               trace_out: str = "", metrics_out: str = "") -> TrainResult:
    """Every step runs inside a ``jax.profiler.StepTraceAnnotation``
    named ``train.step`` (argument ``kind``: local or sync), whose children
    ``train.batch_build``, ``train.transfer``, ``train.dispatch``,
    ``train.loss_read``, ``train.observe`` and, where they run,
    ``train.log`` and ``train.ckpt`` mark the host's work
    (``repro.trace.events.HostSpans``). They land in any ``jax.profiler``
    trace of the run, on the device trace's clock; with no profiler
    running they cost one inactive check each.

    ``trace_out`` records the run as a span stream (``repro.trace``):
    one timeline row per worker per step carrying the sync decisions the
    engine actually took, plus modeled device/wire costs on sync rounds —
    the input of the what-if replay engine and the Chrome/Perfetto export.
    Its host spans take their boundaries from the same ``with`` blocks, on
    the recorder's ``time.perf_counter`` clock (``wall_s`` reads it too).

    ``metrics_out`` streams the run's health metrics (``repro.obs``): one
    JSONL row per step — loss, grad norm, drift, B² quantiles per dtype
    bucket, and on sync rounds the EF residual norms and quantization MSE —
    plus a Prometheus textfile snapshot next to it (``<base>.prom``).
    Both instrumentations share one ``SyncHealthProbe``, so the trace spans
    and the metrics rows report the same numbers."""
    if trace_out or metrics_out:
        # compile the grad-norm health metric into the step programs; an
        # uninstrumented run's programs stay byte-identical (the emission
        # is absent, not skipped)
        opt_cfg = dataclasses.replace(opt_cfg, obs_metrics=True)
    mesh = mesh or worker_mesh()
    plan = plan or resolve_plan(cfg, mesh, optimizer=opt_cfg.name)
    with mesh:
        programs = build_train_programs(cfg, shape, opt_cfg, mesh, plan)
        R = programs.n_workers if programs.is_local else 1
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                         n_workers=max(R, 1), seed=seed, non_iid=non_iid)
        params, opt_state = programs.init_fn(jax.random.PRNGKey(seed))

        # The whole sync round is the engine's: the host-side schedule
        # (fixed_h reproduces the historical `(step+1) % H` modulo
        # bit-identically), the wire codec, the fused device-side encode
        # the jitted sync_step already contains, and the checkpointable
        # SyncState the adaptive schedule resumes from.
        engine = make_sync_engine(opt_cfg, is_local=programs.is_local,
                                  H=programs.H if programs.is_local else 1)
        start_step = 0
        sync_state = None
        if checkpoint_dir:
            from repro.checkpoint import (checkpoint_keys, latest_step,
                                          restore_checkpoint)
            from repro.core.flatspace import is_flat_checkpoint
            if latest_step(checkpoint_dir) is not None:
                keys = checkpoint_keys(checkpoint_dir)
                # Pre-SyncState checkpoints are (params, opt_state)
                # 2-tuples; pick the template matching the on-disk manifest
                # so the adaptive window just re-anchors for those, while a
                # genuinely mismatched checkpoint (different arch/worker
                # count) still fails with its real shape/key error.
                no_ss = not any(k.startswith("#2/") for k in keys)
                # A checkpoint written under either parameter layout
                # restores into either mode: the manifest says which layout
                # is on disk (packed planes vs per-leaf pytrees), and the
                # programs' FlatSpace adapters convert after the restore.
                disk_flat = is_flat_checkpoint(keys)
                if disk_flat == programs.is_flat:
                    abstract = jax.eval_shape(lambda: (params, opt_state))
                elif disk_flat:
                    if programs.flat_abstract is None:
                        raise ValueError(
                            "checkpoint holds a flat parameter plane but "
                            "this run has no FlatSpace (flat layout is "
                            "local Local AdaAlter only)")
                    abstract = programs.flat_abstract
                else:
                    abstract = programs.legacy_abstract
                like = (abstract if no_ss
                        else (*abstract, engine.export_state()))
                resharded = False
                if disk_flat:
                    # Cross-MESH flat restore: a plane written under a
                    # different (workers × shards) mesh carries different
                    # plane/counter shapes. Restore into the on-disk shapes,
                    # then reshard host-side (tail-pad-only slot layout: pad/
                    # truncate the zero tail, replicate or merge worker rows).
                    from repro.checkpoint import disk_like
                    like = disk_like(checkpoint_dir, like)
                state, start_step = restore_checkpoint(checkpoint_dir, like)
                if no_ss:
                    params, opt_state = state
                else:
                    params, opt_state, sync_state = state
                if disk_flat:
                    from repro.core.flatspace import adapt_flat_state
                    want = (programs.n_workers,
                            programs.flatspace.plane_size)
                    if tuple(params.shape) != want:
                        disk_shape = tuple(params.shape)
                        params, opt_state = adapt_flat_state(
                            params, opt_state, workers=want[0],
                            plane_size=want[1])
                        resharded = True
                if disk_flat and not programs.is_flat:
                    params, opt_state = programs.to_legacy(params, opt_state)
                elif programs.is_flat and not disk_flat:
                    params, opt_state = programs.to_flat(params, opt_state)
                if verbose:
                    layout = ""
                    if disk_flat != programs.is_flat:
                        layout = (" (flat -> per-leaf)" if disk_flat
                                  else " (per-leaf -> flat)")
                    if resharded:
                        layout += (f" (resharded plane {disk_shape} -> "
                                   f"{want})")
                    print(f"restored checkpoint at step {start_step}"
                          f"{' (no SyncState)' if no_ss else ''}{layout}")
        engine.reset(start_step)
        if sync_state is not None:
            engine.import_state(sync_state)
        n_params = count_params(cfg)

        # ---- obs: metrics registry + the shared sync-health probe --------- #
        from repro.obs import NULL_REGISTRY, SyncHealthProbe
        registry = NULL_REGISTRY
        if metrics_out:
            from repro.obs import MetricsRegistry
            registry = MetricsRegistry(labels={
                "arch": cfg.name, "algorithm": opt_cfg.name,
                "policy": opt_cfg.sync.policy,
                "codec": opt_cfg.sync.compression or "fp32", "workers": R})
            registry.open_jsonl(metrics_out)
        probe = None
        if registry or trace_out:
            probe = SyncHealthProbe.build(engine, programs, n_params)
            if registry:
                registry.set_many(probe.static_summary())

        # ---- trace recorder (repro.trace): spans + modeled round costs ---- #
        recorder = None
        if trace_out:
            from repro.roofline import V5E
            from repro.trace import TraceRecorder
            n_coll = engine.round_collectives(programs.n_payload_leaves,
                                              flat=programs.is_flat)
            round_b = engine.round_bytes(n_params)
            # modeled device-side encode + wire time of ONE sync round —
            # attached to every round's ef_encode/collective spans (a CPU
            # host cannot measure the TPU-side pass or a real fabric)
            enc_bytes = engine.modeled_encode_hbm_bytes(n_params)
            enc_t = enc_bytes / V5E.hbm_bw
            # with a sharded flat plane each device's worker-axis collective
            # moves its sub-plane only — the replay engine prices the round
            # per shard, not full-plane
            shard_b = engine.round_bytes_per_shard(n_params,
                                                   programs.n_shards)
            wire_t = comm.collective_time(shard_b, n_coll, R)
            st0 = engine.export_state()
            recorder = TraceRecorder(meta={
                "kind": "train", "arch": cfg.name,
                "algorithm": opt_cfg.name, "n_params": int(n_params),
                "n_workers": R, "steps": steps, "start_step": start_step,
                "H": programs.H, "is_local": programs.is_local,
                "flat": programs.is_flat,
                "sync": dataclasses.asdict(opt_cfg.sync),
                "use_pallas": opt_cfg.use_pallas,
                "n_payload_leaves": programs.n_payload_leaves,
                "n_collectives_per_round": n_coll,
                "n_shards": programs.n_shards,
                "round_wire_bytes_per_shard": shard_b,
                "fabric": dataclasses.asdict(comm.FabricModel()),
                "hbm_bw": V5E.hbm_bw, "clock": "perf_counter",
                "sync_state0": {"since": int(st0.since),
                                "drift": float(st0.drift)},
            })

        # ---- HLO per-op cost attribution (roofline.region_table) --------- #
        # AOT-lower both step programs and walk their optimized HLO into a
        # per-fused-region flops/bytes/optimal-seconds table. The replay
        # engine prices sync overhead from the sync/local optimal ratio
        # (deterministic program structure, not a noisy difference of two
        # measured means), and every local_step span carries the roofline-
        # optimal wall of its program. Costs one extra compile per program
        # (the AOT cache is separate from the loop's jit cache) — accepted
        # under opt-in tracing. A lowering failure fails the traced run.
        hlo_local_s = hlo_extra_s = None
        if recorder is not None:
            from repro.roofline import region_table
            bnp = make_train_batch(cfg, shape, ds, start_step,
                                   n_workers=R if programs.is_local else 0)
            b0 = jax.tree_util.tree_map(jnp.asarray, bnp)
            tabs = {}
            for prog_key, prog_fn in (("local_step", programs.local_step),
                                      ("sync_step", programs.sync_step)):
                txt = prog_fn.lower(params, opt_state, b0).compile().as_text()
                tabs[prog_key] = region_table(
                    txt, peak_flops=V5E.peak_flops, hbm_bw=V5E.hbm_bw)
            recorder.meta["hlo_cost"] = {
                **tabs, "hw": {"peak_flops": V5E.peak_flops,
                               "hbm_bw": V5E.hbm_bw}}
            hlo_local_s = float(tabs["local_step"]["optimal_s"])
            hlo_extra_s = max(0.0, float(tabs["sync_step"]["optimal_s"])
                              - hlo_local_s)

        losses, ppls = [], []
        from repro.trace.events import HostSpans, health_span_args
        spans = HostSpans(recorder)
        t0 = time.perf_counter()
        for step in range(start_step, steps):
            do_sync = engine.want_sync(step)
            with spans.step(step, kind="sync" if do_sync else "local"):
                with spans("batch_build"):
                    batch_np = make_train_batch(
                        cfg, shape, ds, step,
                        n_workers=R if programs.is_local else 0)
                with spans("transfer"):
                    batch = jax.tree_util.tree_map(jnp.asarray, batch_np)
                fn = programs.sync_step if do_sync else programs.local_step
                with spans("dispatch"):
                    params, opt_state, metrics = fn(params, opt_state, batch)
                # the blocking metric read keeps the device work inside the
                # step's host span
                with spans("loss_read"):
                    loss = float(metrics["loss"])
                    drift_val = (float(metrics.get("drift", 0.0))
                                 if engine.wants_drift else 0.0)
                with spans("observe"):
                    # decision-time window state (before observe folds
                    # this step in)
                    st = (engine.export_state() if recorder is not None
                          else None)
                    engine.observe(step, do_sync,
                                   {"drift": drift_val}
                                   if engine.wants_drift else None)
                    # ONE health summary feeds both exports (same numbers
                    # on the trace spans and in the metrics rows)
                    summary = (probe.step_summary(opt_state, metrics,
                                                  synced=do_sync)
                               if probe is not None else {})
                # the recorder's step span: dispatch through observe
                t_step, dur = spans.since("dispatch")
                if recorder is not None:
                    t_end = t_step + dur
                    health = health_span_args(summary)
                    if hlo_local_s is not None:
                        health["hlo_optimal_s"] = hlo_local_s
                    for w in range(R):
                        recorder.add("local_step", worker=w, step=step,
                                     t0=t_step, dur=dur, synced=do_sync,
                                     loss=loss, drift=drift_val,
                                     sync_since=int(st.since),
                                     sync_drift=float(st.drift), **health)
                        if do_sync:
                            enc_args = {}
                            if hlo_extra_s is not None:
                                enc_args["hlo_extra_optimal_s"] = hlo_extra_s
                            recorder.add("ef_encode", worker=w, step=step,
                                         t0=t_end, dur=enc_t, modeled=True,
                                         hbm_bytes=enc_bytes,
                                         codec=engine.codec.name, **enc_args)
                            recorder.add("collective", worker=w, step=step,
                                         t0=t_end + enc_t, dur=wire_t,
                                         modeled=True, wire_bytes=round_b,
                                         wire_bytes_per_shard=shard_b,
                                         n_shards=programs.n_shards,
                                         n_collectives=n_coll,
                                         codec=engine.codec.name, workers=R)
                losses.append(loss)
                ppls.append(math.exp(min(loss, 30.0)))
                echo = verbose and (step % log_every == 0
                                    or step == steps - 1)
                if registry or echo:
                    with spans("log", record="eval" if echo else None,
                               step=step, loss=loss):
                        if registry:
                            registry.counter("steps_total").inc()
                            registry.gauge(
                                "loss", help="train loss (mean over workers)"
                            ).set(loss)
                            registry.histogram(
                                "step_time_s",
                                help="host wall of one train step"
                            ).observe(dur)
                            probe.record(registry, summary, step=step,
                                         synced=do_sync)
                            registry.collect(step)
                        if echo:
                            print(f"step {step:5d} loss {loss:8.4f} ppl "
                                  f"{ppls[-1]:10.2f} "
                                  f"{'sync' if do_sync else 'local'}")
                if checkpoint_dir and checkpoint_every and \
                        (step + 1) % checkpoint_every == 0:
                    from repro.checkpoint import save_checkpoint
                    with spans("ckpt", record="ckpt", step=step,
                               dir=checkpoint_dir):
                        save_checkpoint(checkpoint_dir, step + 1,
                                        (params, opt_state,
                                         engine.export_state()))

        wall = time.perf_counter() - t0
        executed = max(steps - start_step, 0)
        # Measured comm: what the schedule that actually ran moved — the
        # engine's sync count times its per-round codec payload (for local
        # optimizers; synchronous ones all-reduce a gradient every step).
        # The static 2P/H formula is kept alongside as `comm_bytes_modeled`;
        # the two diverge under the adaptive policy and after a restore into
        # the middle of an H-window.
        if programs.is_local:
            total = engine.sync_count * engine.round_bytes(n_params)
            modeled = engine.modeled_bytes_per_step(n_params)
        else:
            # Synchronous execution (incl. a LocalOptimizer forced onto a
            # sync-only plan, where `sync` runs every step with an identity
            # mean): the only wire traffic is GSPMD's per-step gradient
            # all-reduce — P bytes, untouched by H or the sync codec — so
            # both numbers report that, not the inapplicable 2P/H formula.
            total = executed * engine.grad_allreduce_bytes(n_params)
            modeled = engine.grad_allreduce_bytes(n_params)
        # After a restore only the post-restore losses exist: report the
        # steps actually executed and guard the empty-run case (restore at or
        # past the target used to yield steps=target and a NaN-mean warning).
        final = float(np.mean(losses[-10:])) if losses else float("nan")
        if registry:
            registry.gauge("final_loss",
                           help="mean loss over the last 10 steps").set(final)
            base = (metrics_out[:-len(".jsonl")]
                    if metrics_out.endswith(".jsonl") else metrics_out)
            registry.write_prom(base + ".prom")
            registry.close()
            if verbose:
                print(f"wrote metrics {metrics_out} "
                      f"(+ Prometheus textfile {base + '.prom'})")
        if recorder is not None:
            recorder.meta["measured"] = {
                "wall_s": wall, "sync_count": engine.sync_count,
                "sync_steps": list(engine.sync_steps), "final_loss": final}
            recorder.save(trace_out)
            if verbose:
                print(f"wrote trace {trace_out} ({len(recorder.spans)} "
                      f"spans; python -m repro.trace.chrome {trace_out} "
                      f"to view, python -m repro.trace.replay for what-ifs)")
        return TrainResult(losses=losses, ppl=ppls, steps=executed,
                           n_workers=R,
                           comm_bytes_per_step=total / executed if executed
                           else 0.0,
                           wall_s=wall, final_loss=final,
                           start_step=start_step,
                           sync_count=engine.sync_count,
                           sync_steps=list(engine.sync_steps),
                           comm_bytes_total=total,
                           comm_bytes_modeled=modeled,
                           sync_policy=engine.name)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="biglstm", help=f"one of {sorted(ARCHS)}")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-sized family member (CPU-friendly)")
    ap.add_argument("--optimizer", default="local_adaalter",
                    choices=["sgd", "adagrad", "adaalter", "local_sgd",
                             "local_adaalter"])
    ap.add_argument("--H", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", nargs="?", const="int8", default="",
                    choices=["", *CODEC_NAMES], metavar="SCHEME",
                    help="sync wire codec (local optimizers): 'bf16' halves "
                         "the payload, 'int8' shrinks it ~4x (per-block "
                         "int8 + fp32 scales); both get error feedback. "
                         "Bare --compress means int8")
    ap.add_argument("--sync-policy", default="fixed_h", choices=POLICY_NAMES,
                    help="'fixed_h': the paper's every-H-steps schedule; "
                         "'adaptive': CADA-style — sync when the accumulated "
                         "parameter drift since the last sync crosses "
                         "--sync-threshold, no sooner than --h-min steps, "
                         "no later than --h-max")
    ap.add_argument("--sync-threshold", type=float, default=0.05,
                    help="adaptive trigger on the accumulated drift "
                         "statistic (metrics['drift'])")
    ap.add_argument("--drift-metric", default="update_norm",
                    choices=DRIFT_METRICS,
                    help="which drift statistic feeds the adaptive policy: "
                         "'update_norm' (relative per-step parameter "
                         "movement) or 'grad_staleness' (CADA-proper "
                         "relative ||g_t - g_last_sync||^2)")
    ap.add_argument("--h-min", type=int, default=1,
                    help="adaptive: minimum local steps between syncs")
    ap.add_argument("--h-max", type=int, default=0,
                    help="adaptive: maximum local steps between syncs "
                         "(0 -> 4*H)")
    ap.add_argument("--unfused-sync", action="store_true",
                    help="compose the sync encode from three HBM passes "
                         "(EF add / quantize / dequantize+residual) instead "
                         "of the fused one-pass kernel — bitwise identical; "
                         "bench/debug knob")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the fused AdaAlter update and the sync "
                         "codec through the Pallas kernels (interpret mode "
                         "off-TPU, Mosaic on TPU)")
    ap.add_argument("--flat", action="store_true",
                    help="flat parameter plane (core/flatspace.py): pack "
                         "params + optimizer state into contiguous planes "
                         "at init; the AdaAlter step becomes ONE kernel "
                         "launch and the sync round ONE kernel + ONE "
                         "collective instead of per-leaf ones. Train state "
                         "is bitwise identical to the per-leaf layout under "
                         "the same schedule (adaptive drift scalars, like "
                         "loss, may differ in ulps and shift a threshold-"
                         "edge sync); checkpoints restore across both "
                         "layouts")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="record the run as a span timeline (repro.trace): "
                         "per-worker per-step spans with the engine's sync "
                         "decisions + modeled device/wire costs. Export "
                         "with `python -m repro.trace.chrome`, what-if "
                         "replay with `python -m repro.trace.replay`")
    ap.add_argument("--metrics", default="", metavar="OUT.jsonl",
                    help="stream per-step health metrics (repro.obs): one "
                         "JSONL row per step — loss, raw-grad norm, drift, "
                         "B² quantiles per dtype bucket, EF residual norms "
                         "and quantization MSE on sync rounds, wire "
                         "compression ratio — plus a Prometheus textfile "
                         "snapshot next to it (OUT.prom)")
    ap.add_argument("--workers", type=int, default=0, metavar="N",
                    help="size of the mesh's data (worker) axis; remaining "
                         "devices form the model axis, which a --flat "
                         "run uses to FSDP/TP-shard each worker's plane "
                         "(sharded sub-planes, per-shard sync payload). "
                         "0 -> all devices on the worker axis. On the CPU, "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=K "
                         "gives K virtual devices")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--iid", action="store_true", help="disable non-IID workers")
    ap.add_argument("--out", default="", help="write metrics JSON here")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg, vocab=args.vocab)
    shape = ShapeConfig(name="cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    from repro.configs.base import SyncConfig
    opt_cfg = OptimizerConfig.from_sync(
        SyncConfig(policy=args.sync_policy, threshold=args.sync_threshold,
                   h_min=args.h_min, h_max=args.h_max,
                   drift_metric=args.drift_metric,
                   compression=args.compress,
                   fused=not args.unfused_sync),
        name=args.optimizer, lr=args.lr, H=args.H,
        warmup_steps=args.warmup, use_pallas=args.use_pallas,
        flat=args.flat)
    sched = (f"H={args.H}" if args.sync_policy == "fixed_h" else
             f"adaptive(thr={args.sync_threshold}, "
             f"h=[{args.h_min},{args.h_max or 4 * args.H}])")
    enable_compile_cache()
    mesh = worker_mesh(args.workers or None)
    print(f"training {cfg.name} ({count_params(cfg):,} params) with "
          f"{args.optimizer} {sched}"
          f"{' +' + args.compress + ' sync' if args.compress else ''} "
          f"on {jax.device_count()} device(s), mesh "
          f"{dict(mesh.shape)}")
    res = train_loop(cfg, shape, opt_cfg, steps=args.steps, seed=args.seed,
                     mesh=mesh, non_iid=not args.iid,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every,
                     trace_out=args.trace, metrics_out=args.metrics)
    print(f"done in {res.wall_s:.1f}s; final loss {res.final_loss:.4f}; "
          f"{res.sync_count} syncs in {res.steps} steps; measured comm/step "
          f"{res.comm_bytes_per_step / 1e6:.1f} MB (modeled "
          f"{res.comm_bytes_modeled / 1e6:.1f} MB; {res.n_workers} workers)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(dataclasses.asdict(res), f, indent=1)


if __name__ == "__main__":
    main()
