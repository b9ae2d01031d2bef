"""Run Local AdaAlter training on TPU chips and check what comes out.

  python chip_smoke.py             # one chip: phases A and B
  python chip_smoke.py --chips 4   # four chips: the multi-chip path only

Phase A (one chip): biglstm at its published widths (hidden 2048,
projection 512, 2 layers, vocabulary 793,471), Local AdaAlter, fixed_h H=2,
fp32 wire, per-leaf layout, batch 8 x seq 20, 6 steps (3 sync rounds)
through ``repro.launch.train.train_loop``. Checks: every loss is finite;
the step-0 loss lies within 0.5 nats of ln V; the step-0 loss matches a
float32 evaluation of the model's loss on the same parameters and batch.

Phase B (one chip): the same widths at a 1/8 share of the vocabulary
(99,184 rows), ``--flat --use-pallas`` with the int8 wire, H=2, batch
32 x seq 128, 6 steps. Checks: the compiled steps hold Mosaic kernels
(``tpu_custom_call``); after every sync round the flat plane's params, B²
and error-feedback residuals equal those of the per-leaf ``--use-pallas``
run bit for bit.

``--chips 4``: (1) 4 workers x 1 chip at phase A's config. After every sync
round the workers' params and ``b2_sync`` are equal bit for bit (every
element, compared on the chips), and at up to 2^20 seeded positions per
leaf they match the host-side mean of the rows ``local_step`` produces from
the same state (replayed from init: the chips cannot hold a second copy,
and moving 33 GB of state through the host does not fit the time limit).
(2) 2 workers x a 2-way sharded flat plane at phase B's config, equal bit
for bit to the replicated plane on the same mesh.

One process drives every chip; nothing runs on the CPU or in Pallas
interpret mode. Without a TPU the script exits non-zero before any phase.
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.configs import OptimizerConfig, ShapeConfig, get_arch  # noqa: E402
from repro.configs.base import SyncConfig  # noqa: E402
from repro.data import SyntheticLM, make_train_batch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import resolve_plan, worker_mesh  # noqa: E402
from repro.launch.steps import build_train_programs  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.counting import count_params  # noqa: E402

BIGLSTM = get_arch("biglstm")
SHARE_VOCAB = 99_184             # 1/8 of biglstm's 793,471 vocabulary rows
SHAPE_A = ShapeConfig(name="smoke_a", seq_len=20, global_batch=8, kind="train")
SHAPE_B = ShapeConfig(name="smoke_b", seq_len=128, global_batch=32,
                      kind="train")
STEPS, H = 6, 2
LOSS0_NATS = 0.5                 # |step-0 loss - ln V| bound at random init
# |bf16 step loss - fp32 reference loss|: read 4.2e-5 on a v5e chip, 3e-5
# on the CPU; a model that outputs uniform logits lands 8e-4 away
REF_TOL_NATS = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def share_config():
    return dataclasses.replace(BIGLSTM, vocab_size=SHARE_VOCAB)


def opt_a() -> OptimizerConfig:
    # the train CLI's defaults for lr (0.5) and warmup (100)
    return OptimizerConfig.from_sync(SyncConfig(policy="fixed_h"),
                                     name="local_adaalter", lr=0.5, H=H,
                                     warmup_steps=100)


def opt_b(flat: bool) -> OptimizerConfig:
    return OptimizerConfig.from_sync(
        SyncConfig(policy="fixed_h", compression="int8"),
        name="local_adaalter", lr=0.5, H=H, warmup_steps=100,
        use_pallas=True, flat=flat)


class CompileStats:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events (a cache hit's load time counts as compile
    time; a miss is written only if it took JAX's minimum compile time)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, what: str, since: tuple) -> tuple:
        now = (self.seconds, self.hits, self.misses)
        log(f"[{what}] compile {now[0] - since[0]:.1f} s, persistent cache "
            f"hits {now[1] - since[1]}, misses {now[2] - since[2]}")
        return now

    def mark(self) -> tuple:
        return (self.seconds, self.hits, self.misses)


def peak_bytes(devices) -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def check(ok: bool, what: str) -> None:
    log(f"  check {'PASS' if ok else 'FAIL'}: {what}")
    if not ok:
        raise AssertionError(what)


def check_mosaic(compiled, what: str) -> None:
    check("tpu_custom_call" in compiled.as_text(),
          f"{what} holds Mosaic kernels (tpu_custom_call)")


def describe(cfg, shape, opt, layout: str) -> None:
    wire = opt.sync.compression or "fp32"
    log(f"  config: {cfg.name} hidden {cfg.d_model} proj {cfg.lstm_proj} "
        f"layers {cfg.n_layers} vocab {cfg.vocab_size:,} "
        f"({count_params(cfg):,} params, {cfg.param_dtype}); "
        f"{opt.name} fixed_h H={opt.H} lr {opt.lr} warmup "
        f"{opt.warmup_steps}; {wire} wire; {layout}; batch "
        f"{shape.global_batch} x seq {shape.seq_len} = "
        f"{shape.global_batch * shape.seq_len} tokens/step")


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and bool(
        (_bits(a) == _bits(b)).all())


def batch_at(cfg, shape, ds, step, n_workers):
    return jax.tree_util.tree_map(
        jnp.asarray, make_train_batch(cfg, shape, ds, step,
                                      n_workers=n_workers))


def fp32_reference_loss(cfg, params, batch) -> float:
    """The model's loss in float32 with full-precision matmuls."""
    model32 = build_model(dataclasses.replace(cfg, param_dtype="float32"))
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p, b: model32.loss_fn(p, b)[0])(p32, batch)
    return float(loss)


# --------------------------------------------------------------------------- #
# phase A: the published model through train_loop on one chip
# --------------------------------------------------------------------------- #
def phase_a(stats: CompileStats, devices, cfg=BIGLSTM, shape=SHAPE_A,
            steps: int = STEPS) -> None:
    log("== phase A: biglstm at its published widths, per-leaf, fp32 wire")
    opt = opt_a()
    describe(cfg, shape, opt, "per-leaf")
    mesh = worker_mesh(devices=devices)
    mark = stats.mark()
    t0 = time.perf_counter()
    res = train_loop(cfg, shape, opt, steps=steps, seed=0, log_every=1,
                     mesh=mesh)
    log(f"  train_loop: {res.steps} steps, {res.sync_count} sync rounds at "
        f"steps {res.sync_steps}, {time.perf_counter() - t0:.1f} s wall "
        "(compile included)")
    stats.report("phase A train_loop", mark)
    log(f"  losses: {res.losses}")
    log(f"  peak_bytes_in_use: {peak_bytes(devices):,}")
    check(res.steps == steps and res.sync_count == steps // H,
          f"{steps} steps with {steps // H} sync rounds")
    check(all(math.isfinite(x) for x in res.losses), "every loss is finite")
    ln_v = math.log(cfg.vocab_size)
    check(abs(res.losses[0] - ln_v) <= LOSS0_NATS,
          f"step-0 loss {res.losses[0]:.4f} within {LOSS0_NATS} nats of "
          f"ln V = {ln_v:.4f}")

    # train_loop's step-0 input: the state its programs' init_fn draws from
    # PRNGKey(seed), worker row 0, and step 0's batch
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer=opt.name)
        pr = build_train_programs(cfg, shape, opt, mesh, plan)
        stacked, state = pr.init_fn(jax.random.PRNGKey(0))
        del state
        params = jax.tree_util.tree_map(lambda x: x[0], stacked)
        del stacked
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                     n_workers=1, seed=0, non_iid=True)
    b = make_train_batch(cfg, shape, ds, 0, n_workers=1)
    batch = {k: jnp.asarray(v[0]) for k, v in b.items()}
    ref = fp32_reference_loss(cfg, params, batch)
    check(abs(res.losses[0] - ref) <= REF_TOL_NATS,
          f"step-0 loss {res.losses[0]:.6f} vs float32 reference "
          f"{ref:.6f} (highest matmul precision): |diff| "
          f"{abs(res.losses[0] - ref):.2e} <= {REF_TOL_NATS}")
    log(f"  peak_bytes_in_use: {peak_bytes(devices):,}")


# --------------------------------------------------------------------------- #
# phase B: Mosaic kernels, int8 wire, flat plane == per-leaf, one chip
# --------------------------------------------------------------------------- #
def _compile_pair(pr, state, batch):
    params, opt_state = state
    return (pr.local_step.lower(params, opt_state, batch).compile(),
            pr.sync_step.lower(params, opt_state, batch).compile())


def phase_b(stats: CompileStats, devices, cfg=None, shape=SHAPE_B,
            steps: int = STEPS) -> None:
    cfg = cfg or share_config()
    log(f"== phase B: biglstm widths at a 1/8 vocabulary share "
        f"({BIGLSTM.vocab_size:,} -> {cfg.vocab_size:,} rows), Pallas "
        "kernels, int8 wire, flat plane vs per-leaf")
    describe(cfg, shape, opt_b(True), "flat plane vs per-leaf, --use-pallas")
    mesh = worker_mesh(devices=devices)
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer="local_adaalter")
        mark = stats.mark()
        leaf = build_train_programs(cfg, shape, opt_b(False), mesh, plan)
        flat = build_train_programs(cfg, shape, opt_b(True), mesh, plan)
        fs = flat.flatspace
        R = leaf.n_workers
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                         n_workers=R, seed=0, non_iid=True)
        sL = leaf.init_fn(jax.random.PRNGKey(0))
        sF = flat.init_fn(jax.random.PRNGKey(0))
        b0 = batch_at(cfg, shape, ds, 0, R)
        leaf_c = _compile_pair(leaf, sL, b0)
        flat_c = _compile_pair(flat, sF, b0)
        stats.report("phase B compile (4 programs)", mark)
        for name, c in (("per-leaf local_step", leaf_c[0]),
                        ("per-leaf sync_step", leaf_c[1]),
                        ("flat local_step", flat_c[0]),
                        ("flat sync_step", flat_c[1])):
            check_mosaic(c, name)
        for step in range(steps):
            sync = (step + 1) % H == 0
            batch = batch_at(cfg, shape, ds, step, R)
            *sL, mL = leaf_c[sync](*sL, batch)
            *sF, mF = flat_c[sync](*sF, batch)
            lL, lF = float(mL["loss"]), float(mF["loss"])
            log(f"  step {step} {'sync ' if sync else 'local'} loss "
                f"per-leaf {lL:.6f} flat {lF:.6f}")
            check(math.isfinite(lL) and math.isfinite(lF),
                  f"step {step} losses are finite")
            if not sync:
                continue
            (pL, stL), (pF, stF) = sL, sF
            ok = all(same_bits(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(pL),
                jax.tree_util.tree_leaves(fs.unpack(pF))))
            for key in ("b2_sync", "b2_local", "res_params", "res_b2"):
                ok &= all(same_bits(a, b) for a, b in zip(
                    jax.tree_util.tree_leaves(stL[key]),
                    jax.tree_util.tree_leaves(
                        fs.unpack(stF[key], dtype=jnp.float32))))
            check(ok, f"sync round at step {step}: flat params, b2_sync, "
                      "b2_local, res_params, res_b2 == per-leaf, bitwise")
    log(f"  peak_bytes_in_use: {peak_bytes(devices):,}")


# --------------------------------------------------------------------------- #
# --chips 4: the sync round across chips
# --------------------------------------------------------------------------- #
SAMPLE = 1 << 20     # host-mean check: positions drawn per leaf


def _bit_view(x):
    return jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])


@jax.jit
def _rows_equal(x):
    """Every worker row of ``x`` equals row 0, bit for bit (on device: a
    full-vocabulary state does not fit the host's time budget)."""
    b = _bit_view(x)
    return jnp.all(b == b[:1])


@jax.jit
def _planes_equal(a, b):
    """Two worker-stacked planes agree bit for bit over their shared
    length, and the longer one's tail (shard-alignment pad) is zero."""
    n = min(a.shape[-1], b.shape[-1])
    tails = [jnp.all(_bit_view(x[..., n:]) == 0) for x in (a, b)]
    return jnp.all(_bit_view(a[..., :n]) == _bit_view(b[..., :n])) & (
        tails[0] & tails[1])


def _positions(tree, seed: int = 0):
    """Per leaf, ``SAMPLE`` sorted flat positions drawn from a seeded
    generator (all of them for a smaller leaf)."""
    rng = np.random.default_rng(seed)
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        size = math.prod(x.shape[1:])
        out.append(np.arange(size) if size <= SAMPLE else
                   np.unique(rng.integers(0, size, SAMPLE)))
    return out


@jax.jit
def _gather(x, positions):
    return x.reshape(x.shape[0], -1)[:, positions].astype(jnp.float32)


def _sample(tree, positions):
    """Host (workers, positions) float32 rows of every leaf of ``tree``."""
    return [np.asarray(_gather(x, i))
            for x, i in zip(jax.tree_util.tree_leaves(tree), positions)]


def _vs_host_mean(synced, rows, dtype) -> float:
    """Worst |synced row 0 - host mean of the reference rows| over its
    bound, at the sampled positions; <= 1 passes.

    The bound, with u the leaf dtype's relative ulp (2^-7 bf16, 2^-23
    fp32): u/2 |mean| for rounding the fp32 mean to the dtype, plus
    u mean|row| for rows the sync program computes up to one ulp apart
    from local_step's (another fusion of the same update), plus
    3 * 2^-24 mean|row| for the fp32 sum of four rows in another order.
    """
    mean, amean = rows.mean(axis=0), np.abs(rows).mean(axis=0)
    u = float(jnp.finfo(dtype).eps)
    bound = u / 2 * np.abs(mean) + (u + 3 * 2.0 ** -24) * amean + 1e-30
    return float((np.abs(synced[0] - mean) / bound).max())


def workers_4x1(stats: CompileStats, devices, cfg=BIGLSTM, shape=SHAPE_A,
                steps: int = STEPS) -> None:
    n = len(devices)
    log(f"== {n} workers x 1 chip: biglstm at its published widths, "
        "per-leaf, fp32 wire")
    opt = opt_a()
    describe(cfg, shape, opt, f"per-leaf, {n} workers")
    mesh = worker_mesh(n, devices=devices)
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer="local_adaalter")
        pr = build_train_programs(cfg, shape, opt, mesh, plan)
        check(pr.n_workers == n and pr.is_local,
              f"{pr.n_workers} local workers on mesh {dict(mesh.shape)}")
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                         n_workers=n, seed=0, non_iid=True)
        batches = [batch_at(cfg, shape, ds, step, n) for step in range(steps)]

        def state_at(k: int):
            """The state before step k: init, then steps 0..k-1 on their
            fixed_h schedule. The chips cannot hold two copies of it, so
            the reference below replays it instead of copying it."""
            params, state = pr.init_fn(jax.random.PRNGKey(0))
            for step in range(k):
                fn = pr.local_step if (step + 1) % H else pr.sync_step
                params, state, _ = fn(params, state, batches[step])
            return params, state

        mark = stats.mark()
        for k in range(H - 1, steps, H):
            t0 = time.perf_counter()
            params, state = state_at(k)
            pos = _positions((params, state))
            before = _sample((params, state), pos)
            rows_p, rows_s, _ = pr.local_step(params, state, batches[k])
            sync_pos = _positions((rows_p, rows_s["b2_local"]))
            ref = _sample((rows_p, rows_s["b2_local"]), sync_pos)
            del rows_p, rows_s
            params, state = state_at(k)
            check(all(same_bits(a, b) for a, b in zip(
                before, _sample((params, state), pos))),
                f"step {k}: the replayed state equals the first one at "
                "the sampled positions, bitwise")
            params, state, m = pr.sync_step(params, state, batches[k])
            log(f"  step {k} sync  loss {float(m['loss']):.6f}")
            synced = (params, state["b2_sync"])
            check(all(bool(_rows_equal(x))
                      for x in jax.tree_util.tree_leaves(synced)),
                  f"sync round at step {k}: the {n} workers' params and "
                  "b2_sync are equal, bitwise, over every element")
            worst = max(_vs_host_mean(s, r, x.dtype) for s, r, x in zip(
                _sample(synced, sync_pos), ref,
                jax.tree_util.tree_leaves(synced)))
            check(worst <= 1.0,
                  f"sync round at step {k}: params and b2_sync match the "
                  f"host mean of local_step's rows at up to {SAMPLE:,} "
                  f"sampled positions per leaf (worst {worst:.3f} of the "
                  f"bound); {time.perf_counter() - t0:.1f} s with the "
                  "replays")
            del params, state, synced
        stats.report(f"{n} workers x 1 chip", mark)
    log(f"  peak_bytes_in_use: {peak_bytes(devices):,}")


def sharded_plane_2x2(stats: CompileStats, devices, cfg=None, shape=SHAPE_B,
                      steps: int = STEPS) -> None:
    cfg = cfg or share_config()
    log("== 2 workers x 2-way sharded flat plane vs the replicated plane: "
        f"biglstm widths, vocabulary {cfg.vocab_size:,}, Pallas, int8 wire")
    describe(cfg, shape, opt_b(True), "flat plane, 2 workers")
    mesh = worker_mesh(2, devices=devices)
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer="local_adaalter")
        mark = stats.mark()
        sh = build_train_programs(cfg, shape, opt_b(True), mesh, plan)
        rep = build_train_programs(cfg, shape, opt_b(True), mesh,
                                   dataclasses.replace(plan, tp_axis=""))
        check(sh.n_shards == 2 and rep.n_shards == 1,
              f"mesh {dict(mesh.shape)}: sharded plane in {sh.n_shards} "
              f"shards, replicated plane in {rep.n_shards}")
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                         n_workers=2, seed=0, non_iid=True)
        sS = sh.init_fn(jax.random.PRNGKey(0))
        sR = rep.init_fn(jax.random.PRNGKey(0))
        b0 = batch_at(cfg, shape, ds, 0, 2)
        sh_c = _compile_pair(sh, sS, b0)
        rep_c = _compile_pair(rep, sR, b0)
        stats.report("sharded + replicated plane compile (4 programs)", mark)
        check_mosaic(sh_c[0], "sharded flat local_step")
        check_mosaic(sh_c[1], "sharded flat sync_step")
        for step in range(steps):
            sync = (step + 1) % H == 0
            batch = batch_at(cfg, shape, ds, step, 2)
            *sS, mS = sh_c[sync](*sS, batch)
            *sR, mR = rep_c[sync](*sR, batch)
            log(f"  step {step} {'sync ' if sync else 'local'} loss sharded "
                f"{float(mS['loss']):.6f} replicated {float(mR['loss']):.6f}")
            if not sync:
                continue
            ok = bool(_planes_equal(sS[0], sR[0]))
            for key in sorted(sS[1]):
                x, y = sS[1][key], sR[1][key]
                ok &= bool(_planes_equal(x, y) if x.ndim == 2 else
                           same_bits(x, y))
            check(ok, f"sync round at step {step}: sharded plane and every "
                      "state plane == replicated, bitwise")
    log(f"  peak_bytes_in_use: {peak_bytes(devices):,}")


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B on one chip; 4: the multi-chip "
                         "path only")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    log(f"device: {dev.platform} {dev.device_kind}, using {len(devices)} of "
        f"{len(jax.devices())}; jax {jax.__version__}; compile cache "
        f"{cache_dir or 'off'}")
    stats = CompileStats()
    if args.chips == 4:
        workers_4x1(stats, devices)
        sharded_plane_2x2(stats, devices)
    else:
        phase_a(stats, devices)
        phase_b(stats, devices)
    log(f"total: compile {stats.seconds:.1f} s, persistent cache hits "
        f"{stats.hits}, misses {stats.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
