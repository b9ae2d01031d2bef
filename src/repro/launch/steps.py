"""Distributed train/serve step builders (pjit + vmap-over-workers).

Training with a *local* optimizer (the paper's Algorithms 2/4):
  * every trainable array and accumulator carries a leading worker axis R,
    physically sharded over ``plan.local_axes`` — per-device memory equals
    plain data parallelism, but replicas may diverge between syncs;
  * ``train_step(..., do_sync=False)`` — H-1 out of H steps — contains NO
    collective over the worker axes (the paper's skipped rounds);
  * ``train_step(..., do_sync=True)`` adds the params+accumulator average
    (Alg. 4 lines 11-12), which GSPMD lowers to the 2·P all-reduce the paper
    charges 2/H per step for.
  The two variants are compiled separately (static ``do_sync``) so the
  dry-run can attribute collective bytes to each and report the amortized
  ``local + sync/H`` volume exactly. *Which* variant runs each step is the
  ``SyncEngine``'s call (``core/sync_engine.py``, host-side): to feed its
  adaptive (CADA-style) policy — and only when it is configured — the local
  train steps additionally emit ``metrics['drift']``, the statistic
  ``SyncConfig.drift_metric`` selects: ``update_norm`` (per-worker parameter
  movement of the step relative to the parameter norm) or ``grad_staleness``
  (CADA-proper ‖g_t − g_last_sync‖² against the ``g_anchor`` state leaf,
  which sync steps re-anchor). Either statistic reduces each worker to a
  scalar *before* the (R,)-sized cross-worker mean, so the skipped rounds
  stay communication-free in any meaningful sense. Under the same opt-in
  pattern, ``OptimizerConfig.obs_metrics`` compiles in
  ``metrics['grad_norm']`` — the per-worker L2 of the raw (pre-clip)
  gradients — for the ``obs`` health probes and trace span args.
  With ``SyncConfig.compression`` set ('int8', 'bf16') the sync payload
  rides the corresponding ``WireCodec`` (``core/codecs.py``; error feedback)
  via the ``compressed_sync`` shim inside ``opt.sync`` — fused into a
  one-HBM-pass Pallas kernel when the codec provides it
  (``kernels/sync_fused.py``) — so only the sync_step changes; local steps
  stay untouched.

Training with a synchronous optimizer (Alg. 1/3, or models too large for
per-worker replicas): classic data-parallel/FSDP — gradients are implicitly
all-reduced every step by GSPMD.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, OptimizerConfig, ParallelismPlan, ShapeConfig
from repro.core import optimizers as opt_lib
from repro.models import build_model
from repro.sharding.partition import ShardingRules, use_rules
from repro.sharding.specs import param_shardings, opt_state_shardings, shape_safe_spec


def _axes_entry(axes: Tuple[str, ...]):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def worker_count(plan: ParallelismPlan, mesh) -> int:
    n = 1
    for ax in plan.local_axes:
        n *= mesh.shape[ax]
    return n


def _batch_sharding(rules: ShardingRules, batch_tree, *, workers: bool):
    mesh, plan = rules.mesh, rules.plan
    w = _axes_entry(tuple(plan.local_axes))
    d = _axes_entry(tuple(plan.grad_axes))

    def one(leaf):
        if workers:
            spec = P(w, d, *([None] * (leaf.ndim - 2)))
        else:
            spec = P(d, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, shape_safe_spec(leaf.shape, spec, mesh))

    return jax.tree_util.tree_map(one, batch_tree)


@jax.named_scope("worker_mean")
def _mean_over_workers(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape),
        tree)


def _drift_stat(new_params, params):
    """Per-worker parameter drift of one local step, as a single scalar.

    mean over workers of ||x_i' − x_i|| / (||x_i|| + tiny), every leaf
    carrying a leading worker axis. Each worker reduces to a scalar before
    any cross-worker op, so the only collective this adds is over an
    (R,)-sized vector — the adaptive sync policy accumulates it host-side.
    """
    delta = jax.tree_util.tree_map(
        lambda n, p: n.astype(jnp.float32) - p.astype(jnp.float32),
        new_params, params)
    d = opt_lib.global_norm(delta, batch_ndim=1)
    p = opt_lib.global_norm(params, batch_ndim=1)
    return jnp.mean(d / (p + 1e-12))


def _staleness_stat(grads, anchor):
    """CADA-proper gradient staleness, as a single scalar.

    mean over workers of ‖g_i,t − g_i,last_sync‖² / (‖g_i,t‖² + tiny) —
    the squared distance to the gradient each worker saw at its last sync
    round (kept in the ``g_anchor`` state leaf), normalized by the current
    gradient's energy so the threshold is scale-free. Like
    :func:`_drift_stat`, each worker reduces to a scalar before the
    (R,)-sized cross-worker mean, so skipped rounds stay communication-free.
    The anchor starts at zero, so the first window reads a statistic of
    ~1/step — which triggers an early first sync, a conservative start.
    """
    delta = jax.tree_util.tree_map(
        lambda g, a: g.astype(jnp.float32) - a, grads, anchor)
    d2 = jnp.square(opt_lib.global_norm(delta, batch_ndim=1))
    g2 = jnp.square(opt_lib.global_norm(grads, batch_ndim=1))
    return jnp.mean(d2 / (g2 + 1e-12))


@dataclasses.dataclass
class TrainPrograms:
    """Jitted step functions + their input sharding pytrees.

    With ``OptimizerConfig.flat`` the params/opt_state the step functions
    exchange are FlatSpace planes (core/flatspace.py) instead of per-leaf
    pytrees; the adapter fields below let the train loop translate between
    the two layouts (checkpoint restores work across them in both
    directions) — they are populated whenever the run COULD have a flat
    twin (local Local AdaAlter), not only when ``flat`` is on.
    """
    init_fn: Any                 # (rng) -> (params, opt_state)
    local_step: Any              # (params, opt_state, batch) -> (params, opt_state, metrics)
    sync_step: Any               # same signature; includes the H-th-step averaging
    batch_sharding: Any
    param_sharding: Any
    opt_sharding: Any
    n_workers: int
    is_local: bool
    H: int
    n_payload_leaves: int = 0    # param leaves one sync round touches (the
                                 # per-leaf path issues one collective per
                                 # leaf x the algorithm's round multiplier;
                                 # the flat plane issues ONE regardless)
    is_flat: bool = False
    n_shards: int = 1            # FSDP/TP sub-planes per worker (flat runs):
                                 # each device holds plane_size/n_shards
                                 # elements per worker row, and a sync round
                                 # moves per-shard wire bytes, not full-plane
    flatspace: Any = None        # FlatSpace geometry (local_adaalter runs)
    legacy_abstract: Any = None  # (params, opt_state) per-leaf ShapeDtypeStructs
    flat_abstract: Any = None    # (plane, flat_state) ShapeDtypeStructs
    to_flat: Any = None          # per-leaf (params, opt_state) -> planes
    to_legacy: Any = None        # planes -> per-leaf (params, opt_state)


def build_train_programs(cfg: ModelConfig, shape: ShapeConfig,
                         opt_cfg: OptimizerConfig, mesh,
                         plan: ParallelismPlan) -> TrainPrograms:
    model = build_model(cfg)
    opt = opt_lib.make_optimizer(opt_cfg)
    local = opt_lib.is_local(opt) and bool(plan.local_axes)
    overrides = {}
    if getattr(cfg, "seq_parallel", False):
        overrides["seq_sp"] = "model"
    if getattr(cfg, "expert_axes_2d", False):
        overrides["experts"] = ("model", "data")
    rules = ShardingRules(mesh, plan, overrides or None)
    R = worker_count(plan, mesh) if local else 1
    spmd_axes = tuple(plan.local_axes)

    # ---------------- abstract init (for shardings) ---------------------- #
    def _expand(base):
        """base params (no worker axis) -> (params, opt_state), full layout."""
        if local:
            params = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), base)
            state = jax.vmap(opt.init)(params)
        else:
            params, state = base, opt.init(base)
        return params, state

    def raw_init(rng):
        return _expand(model.init(rng))

    with use_rules(rules):
        abstract = jax.eval_shape(raw_init, jax.random.PRNGKey(0))
    p_sh = param_shardings(rules, abstract[0], with_workers=local)
    s_sh = opt_state_shardings(rules, abstract[1], p_sh, with_workers=local)

    # FlatSpace adapters exist for every run that could have a flat twin
    # (so either layout can restore the other's checkpoints); the flat
    # STEP functions are a separate build below.
    flat_ok = local and opt_cfg.name == "local_adaalter"
    if opt_cfg.flat and not flat_ok:
        raise ValueError(
            "OptimizerConfig.flat requires a local Local AdaAlter run "
            f"(got optimizer={opt_cfg.name!r}, local={local})")
    fs = None
    n_shards = 1
    if flat_ok:
        from repro.core import flatspace as fsp
        from repro.sharding.specs import plane_shard_count
        n_shards = plane_shard_count(mesh, plan)
        fs = fsp.FlatSpace.build(abstract[0], batch_ndim=1, shards=n_shards,
                                 eps=opt_cfg.eps if opt_cfg.flat else None)

    # Two-stage init. The RNG draw compiles UNSHARDED: letting GSPMD partition
    # the threefry computation changes the drawn values whenever a
    # non-trailing dim is sharded, so the same seed produced different weights
    # on different meshes (caught by the sharded-equivalence test). Only the
    # draw is RNG-dependent, so the R-way broadcast and accumulator zeros are
    # built under the target shardings — the unsharded spike is P, not ~5·R·P.
    _draw = jax.jit(model.init)
    _place = jax.jit(_expand, out_shardings=(p_sh, s_sh))

    def init_fn(rng):
        return _place(_draw(rng))

    # ---------------- loss/grad ------------------------------------------ #
    def loss_fn(params, batch):
        with use_rules(rules):
            loss, metrics = model.loss_fn(params, batch, remat=plan.remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    # ---------------- step bodies ---------------------------------------- #
    if local:
        def _worker(params, batch):
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        vworker = jax.vmap(_worker, spmd_axis_name=spmd_axes or None)
        vlocal = jax.vmap(opt.local_step)

        def step(params, opt_state, batch, *, do_sync: bool):
            loss, metrics, grads = vworker(params, batch)
            with jax.named_scope("adaalter_update"):
                if opt_cfg.use_pallas and opt_cfg.name == "local_adaalter":
                    from repro.kernels.ops import tree_fused_update
                    # the fused kernel bypasses opt.local_step, so the
                    # grad_clip wrapper never sees these grads — clip per
                    # worker here. `grads` itself stays RAW: the drift
                    # statistics below must see the same values the
                    # non-Pallas path's stat sees (there the wrapper clips
                    # inside opt.local_step, after the stat's inputs are
                    # captured).
                    applied = grads
                    if opt_cfg.grad_clip > 0:
                        applied, _ = opt_lib.clip_by_global_norm(
                            grads, opt_cfg.grad_clip, batch_ndim=1)
                    step_no = opt_state["step"] + 1
                    tprime = opt_state["tprime"] + 1
                    eta = opt_lib.warmup_lr(opt_cfg.lr, step_no[0],
                                            opt_cfg.warmup_steps)
                    extra = tprime[0].astype(jnp.float32) * opt_cfg.eps ** 2
                    new_params, new_b2 = tree_fused_update(
                        params, applied, opt_state["b2_sync"],
                        opt_state["b2_local"], eta, extra, use_pallas=True)
                    # keep extra leaves (e.g. compressed_sync's error-
                    # feedback residuals) instead of rebuilding the dict
                    new_state = {**opt_state, "step": step_no,
                                 "tprime": tprime, "b2_local": new_b2}
                else:
                    new_params, new_state = vlocal(grads, opt_state, params)
            out_metrics = {"loss": jnp.mean(loss),
                           **{k: jnp.mean(v) for k, v in metrics.items()}}
            if opt_cfg.obs_metrics:
                # per-worker L2 of the RAW (pre-clip) gradients, for the
                # obs health probes — same opt-in pattern as drift below:
                # not compiled into an uninstrumented run at all
                out_metrics["grad_norm"] = opt_lib.global_norm(
                    grads, batch_ndim=1)
            # divergence stat for the adaptive sync policy (its only
            # consumer — fixed_h never reads it, so don't make its hot loop
            # pay the extra full-parameter reductions). Which statistic is
            # the SyncConfig's drift_metric: the per-step relative update
            # norm, or the CADA-proper gradient staleness vs the g_anchor
            # state leaf (with_grad_anchor).
            from repro.core.sync_engine import drift_statistic
            stat = drift_statistic(opt_cfg.sync)
            staleness = stat == "grad_staleness"
            if staleness:
                out_metrics["drift"] = _staleness_stat(
                    grads, opt_state["g_anchor"])
            elif stat is not None:
                out_metrics["drift"] = _drift_stat(new_params, params)
            if do_sync:
                with jax.named_scope("sync"):
                    new_params, new_state = opt.sync(new_params, new_state,
                                                     _mean_over_workers)
                if staleness:
                    # re-anchor the staleness statistic at THIS round's
                    # per-worker gradients (the one place they're in scope)
                    new_state = {**new_state, "g_anchor": jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), grads)}
            return new_params, new_state, out_metrics
    else:
        def step(params, opt_state, batch, *, do_sync: bool):
            (loss, metrics), grads = grad_fn(params, batch)
            sq = jax.tree_util.tree_map(lambda g: jnp.square(g.astype(jnp.float32)),
                                        grads)
            if isinstance(opt, opt_lib.LocalOptimizer):
                new_params, new_state = opt.local_step(grads, opt_state, params)
                if do_sync:
                    new_params, new_state = opt.sync(new_params, new_state)
            else:
                new_params, new_state = opt.update(grads, sq, opt_state, params)
            out_metrics = {"loss": loss,
                           **{k: jnp.mean(v) for k, v in metrics.items()}}
            if opt_cfg.obs_metrics:
                out_metrics["grad_norm"] = opt_lib.global_norm(
                    grads, batch_ndim=0)
            return new_params, new_state, out_metrics

    # ---------------- batch specs + jit ----------------------------------- #
    example_batch = train_batch_specs(cfg, shape, R if local else 0)
    b_sh = _batch_sharding(rules, example_batch, workers=local)

    common = dict(
        in_shardings=(p_sh, s_sh, b_sh),
        out_shardings=(p_sh, s_sh, None),
        donate_argnums=(0, 1),
    )
    local_step, sync_step = _jit_steps(step, **common)

    # ---------------- flat-plane rebuild (OptimizerConfig.flat) ----------- #
    flat_fields = {}
    if fs is not None:
        from repro.core import flatspace as fsp
        flat_fields = dict(
            flatspace=fs, legacy_abstract=abstract,
            flat_abstract=fsp.flat_abstract(fs, abstract[0], abstract[1]),
            to_flat=lambda p_, s_: (fs.pack(p_), fsp.pack_opt_state(fs, s_)),
            to_legacy=lambda pl_, st_: (fs.unpack(pl_),
                                        fsp.unpack_opt_state(fs, st_)))
    if opt_cfg.flat:
        init_fn, local_step, sync_step, p_sh, s_sh = _flat_programs(
            fs, opt_cfg, mesh, plan, R, abstract, _expand, _draw, vworker,
            b_sh, leaf_p_sh=p_sh)

    return TrainPrograms(
        init_fn=init_fn, local_step=local_step, sync_step=sync_step,
        batch_sharding=b_sh, param_sharding=p_sh, opt_sharding=s_sh,
        n_workers=R, is_local=local,
        H=getattr(opt, "H", 1) if opt_lib.is_local(opt) else 1,
        n_payload_leaves=len(jax.tree_util.tree_leaves(abstract[0])),
        is_flat=opt_cfg.flat, n_shards=n_shards, **flat_fields)


# --------------------------------------------------------------------------- #
# flat-plane step builders (OptimizerConfig.flat; core/flatspace.py)
# --------------------------------------------------------------------------- #
def _flat_programs(fs, opt_cfg: OptimizerConfig, mesh, plan, R: int,
                   abstract, _expand, _draw, vworker, b_sh, *, leaf_p_sh):
    """Local AdaAlter over FlatSpace planes: the whole per-step update is
    ONE Pallas launch over the packed plane (vs one per leaf), and the sync
    round is ONE fused EF kernel + ONE all-reduce of a single flat wire
    array (vs 2·L small collectives). Given the same schedule the train
    STATE is bitwise identical to the per-leaf path — both with
    ``use_pallas`` (kernel vs kernel) and without (the jnp fallbacks mirror
    each other's cast orders); pinned by tests/test_flat_step.py. Derived
    scalars (loss, the adaptive drift statistic below — computed over the
    plane rather than leaf-by-leaf) are reduction-order-dependent and may
    differ in ulps between the two compiled programs, so an adaptive
    schedule can diverge at a threshold edge; fixed_h cannot.

    When the plan carries FSDP/TP axes the mesh can use
    (``sharding.partition.plane_shard_axes``), each worker row of every
    plane is additionally split into ``fs.shards`` contiguous tile-aligned
    sub-planes, one per device down the shard axes. The flat kernels then
    run shard-local under ``shard_map`` (pallas_call has no partitioning
    rule) with per-shard sidecar views, the ``[params ‖ B²]`` sync payload
    is concatenated shard-locally (shard boundaries are block boundaries,
    so the blocked quantization partitions the same elements), and the sync
    mean reduces over the WORKER axes only — sharded slots stay partitioned
    through the round. The unpacked per-leaf param views are pinned to the
    per-leaf shardings (``leaf_p_sh``) so the model forward compiles to the
    same sharded program whether the plane is replicated or sharded —
    that, plus the shard-local kernels being elementwise/block-exact, is
    what keeps sharded-flat bitwise equal to replicated-flat (pinned by
    tests/test_flat_sharded.py).

    Returns ``(init_fn, local_step, sync_step, p_sh, s_sh)`` where the
    state layout is (plane, {scalars + per-state planes}).
    """
    import numpy as np

    from repro.core.flatspace import (SCALAR_STATE_KEYS, mean_planes,
                                      pack_opt_state)
    from repro.core.sync_engine import drift_statistic
    from repro.kernels.adaalter_update import LANES as _LANES
    from repro.kernels.ops import on_tpu
    from repro.sharding.specs import plane_shardings

    if opt_cfg.eps <= 0:
        raise ValueError("flat mode requires eps > 0: the zero slot padding "
                         "must stay zero through rsqrt(B² + t'·ε²)")
    sync_cfg = opt_cfg.sync
    psize = fs.plane_size
    lossless = sync_cfg.compression in ("", "fp32")
    block = sync_cfg.block
    if psize % block or fs.align % block:
        raise ValueError(f"sync block {block} must divide the FlatSpace "
                         f"alignment {fs.align}")
    # sidecars, built once: where the plane must round through bf16, and
    # the per-block lower clamp of the [params ‖ B²] sync payload
    elems = fs.round16_elems()                               # (P,) bool
    upd_rnd_rows = np.tile(fs.rows_sidecar(elems, _LANES), (R, 1))
    sync_rnd_elems = np.concatenate([elems, np.zeros(psize, np.bool_)])
    sync_rnd_blocks = fs.rows_sidecar(sync_rnd_elems, block)
    f32min = float(jnp.finfo(jnp.float32).min)
    sync_low_elems = np.concatenate(
        [np.full(psize, f32min, np.float32), np.zeros(psize, np.float32)])
    sync_low_blocks = sync_low_elems.reshape(-1, block)[:, :1]
    stat = drift_statistic(sync_cfg)
    staleness = stat == "grad_staleness"

    w_entry = _axes_entry(tuple(plan.local_axes))
    plane_sh, scalar_sh, shard_axes = plane_shardings(mesh, plan)
    n_shards = 1
    for a in shard_axes:
        n_shards *= mesh.shape[a]
    assert fs.shards == n_shards, (fs.shards, n_shards, shard_axes)
    # a sharded plane runs its kernels shard-local under shard_map; so do
    # the Pallas kernels of a replicated plane on more than one device,
    # since pallas_call has no GSPMD partitioning rule (Mosaic refuses to
    # be partitioned). The jnp path of a replicated plane stays GSPMD.
    split = n_shards > 1 or (opt_cfg.use_pallas and mesh.devices.size > 1)
    p_sh = plane_sh
    s_sh = {k: (scalar_sh if k in SCALAR_STATE_KEYS else plane_sh)
            for k in abstract[1]}

    # ---------------- device-local kernel wrappers (split planes) -------- #
    # Each device sees its (R_local, plane_size/n_shards) sub-planes plus
    # per-shard sidecar VIEWS (the sidecars are shard_map inputs sharded
    # over the shard axes, i.e. slices indexed relative to the shard
    # origin). Everything inside is elementwise or blocked within a shard,
    # and shard boundaries land on tile/block boundaries, so shard-local
    # bits == replicated bits.
    if split:
        s_entry = _axes_entry(shard_axes)
        pspec = P(w_entry, s_entry)
        side_spec = P(s_entry, None)
        upd_rnd_pw = fs.rows_sidecar(elems, _LANES)       # (P//LANES, 1)
        enc_rnd_pw = fs.rows_sidecar(elems, block)        # (P//block, 1)

        def _upd_local(x, g, bs, bl, eta, extra, rnd):
            if opt_cfg.use_pallas:
                from repro.kernels.adaalter_update import flat_fused_update
                return flat_fused_update(x, g, bs, bl, eta, extra, rnd,
                                         interpret=not on_tpu())
            from repro.kernels.ref import flat_fused_update_ref
            e16 = jnp.broadcast_to(rnd > 0,
                                   (rnd.shape[0], _LANES)).reshape(-1)
            return flat_fused_update_ref(x, g, bs, bl, eta, extra, e16)

        _upd_sharded = jax.shard_map(
            _upd_local, mesh=mesh,
            in_specs=(pspec, pspec, pspec, pspec, P(), P(), side_spec),
            out_specs=(pspec, pspec), check_vma=False)

        def _enc_local(pp, bb, rp, rb, rndp):
            # shard-local [params ‖ B²] concat: the boundary sits at a
            # multiple of align (hence block), so every quantization block
            # holds exactly the elements the replicated concat's would
            nb = rndp.shape[0]
            rnd = jnp.concatenate([rndp, jnp.zeros_like(rndp)], 0)
            low = jnp.concatenate(
                [jnp.full((nb, 1), f32min, jnp.float32),
                 jnp.zeros((nb, 1), jnp.float32)], 0)
            payload = jnp.concatenate([pp, bb], -1)
            res = jnp.concatenate([rp, rb], -1)
            half = pp.shape[-1]
            if sync_cfg.compression == "int8":
                from repro.kernels.sync_fused import flat_ef_plane
                wire, nres = flat_ef_plane(
                    payload, res, rnd, low, block=block,
                    use_pallas=opt_cfg.use_pallas, fused=sync_cfg.fused)
            else:       # bf16 wire: elementwise EF roundtrip
                from repro.kernels.tiling import round_through_bf16
                low_e = jnp.broadcast_to(low, (2 * nb, block)).reshape(-1)
                rnd_e = jnp.broadcast_to(rnd > 0,
                                         (2 * nb, block)).reshape(-1)
                v = payload + res
                vq = jnp.maximum(round_through_bf16(v), low_e)
                wire = jnp.where(rnd_e, round_through_bf16(vq), vq)
                nres = v - wire
            return (wire[..., :half], wire[..., half:],
                    nres[..., :half], nres[..., half:])

        _enc_sharded = jax.shard_map(
            _enc_local, mesh=mesh,
            in_specs=(pspec, pspec, pspec, pspec, side_spec),
            out_specs=(pspec, pspec, pspec, pspec), check_vma=False)

    def _expand_flat(base):
        params, state = _expand(base)
        return fs.pack(params), pack_opt_state(fs, state)

    _place = jax.jit(_expand_flat, out_shardings=(p_sh, s_sh))

    def init_fn(rng):
        return _place(_draw(rng))

    def flat_sync_sharded(new_plane, new_state):
        """Alg. 4 lines 11-12 with a plane split across devices: the EF
        encode runs shard-local, and the wire mean reduces over the WORKER
        axes only — GSPMD all-reduces each device's sub-plane across its worker
        replicas while the shard (FSDP/TP) slots stay partitioned, so the
        round moves per-shard wire bytes per device, not full-plane. An
        unsharded plane keeps the ONE collective over [params ‖ B²]."""
        b2 = new_state["b2_local"]
        if lossless:
            wire_p, wire_b = new_plane, b2
            nres_p = nres_b = None
        else:
            with jax.named_scope("ef_encode"):
                wire_p, wire_b, nres_p, nres_b = _enc_sharded(
                    new_plane, b2, new_state["res_params"],
                    new_state["res_b2"], jnp.asarray(enc_rnd_pw))
        if n_shards == 1:
            mean = mean_planes(jnp.concatenate([wire_p, wire_b], -1),
                               sync_rnd_elems)
            mean_p, mean_b = mean[..., :psize], mean[..., psize:]
        else:
            mean_p = mean_planes(wire_p, elems)    # worker-axes collective
            mean_b = mean_planes(wire_b, None)
        out_state = {**new_state,
                     "tprime": jnp.zeros_like(new_state["tprime"]),
                     "b2_sync": mean_b, "b2_local": mean_b}
        if nres_p is not None:
            out_state["res_params"] = nres_p
            out_state["res_b2"] = nres_b
        return mean_p, out_state

    def flat_sync(new_plane, new_state):
        """Alg. 4 lines 11-12 over the packed payload — one wire array."""
        if split:
            return flat_sync_sharded(new_plane, new_state)
        payload = jnp.concatenate([new_plane, new_state["b2_local"]], -1)
        new_res = None
        if lossless:
            wire = payload
        elif sync_cfg.compression == "int8":
            from repro.kernels.sync_fused import flat_ef_plane
            with jax.named_scope("ef_encode"):
                res = jnp.concatenate([new_state["res_params"],
                                       new_state["res_b2"]], -1)
                wire, new_res = flat_ef_plane(
                    payload, res, sync_rnd_blocks, sync_low_blocks,
                    block=block, use_pallas=opt_cfg.use_pallas,
                    fused=sync_cfg.fused)
        else:   # bf16 wire: elementwise EF roundtrip, same bits per leaf
            from repro.kernels.tiling import round_through_bf16
            with jax.named_scope("ef_encode"):
                res = jnp.concatenate([new_state["res_params"],
                                       new_state["res_b2"]], -1)
                v = payload + res
                # the codec truncates EVERY payload (B² included); the wire
                # cast then re-rounds only the bf16 param slots (a no-op)
                vq = jnp.maximum(round_through_bf16(v),
                                 jnp.asarray(sync_low_elems))
                wire = jnp.where(jnp.asarray(sync_rnd_elems),
                                 round_through_bf16(vq), vq)
                new_res = v - wire
        mean = mean_planes(wire, sync_rnd_elems)       # the ONE collective
        b2m = mean[..., psize:]
        out_state = {**new_state,
                     "tprime": jnp.zeros_like(new_state["tprime"]),
                     "b2_sync": b2m, "b2_local": b2m}
        if new_res is not None:
            out_state["res_params"] = new_res[..., :psize]
            out_state["res_b2"] = new_res[..., psize:]
        return mean[..., :psize], out_state

    def step(plane, fstate, batch, *, do_sync: bool):
        # pin the unpacked per-leaf views to the SAME per-leaf shardings
        # the non-flat path trains under: the forward then compiles to one
        # sharded program regardless of how the plane itself is laid out
        # (replicated vs sharded plane → identical grads, bit for bit)
        p_tree = jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            fs.unpack(plane), leaf_p_sh)
        loss, metrics, grads = vworker(p_tree, batch)
        applied = grads
        if opt_cfg.grad_clip > 0:
            applied, _ = opt_lib.clip_by_global_norm(
                grads, opt_cfg.grad_clip, batch_ndim=1)
        a_plane = jax.lax.with_sharding_constraint(fs.pack(applied),
                                                   plane_sh)
        # the drift statistics must see RAW gradients (same contract as the
        # per-leaf fused path); with clipping off the packed plane is both
        g_plane = (a_plane if (not staleness or opt_cfg.grad_clip <= 0)
                   else jax.lax.with_sharding_constraint(fs.pack(grads),
                                                         plane_sh))
        with jax.named_scope("adaalter_update"):
            step_no = fstate["step"] + 1
            tprime = fstate["tprime"] + 1
            eta = opt_lib.warmup_lr(opt_cfg.lr, step_no[0],
                                    opt_cfg.warmup_steps)
            extra = tprime[0].astype(jnp.float32) * opt_cfg.eps ** 2
            if split:
                new_plane, new_b2 = _upd_sharded(
                    plane, a_plane, fstate["b2_sync"], fstate["b2_local"],
                    eta, extra, jnp.asarray(upd_rnd_pw))
            elif opt_cfg.use_pallas:
                from repro.kernels.adaalter_update import flat_fused_update
                new_plane, new_b2 = flat_fused_update(
                    plane, a_plane, fstate["b2_sync"], fstate["b2_local"],
                    eta, extra, jnp.asarray(upd_rnd_rows),
                    interpret=not on_tpu())
            else:
                from repro.kernels.ref import flat_fused_update_ref
                new_plane, new_b2 = flat_fused_update_ref(
                    plane, a_plane, fstate["b2_sync"], fstate["b2_local"],
                    eta, extra, jnp.asarray(elems))
        new_state = {**fstate, "step": step_no, "tprime": tprime,
                     "b2_local": new_b2}
        out_metrics = {"loss": jnp.mean(loss),
                       **{k: jnp.mean(v) for k, v in metrics.items()}}
        if opt_cfg.obs_metrics:
            out_metrics["grad_norm"] = opt_lib.global_norm(
                grads, batch_ndim=1)
        if staleness:
            delta = g_plane - fstate["g_anchor"]
            d2 = jnp.sum(jnp.square(delta), axis=-1)
            g2 = jnp.sum(jnp.square(g_plane), axis=-1)
            out_metrics["drift"] = jnp.mean(d2 / (g2 + 1e-12))
        elif stat is not None:
            d = jnp.sqrt(jnp.sum(jnp.square(new_plane - plane), -1))
            pn = jnp.sqrt(jnp.sum(jnp.square(plane), -1))
            out_metrics["drift"] = jnp.mean(d / (pn + 1e-12))
        if do_sync:
            with jax.named_scope("sync"):
                new_plane, new_state = flat_sync(new_plane, new_state)
            if staleness:
                new_state = {**new_state, "g_anchor": g_plane}
        return new_plane, new_state, out_metrics

    common = dict(in_shardings=(p_sh, s_sh, b_sh),
                  out_shardings=(p_sh, s_sh, None),
                  donate_argnums=(0, 1))
    return (init_fn, *_jit_steps(step, **common), p_sh, s_sh)


def _jit_steps(step, **jit_kw):
    """The two jitted step programs of ``step(..., do_sync=...)``, named
    ``local_step`` and ``sync_step``: a profiler trace names them
    ``jit_local_step`` and ``jit_sync_step`` in its ``XLA Modules``."""
    programs = []
    for name, do_sync in (("local_step", False), ("sync_step", True)):
        fn = partial(step, do_sync=do_sync)
        fn.__name__ = name
        programs.append(jax.jit(fn, **jit_kw))
    return programs


# --------------------------------------------------------------------------- #
# abstract input specs (ShapeDtypeStructs — never allocated)
# --------------------------------------------------------------------------- #
def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig, n_workers: int = 0):
    """n_workers > 0 -> leading worker axis with per-worker batch slices."""
    S = shape.seq_len
    if n_workers:
        assert shape.global_batch % n_workers == 0, (shape, n_workers)
        lead = (n_workers, shape.global_batch // n_workers)
    else:
        lead = (shape.global_batch,)
    toks = jax.ShapeDtypeStruct(lead + (S,), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    if cfg.cross_attn_every:
        batch["image_embeds"] = jax.ShapeDtypeStruct(
            lead + (cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        batch["audio_frames"] = jax.ShapeDtypeStruct(
            lead + (S, cfg.d_model), jnp.bfloat16)
    return batch
