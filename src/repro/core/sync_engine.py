"""The sync round as one owned subsystem: SyncEngine = policy + codec + kernel.

The paper's whole win is cheaper sync rounds, and three orthogonal pieces
decide what one round costs:

  *when*  a host-side :class:`~repro.core.sync_policy.SyncPolicy` (the
          paper's fixed every-H-steps schedule, or the CADA-style adaptive
          trigger fed by the drift statistic the compiled steps emit);
  *what*  a :class:`~repro.core.codecs.WireCodec` (fp32 / bf16 / int8+scales
          with error feedback);
  *how*   the device-side error-feedback encode — either the codec's fused
          one-HBM-pass kernel (``kernels/sync_fused.py``) or the generic
          three-pass encode/decode composition (:func:`ef_apply` picks).

:class:`SyncEngine` composes the three behind one object that
``launch.train.train_loop`` drives and the benchmarks/dry-run query for
accounting, so no call site hand-wires policy + codec + kernel again.

The engine also owns an explicit, pytree-serializable :class:`SyncState`
(the policy's schedule-critical host state: window position + drift
accumulator — kept as float64 numpy scalars so a checkpoint round-trip is
bit-exact against the host accumulation). ``checkpoint/store.py`` saves it
next to ``(params, opt_state)``; restoring it resumes the *exact* adaptive
schedule instead of re-anchoring the window at the restore point (the
error-feedback residuals, the other half of the sync state, already live in
the optimizer state as ``res_params``/``res_b2`` leaves and ride the normal
checkpoint path). fixed_h has no host state; its SyncState is zeros and the
restore is a no-op, preserving the bit-identity guarantees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import comm
from repro.core.codecs import WireCodec, get_codec
from repro.core.sync_policy import SyncPolicy, make_sync_policy

Pytree = Any

#: drift statistics the compiled local steps can emit for the adaptive
#: policy (configs.base.SyncConfig.drift_metric).
DRIFT_METRICS = ("update_norm", "grad_staleness")


#: policy names that consume ``metrics['drift']`` — the one condition
#: ``drift_statistic`` and :attr:`SyncEngine.wants_drift` both check.
_DRIFT_CONSUMERS = ("adaptive",)


def drift_statistic(sync_cfg) -> Optional[str]:
    """Which drift statistic the compiled steps must emit for this
    SyncConfig — ``None`` unless a drift-consuming policy is configured.
    The single source of truth ``launch.steps`` (emit the metric),
    ``core.optimizers`` (carry the gradient anchor) and
    :attr:`SyncEngine.wants_drift` (read it back) all share.
    """
    return (sync_cfg.drift_metric if sync_cfg.policy in _DRIFT_CONSUMERS
            else None)


# --------------------------------------------------------------------------- #
# checkpointable sync state
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class SyncState:
    """Schedule-critical host state of the sync policy, as a pytree.

    ``since``  completed local steps since the last sync (window position);
    ``drift``  drift accumulated over those steps (float64: bit-exact vs the
               host-side Python accumulation, so a restored run makes the
               same threshold comparisons as the uninterrupted one).
    """

    since: np.ndarray
    drift: np.ndarray

    @staticmethod
    def make(since: int = 0, drift: float = 0.0) -> "SyncState":
        return SyncState(since=np.asarray(since, np.int64),
                         drift=np.asarray(drift, np.float64))


jax.tree_util.register_dataclass(
    SyncState, data_fields=["since", "drift"], meta_fields=[])


# --------------------------------------------------------------------------- #
# device-side: error-feedback encode of one payload pytree
# --------------------------------------------------------------------------- #
@jax.named_scope("ef_encode")
def ef_apply(tree: Pytree, residual: Pytree, codec: WireCodec,
             batch_ndim: int, *, clamp_nonneg: bool = False
             ) -> Tuple[Pytree, Pytree]:
    """-> (wire values cast like ``tree``, new residual), per leaf:

        v     = x + e                       # fp32
        v̂     = codec.roundtrip(v)          # what the wire carries
        wire  = v̂ cast to x.dtype           # [clamped >= 0 for accumulators]
        e'    = v − wire

    When the codec provides a fused ``ef_roundtrip`` (int8 with
    ``SyncConfig.fused``), the whole chain runs in ONE HBM pass per leaf;
    otherwise it is composed from ``encode``/``decode`` (three passes over
    the payload). The two are bitwise identical (tests/test_sync_fused.py).
    Blocked codecs never let a block straddle the leading ``batch_ndim``
    (per-worker) axes.
    """
    flat_x, treedef = jax.tree_util.tree_flatten(tree)
    flat_e = treedef.flatten_up_to(residual)
    # Pin every payload leaf to its STORED dtype value before encoding.
    # XLA's excess-precision simplification may otherwise feed the encode an
    # unrounded fp32 view of a bf16 leaf (whatever the producing update
    # computed), making the wire/residual bits depend on fusion context —
    # a real multi-host wire materializes the bf16 buffer, and the flat
    # plane path (which re-rounds explicitly) must see the same bits.
    flat_x = [jax.lax.optimization_barrier(x) for x in flat_x]
    if codec.ef_roundtrip is not None:
        pairs = [codec.ef_roundtrip(x, e, min(batch_ndim, x.ndim),
                                    clamp_nonneg)
                 for x, e in zip(flat_x, flat_e)]
        return (treedef.unflatten([w for w, _ in pairs]),
                treedef.unflatten([r for _, r in pairs]))

    import jax.numpy as jnp
    # clamp_nonneg keeps accumulator payloads >= 0 (they feed rsqrt); for
    # plain payloads the value-preserving max against float32 min pins the
    # decoded wire value so the backend cannot contract the residual's
    # v − decode(...) into an FMA — the same guard the fused kernel uses,
    # keeping the two paths bitwise interchangeable (kernels/sync_fused.py).
    lower = 0.0 if clamp_nonneg else float(jnp.finfo(jnp.float32).min)
    wires, residuals = [], []
    for x, e in zip(flat_x, flat_e):
        v = x.astype(jnp.float32) + e
        vq = codec.roundtrip(v, min(batch_ndim, v.ndim))
        vq = jnp.maximum(vq, lower)
        # the barrier pins the wire cast the same way (excess precision
        # would otherwise let the residual subtract the unrounded value)
        w = jax.lax.optimization_barrier(vq.astype(x.dtype))
        wires.append(w)
        # residual vs what was ACTUALLY sent (incl. any bf16 wire cast)
        residuals.append(v - w.astype(jnp.float32))
    return treedef.unflatten(wires), treedef.unflatten(residuals)


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #
class SyncEngine:
    """One object owning the sync round end-to-end.

    Host protocol (mirrors what ``train_loop`` used to hand-wire):
      reset(start_step) -> want_sync(step) -> [run step] -> observe(...)
    plus ``export_state()`` / ``import_state()`` around checkpoints, and
    the accounting queries the benchmarks/dry-run/TrainResult report.
    """

    def __init__(self, policy: SyncPolicy, codec: WireCodec, *,
                 algorithm: str = "local_adaalter", H: int = 1,
                 drift_metric: str = "update_norm",
                 block: int = 256) -> None:
        if drift_metric not in DRIFT_METRICS:
            raise ValueError(f"unknown drift_metric {drift_metric!r} "
                             f"(expected one of {DRIFT_METRICS})")
        self.policy = policy
        self.codec = codec
        self.algorithm = algorithm
        self.H = H
        self.drift_metric = drift_metric
        self.block = block

    # ---------------- schedule (delegates to the policy) ----------------- #
    def reset(self, start_step: int = 0) -> None:
        self.policy.reset(start_step)

    def want_sync(self, step: int) -> bool:
        return self.policy.want_sync(step)

    def observe(self, step: int, synced: bool,
                metrics: Optional[Dict[str, float]] = None) -> None:
        self.policy.observe(step, synced, metrics)

    @property
    def name(self) -> str:
        return self.policy.name

    @property
    def sync_count(self) -> int:
        return self.policy.sync_count

    @property
    def sync_steps(self) -> List[int]:
        return self.policy.sync_steps

    @property
    def wants_drift(self) -> bool:
        """Whether the compiled steps must emit ``metrics['drift']``."""
        return self.policy.name in _DRIFT_CONSUMERS

    # ---------------- checkpointable state -------------------------------- #
    def export_state(self) -> SyncState:
        since, drift = self.policy.host_state()
        return SyncState.make(since, drift)

    def import_state(self, state: SyncState) -> None:
        """Resume the exact schedule a checkpoint was saved under (call
        after :meth:`reset`; measured counters stay this-run-only)."""
        self.policy.load_host_state(int(np.asarray(state.since)),
                                    float(np.asarray(state.drift)))

    # ---------------- accounting ------------------------------------------ #
    def round_bytes(self, n_params: int) -> float:
        """Per-worker wire bytes of ONE sync round under this codec."""
        return comm.sync_payload_bytes(
            self.algorithm, n_params, compression=self.codec,
            block=self.block)

    def round_bytes_per_shard(self, n_params: int, n_shards: int = 1
                              ) -> float:
        """Per-DEVICE wire bytes of one sync round when the flat plane is
        FSDP/TP-sharded ``n_shards``-ways: each device all-reduces only its
        tile-aligned sub-plane across the worker axes, so the round moves
        ``round_bytes / n_shards`` per device (the full payload still
        crosses the fabric, but spread over the shard axis — this is the
        number the alpha-beta model and the trace/replay engine charge a
        device's collective with). ``n_shards == 1`` is :meth:`round_bytes`
        exactly."""
        return self.round_bytes(n_params) / max(1, int(n_shards))

    def modeled_bytes_per_step(self, n_params: int) -> float:
        """The static fixed-H formula (the paper's 2P/H claim)."""
        return comm.sync_bytes_per_step(
            self.algorithm, n_params, self.H, compression=self.codec,
            block=self.block)

    def grad_allreduce_bytes(self, n_params: int) -> float:
        """Per-step gradient all-reduce of fully synchronous execution —
        what moves when there is no sync round to skip."""
        return comm.payload_bytes(n_params)

    def encode_hbm_bytes(self, n_params: int, *,
                         fused: Optional[bool] = None) -> float:
        """Modeled device-side HBM traffic of one EF encode (see comm).

        The model describes the blocked int8 quantize pipeline; other
        codecs never run those passes, so asking is a caller bug, not a
        number to silently get wrong.
        """
        if self.codec.name != "int8":
            raise ValueError(
                f"ef_sync_hbm_bytes models the int8 quantize pipeline; "
                f"this engine's codec is {self.codec.name!r}")
        if fused is None:
            fused = self.codec.ef_roundtrip is not None
        return comm.ef_sync_hbm_bytes(
            int(n_params * comm.sync_round_multiplier(self.algorithm)),
            fused=fused, block=self.block)

    def round_collectives(self, n_payload_leaves: int, *,
                          flat: bool = False) -> int:
        """Collectives ONE sync round issues: the flat plane all-reduces a
        single packed wire array; the per-leaf path pays one all-reduce per
        payload leaf (x the algorithm's round multiplier). This is the
        ``n_collectives`` the alpha-beta fabric model charges latency for,
        and what the trace recorder stamps on ``collective`` spans."""
        return comm.round_collectives(self.algorithm, n_payload_leaves,
                                      flat=flat)

    def modeled_encode_hbm_bytes(self, n_params: int) -> float:
        """Modeled device-side HBM traffic of one sync round's EF encode,
        for ANY codec — the trace recorder's ``ef_encode`` span model
        (unlike :meth:`encode_hbm_bytes`, which answers only for the int8
        quantize pipeline it models exactly).

        int8  -> the fused/unfused pipeline model (``comm.ef_sync_hbm_bytes``)
        bf16  -> one EF pass over the payload: read x + residual, write the
                 re-rounded wire + new residual (fp32 master copies: 16n)
        fp32  -> 0 (lossless: no encode runs at all)
        """
        if self.codec.name == "int8":
            return self.encode_hbm_bytes(n_params)
        n = int(n_params * comm.sync_round_multiplier(self.algorithm))
        if self.codec.name == "bf16":
            return 16.0 * n
        return 0.0

    def __repr__(self) -> str:
        return (f"SyncEngine(policy={self.policy.name!r}, "
                f"codec={self.codec.name!r}, H={self.H}, "
                f"drift_metric={self.drift_metric!r}, "
                f"fused={self.codec.ef_roundtrip is not None})")


def make_sync_engine(opt_cfg, *, is_local: bool = True,
                     H: int = 0) -> SyncEngine:
    """OptimizerConfig (with its SyncConfig block) -> SyncEngine.

    ``H`` overrides ``cfg.H`` exactly like :func:`make_sync_policy` (the
    train loop passes the resolved ``programs.H``; synchronous execution
    gets H=1 == a round every step).
    """
    sync = opt_cfg.sync
    policy = make_sync_policy(opt_cfg, is_local=is_local, H=H)
    codec = get_codec(sync.compression, block=sync.block,
                      use_pallas=getattr(opt_cfg, "use_pallas", False),
                      fused=sync.fused)
    return SyncEngine(policy, codec, algorithm=opt_cfg.name,
                      H=H or opt_cfg.H, drift_metric=sync.drift_metric,
                      block=sync.block)
