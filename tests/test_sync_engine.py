"""SyncEngine subsystem: composition, SyncState checkpointing, adaptive
mid-window restore (bit-identical schedule), grad-staleness drift metric,
SyncConfig back-compat aliases."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.core import comm
from repro.core.sync_engine import (DRIFT_METRICS, SyncEngine, SyncState,
                                    make_sync_engine)
from repro.core.sync_policy import AdaptiveSyncPolicy, FixedHPolicy
from repro.core import optimizers as opt_lib
from repro.data import SyntheticLM, make_train_batch
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs
from repro.launch.train import train_loop

SHAPE = ShapeConfig(name="eng", seq_len=32, global_batch=8, kind="train")


def _cfg(vocab=128):
    return reduced(get_arch("biglstm"), vocab=vocab)


# --------------------------------------------------------------------------- #
# SyncConfig block + back-compat aliases
# --------------------------------------------------------------------------- #
def test_sync_config_built_from_aliases():
    cfg = OptimizerConfig(name="local_adaalter", sync_policy="adaptive",
                          sync_threshold=0.1, h_min=2, h_max=8,
                          compression="int8", compression_block=128,
                          drift_metric="grad_staleness", sync_fused=False)
    assert cfg.sync == SyncConfig(policy="adaptive", threshold=0.1, h_min=2,
                                  h_max=8, drift_metric="grad_staleness",
                                  compression="int8", block=128, fused=False)
    # aliases mirror the block
    assert cfg.sync_policy == "adaptive" and cfg.compression == "int8"
    assert cfg.compression_block == 128 and cfg.sync_fused is False


def test_sync_config_block_and_aliases_compose_with_replace():
    cfg = OptimizerConfig.from_sync(
        SyncConfig(policy="adaptive", threshold=0.5))
    assert cfg.sync_policy == "adaptive" and cfg.sync_threshold == 0.5
    # replace via an alias updates the block...
    c2 = dataclasses.replace(cfg, compression="bf16")
    assert c2.sync.compression == "bf16" and c2.sync.policy == "adaptive"
    # ... and swapping the whole block resets everything not overridden
    c3 = c2.with_sync(SyncConfig(compression="int8"))
    assert c3.sync_policy == "fixed_h" and c3.compression == "int8"
    assert c3.lr == cfg.lr                   # non-sync fields untouched


# --------------------------------------------------------------------------- #
# engine composition + accounting
# --------------------------------------------------------------------------- #
def test_make_sync_engine_composes_policy_and_codec():
    eng = make_sync_engine(OptimizerConfig(H=4), is_local=True)
    assert isinstance(eng.policy, FixedHPolicy) and eng.policy.H == 4
    assert eng.codec.name == "fp32" and not eng.wants_drift
    eng = make_sync_engine(
        OptimizerConfig(sync_policy="adaptive", sync_threshold=0.1,
                        compression="int8"), is_local=True, H=4)
    assert isinstance(eng.policy, AdaptiveSyncPolicy)
    assert eng.codec.name == "int8" and eng.wants_drift
    assert eng.codec.ef_roundtrip is not None          # fused by default
    eng = make_sync_engine(
        OptimizerConfig(compression="int8", sync_fused=False), is_local=True)
    assert eng.codec.ef_roundtrip is None


def test_engine_rejects_unknown_drift_metric():
    with pytest.raises(ValueError, match="drift_metric"):
        SyncEngine(FixedHPolicy(4), None, drift_metric="vibes")
    assert set(DRIFT_METRICS) == {"update_norm", "grad_staleness"}


def test_engine_accounting_matches_comm():
    P = 1_000_000
    eng = make_sync_engine(
        OptimizerConfig(name="local_adaalter", H=4, compression="int8"),
        is_local=True, H=4)
    assert eng.round_bytes(P) == comm.sync_payload_bytes(
        "local_adaalter", P, compression="int8")
    assert eng.modeled_bytes_per_step(P) == pytest.approx(
        eng.round_bytes(P) / 4)
    assert eng.grad_allreduce_bytes(P) == 4.0 * P
    # fused encode touches ~2.4x less HBM than the three-pass composition
    # (38n vs 16n bytes modeled in comm.ef_sync_hbm_bytes)
    ratio = (eng.encode_hbm_bytes(P, fused=False)
             / eng.encode_hbm_bytes(P, fused=True))
    assert 2.0 < ratio < 3.0
    # the HBM model describes the int8 pipeline only — other codecs must
    # not silently get its quantize/scales passes charged to them
    bf = make_sync_engine(
        OptimizerConfig(name="local_adaalter", compression="bf16"),
        is_local=True, H=4)
    with pytest.raises(ValueError, match="int8"):
        bf.encode_hbm_bytes(P)


def test_engine_schedule_delegates_to_policy():
    eng = make_sync_engine(OptimizerConfig(H=3), is_local=True, H=3)
    eng.reset(0)
    synced = []
    for step in range(9):
        s = eng.want_sync(step)
        eng.observe(step, s, {"drift": 0.0})
        if s:
            synced.append(step)
    assert synced == [2, 5, 8]
    assert eng.sync_count == 3 and eng.sync_steps == synced
    assert eng.name == "fixed_h"


# --------------------------------------------------------------------------- #
# SyncState: export/import + checkpoint round-trip
# --------------------------------------------------------------------------- #
def test_sync_state_roundtrips_host_state_exactly():
    eng = make_sync_engine(
        OptimizerConfig(sync_policy="adaptive", sync_threshold=1e9,
                        h_min=1, h_max=64), is_local=True, H=4)
    eng.reset(0)
    # accumulate an 'awkward' float64 drift sum a float32 cast would mangle
    for step in range(7):
        s = eng.want_sync(step)
        eng.observe(step, s, {"drift": 0.1 + 1e-12})
    st = eng.export_state()
    assert st.drift.dtype == np.float64 and st.since.dtype == np.int64
    eng2 = make_sync_engine(
        OptimizerConfig(sync_policy="adaptive", sync_threshold=1e9,
                        h_min=1, h_max=64), is_local=True, H=4)
    eng2.reset(7)
    eng2.import_state(st)
    assert eng2.policy.host_state() == eng.policy.host_state()  # bit-exact


def test_sync_state_is_checkpointable_pytree(tmp_path):
    state = ({"w": jnp.arange(5.0)}, SyncState.make(3, 0.7500000000000018))
    d = str(tmp_path)
    save_checkpoint(d, 11, state)
    like = ({"w": jnp.zeros(5)}, SyncState.make())
    restored, step = restore_checkpoint(d, like)
    assert step == 11
    _, sync = restored
    assert isinstance(sync, SyncState)
    assert float(sync.drift) == 0.7500000000000018       # float64 survives
    assert int(sync.since) == 3


def test_fixed_h_state_is_inert():
    eng = make_sync_engine(OptimizerConfig(H=4), is_local=True, H=4)
    eng.reset(0)
    st = eng.export_state()
    assert int(st.since) == 0 and float(st.drift) == 0.0
    eng.import_state(SyncState.make(3, 9.9))             # no-op for fixed_h
    assert eng.want_sync(3)                              # still (step+1)%H


# --------------------------------------------------------------------------- #
# host-side proof that restoring SyncState fixes the re-anchoring bug
# --------------------------------------------------------------------------- #
def _drive(policy, steps, drift, start=0, stop_at=None, state=None):
    if state is not None:
        policy.reset(start)
        policy.load_host_state(*state)
    else:
        policy.reset(start)
    synced = []
    for step in range(start, steps):
        if stop_at is not None and step == stop_at:
            return synced, policy.host_state()
        s = policy.want_sync(step)
        policy.observe(step, s, {"drift": drift[step]})
        if s:
            synced.append(step)
    return synced, policy.host_state()


def test_adaptive_restore_with_state_matches_uninterrupted():
    rng = np.random.default_rng(0)
    drift = rng.uniform(0.0, 0.2, size=40)
    mk = lambda: AdaptiveSyncPolicy(threshold=0.3, h_min=2, h_max=9)
    full, _ = _drive(mk(), 40, drift)
    # save mid-window at step 15 (not a sync step for this drift stream)
    assert 15 not in full
    _, saved = _drive(mk(), 40, drift, stop_at=15)
    resumed, _ = _drive(mk(), 40, drift, start=15, state=saved)
    assert resumed == [s for s in full if s >= 15]
    # without the saved state the window re-anchors and the schedule shifts
    reanchored, _ = _drive(mk(), 40, drift, start=15)
    assert reanchored != resumed


# --------------------------------------------------------------------------- #
# end-to-end: mid-window checkpoint restore under the adaptive policy
# --------------------------------------------------------------------------- #
def test_adaptive_midwindow_restore_bit_identical_schedule(tmp_path):
    """Save at a non-sync step, restore, and the subsequent sync schedule
    (and losses) must be identical to the uninterrupted run — the SyncState
    in the checkpoint resumes the exact drift accumulator and window
    position instead of re-anchoring."""
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, warmup_steps=5,
                          sync_policy="adaptive", sync_threshold=0.02,
                          h_min=2, h_max=6)
    full = train_loop(cfg, SHAPE, opt, steps=18, verbose=False)
    assert 8 not in full.sync_steps, \
        "calibrate the test: step 8 must fall mid-window"
    d = str(tmp_path / "ckpt")
    train_loop(cfg, SHAPE, opt, steps=9, checkpoint_dir=d,
               checkpoint_every=9, verbose=False)
    resumed = train_loop(cfg, SHAPE, opt, steps=18, checkpoint_dir=d,
                         checkpoint_every=100, verbose=False)
    assert resumed.start_step == 9
    assert resumed.sync_steps == [s for s in full.sync_steps if s >= 9]
    np.testing.assert_allclose(resumed.losses, full.losses[9:],
                               rtol=1e-5, atol=1e-5)
    assert resumed.sync_count == len(resumed.sync_steps)


def test_legacy_two_tuple_checkpoint_still_restores(tmp_path):
    """Pre-SyncState checkpoints (params, opt_state) restore through the
    fallback path; the adaptive window then re-anchors at the restore."""
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, H=4, warmup_steps=5)
    mesh = worker_mesh()
    plan = resolve_plan(cfg, mesh, optimizer=opt.name)
    with mesh:
        programs = build_train_programs(cfg, SHAPE, opt, mesh, plan)
        params, opt_state = programs.init_fn(jax.random.PRNGKey(0))
    d = str(tmp_path / "legacy")
    save_checkpoint(d, 2, (params, opt_state))
    res = train_loop(cfg, SHAPE, opt, steps=6, checkpoint_dir=d,
                     verbose=False)
    assert res.start_step == 2 and res.steps == 4
    assert res.sync_steps == [3]          # fixed_h stays globally anchored
    assert np.isfinite(res.final_loss)


# --------------------------------------------------------------------------- #
# grad-staleness drift metric
# --------------------------------------------------------------------------- #
def test_with_grad_anchor_manages_leaf():
    base = opt_lib.local_adaalter(lr=0.3, H=4, warmup_steps=0)
    o = opt_lib.with_grad_anchor(base)
    params = {"w": jnp.ones(32)}
    state = o.init(params)
    assert "g_anchor" in state
    np.testing.assert_array_equal(np.asarray(state["g_anchor"]["w"]), 0.0)
    marker = {"w": jnp.full(32, 5.0)}
    state["g_anchor"] = marker
    g = {"w": jnp.full(32, 0.1)}
    params, state = o.local_step(g, state, params)
    np.testing.assert_array_equal(np.asarray(state["g_anchor"]["w"]), 5.0)
    params, state = o.sync(params, state)
    np.testing.assert_array_equal(np.asarray(state["g_anchor"]["w"]), 5.0)
    # the base numerics are untouched by the wrapper
    pb, sb = base.local_step(g, base.init({"w": jnp.ones(32)}),
                             {"w": jnp.ones(32)})
    np.testing.assert_array_equal(np.asarray(params["w"]),
                                  np.asarray(pb["w"]))


def test_make_optimizer_adds_anchor_only_for_staleness():
    staleness = OptimizerConfig(name="local_adaalter", sync_policy="adaptive",
                                drift_metric="grad_staleness")
    o = opt_lib.make_optimizer(staleness)
    assert "g_anchor" in o.init({"w": jnp.zeros(4)})
    for cfg in (OptimizerConfig(name="local_adaalter"),
                OptimizerConfig(name="local_adaalter",
                                sync_policy="adaptive")):
        assert "g_anchor" not in opt_lib.make_optimizer(cfg).init(
            {"w": jnp.zeros(4)})


def _run_program_steps(opt):
    cfg = _cfg()
    mesh = worker_mesh()
    plan = resolve_plan(cfg, mesh, optimizer=opt.name)
    with mesh:
        programs = build_train_programs(cfg, SHAPE, opt, mesh, plan)
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SHAPE.seq_len,
                         n_workers=programs.n_workers, seed=0, non_iid=True)
        batch = jax.tree_util.tree_map(jnp.asarray, make_train_batch(
            cfg, SHAPE, ds, 0, n_workers=programs.n_workers))
        # the programs donate (params, opt_state): init fresh for each call
        params, opt_state = programs.init_fn(jax.random.PRNGKey(0))
        _, s1, m1 = programs.local_step(params, opt_state, batch)
        params, opt_state = programs.init_fn(jax.random.PRNGKey(0))
        _, s2, m2 = programs.sync_step(params, opt_state, batch)
    return s1, m1, s2, m2


def test_steps_emit_staleness_drift_and_reanchor():
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, warmup_steps=0,
                          sync_policy="adaptive", sync_threshold=0.01,
                          drift_metric="grad_staleness")
    s_local, m_local, s_sync, m_sync = _run_program_steps(opt)
    # anchor starts at 0 -> ||g - 0||^2 / ||g||^2 ~= 1 on the first step
    assert float(m_local["drift"]) == pytest.approx(1.0, rel=1e-3)
    # local steps keep the anchor; the sync step re-anchors it to fresh g
    anchor_local = np.asarray(
        jax.tree_util.tree_leaves(s_local["g_anchor"])[0])
    anchor_sync = np.asarray(
        jax.tree_util.tree_leaves(s_sync["g_anchor"])[0])
    assert np.abs(anchor_local).max() == 0.0
    assert np.abs(anchor_sync).max() > 0.0


def test_grad_staleness_end_to_end_respects_bounds():
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, warmup_steps=5,
                          sync_policy="adaptive", sync_threshold=3.0,
                          h_min=2, h_max=6, drift_metric="grad_staleness")
    res = train_loop(cfg, SHAPE, opt, steps=18, verbose=False)
    assert res.sync_policy == "adaptive"
    gaps = np.diff([-1] + res.sync_steps)
    assert gaps.min() >= 2 and gaps.max() <= 6
    assert np.isfinite(res.final_loss)
