"""Sync-policy subsystem: fixed_h bit-identity, adaptive bounds, measured comm."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.core.comm import sync_payload_bytes
from repro.core.sync_policy import (AdaptiveSyncPolicy, FixedHPolicy,
                                    make_sync_policy)
from repro.data import SyntheticLM, make_train_batch
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs
from repro.launch.train import train_loop

SHAPE = ShapeConfig(name="pol", seq_len=32, global_batch=8, kind="train")


def _cfg(vocab=128):
    return reduced(get_arch("biglstm"), vocab=vocab)


def _drive(policy, n_steps, drift=0.0, start=0):
    """Run a policy host-side with a constant per-step drift statistic."""
    policy.reset(start)
    synced = []
    for step in range(start, start + n_steps):
        s = policy.want_sync(step)
        policy.observe(step, s, {"drift": drift})
        if s:
            synced.append(step)
    return synced


# --------------------------------------------------------------------------- #
# policy unit behaviour (pure host-side, no jax)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("H", [1, 3, 4])
def test_fixed_h_matches_modulo(H):
    pol = FixedHPolicy(H)
    want = [s for s in range(20) if (s + 1) % H == 0]
    assert _drive(pol, 20) == want
    assert pol.sync_count == len(want)


def test_fixed_h_restore_keeps_global_anchor():
    """Restoring mid-window must continue the PRE-restore schedule."""
    pol = FixedHPolicy(4)
    assert _drive(pol, 10, start=6) == [7, 11, 15]   # (step+1) % 4 == 0


def test_adaptive_threshold_zero_syncs_every_h_min():
    pol = AdaptiveSyncPolicy(threshold=0.0, h_min=3, h_max=12)
    assert _drive(pol, 12) == [2, 5, 8, 11]


def test_adaptive_threshold_inf_syncs_every_h_max():
    pol = AdaptiveSyncPolicy(threshold=math.inf, h_min=1, h_max=5)
    assert _drive(pol, 15, drift=1e9) == [4, 9, 14]


def test_adaptive_h_min_equals_h_max_is_fixed_h():
    pol = AdaptiveSyncPolicy(threshold=0.123, h_min=4, h_max=4)
    assert _drive(pol, 16, drift=0.5) == _drive(FixedHPolicy(4), 16)


def test_adaptive_triggers_on_accumulated_drift():
    # drift 0.2/step, threshold 0.5, h_min 2: the 4th step since a sync is
    # the first with accumulated drift >= 0.5 (the deciding step's own drift
    # is not yet known — the policy runs before the step)
    pol = AdaptiveSyncPolicy(threshold=0.5, h_min=2, h_max=10)
    assert _drive(pol, 12, drift=0.2) == [3, 7, 11]


def test_adaptive_reset_clears_window():
    pol = AdaptiveSyncPolicy(threshold=1e9, h_min=1, h_max=4)
    _drive(pol, 3)                 # mid-window
    assert _drive(pol, 8, start=3) == [6, 10]   # window re-anchored at 3


def test_policy_validation():
    with pytest.raises(ValueError, match="h_max"):
        AdaptiveSyncPolicy(threshold=0.1, h_min=4, h_max=2)
    with pytest.raises(ValueError, match="h_min"):
        AdaptiveSyncPolicy(threshold=0.1, h_min=0)
    with pytest.raises(ValueError, match="sync_policy"):
        make_sync_policy(OptimizerConfig(sync_policy="sometimes"))
    with pytest.raises(ValueError, match="local optimizer"):
        make_sync_policy(OptimizerConfig(name="adaalter",
                                         sync_policy="adaptive"),
                         is_local=False)


def test_make_sync_policy_defaults():
    pol = make_sync_policy(OptimizerConfig(H=4))
    assert isinstance(pol, FixedHPolicy) and pol.H == 4
    pol = make_sync_policy(OptimizerConfig(H=4, sync_policy="adaptive",
                                           sync_threshold=0.1))
    assert isinstance(pol, AdaptiveSyncPolicy)
    assert pol.h_max == 16                        # h_max=0 -> 4*H


# --------------------------------------------------------------------------- #
# train_loop integration: bit-identity and measured comm
# --------------------------------------------------------------------------- #
def _manual_modulo_loop(cfg, shape, opt_cfg, steps, seed=0):
    """The historical train loop: sync iff (step+1) % H == 0."""
    mesh = worker_mesh()
    plan = resolve_plan(cfg, mesh, optimizer=opt_cfg.name)
    with mesh:
        programs = build_train_programs(cfg, shape, opt_cfg, mesh, plan)
        R = programs.n_workers if programs.is_local else 1
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                         n_workers=max(R, 1), seed=seed, non_iid=True)
        params, opt_state = programs.init_fn(jax.random.PRNGKey(seed))
        H = programs.H if programs.is_local else 1
        losses, sync_steps = [], []
        for step in range(steps):
            batch = jax.tree_util.tree_map(jnp.asarray, make_train_batch(
                cfg, shape, ds, step,
                n_workers=R if programs.is_local else 0))
            do_sync = ((step + 1) % H == 0)
            fn = programs.sync_step if do_sync else programs.local_step
            params, opt_state, metrics = fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if do_sync:
                sync_steps.append(step)
    return losses, sync_steps


def test_fixed_h_bit_identical_to_modulo_loop():
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, H=4, warmup_steps=5)
    res = train_loop(cfg, SHAPE, opt, steps=10, verbose=False)
    want_losses, want_syncs = _manual_modulo_loop(cfg, SHAPE, opt, steps=10)
    assert res.losses == want_losses           # bitwise, not allclose
    assert res.sync_steps == want_syncs == [3, 7]


def test_fixed_h_bit_identical_with_restore(tmp_path):
    """Restore into the middle of an H-window: same schedule, same losses."""
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, H=4, warmup_steps=5)
    d = str(tmp_path / "ckpt")
    train_loop(cfg, SHAPE, opt, steps=6, checkpoint_dir=d,
               checkpoint_every=6, verbose=False)       # stop mid-window
    r2 = train_loop(cfg, SHAPE, opt, steps=13, checkpoint_dir=d,
                    checkpoint_every=100, verbose=False)
    assert r2.start_step == 6
    # schedule stays anchored at global step 0, not the restore point
    assert r2.sync_steps == [7, 11]
    want_losses, _ = _manual_modulo_loop(cfg, SHAPE, opt, steps=13)
    np.testing.assert_allclose(r2.losses, want_losses[6:], rtol=1e-5,
                               atol=1e-5)
    # measured comm comes from the policy's sync count over executed steps —
    # NOT the static 2P/H formula, which this restore violates (2 syncs in
    # the 7 post-restore steps)
    per_round = sync_payload_bytes("local_adaalter", _n_params(cfg))
    assert r2.sync_count == 2
    np.testing.assert_allclose(r2.comm_bytes_per_step, 2 * per_round / 7)
    assert not np.isclose(r2.comm_bytes_per_step, r2.comm_bytes_modeled)


def _n_params(cfg):
    from repro.models.counting import count_params
    return count_params(cfg)


def test_measured_comm_matches_modeled_on_full_windows():
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, H=4, warmup_steps=5)
    res = train_loop(cfg, SHAPE, opt, steps=8, verbose=False)
    assert res.sync_count == 2
    np.testing.assert_allclose(res.comm_bytes_per_step,
                               res.comm_bytes_modeled)
    assert res.comm_bytes_total == res.sync_count * sync_payload_bytes(
        "local_adaalter", _n_params(cfg))


def test_adaptive_end_to_end_respects_bounds():
    cfg = _cfg()
    opt = OptimizerConfig(name="local_adaalter", lr=0.5, warmup_steps=5,
                          sync_policy="adaptive", sync_threshold=0.02,
                          h_min=2, h_max=6)
    res = train_loop(cfg, SHAPE, opt, steps=18, verbose=False)
    assert res.sync_policy == "adaptive"
    assert 3 <= res.sync_count <= 9            # 18/h_max .. 18/h_min
    gaps = np.diff([-1] + res.sync_steps)
    assert gaps.min() >= 2 and gaps.max() <= 6
    # measured accounting follows the triggered schedule
    per_round = sync_payload_bytes("local_adaalter", _n_params(cfg))
    np.testing.assert_allclose(res.comm_bytes_total,
                               res.sync_count * per_round)
    assert np.isfinite(res.final_loss)


def _step_metrics(opt):
    cfg = _cfg()
    mesh = worker_mesh()
    plan = resolve_plan(cfg, mesh, optimizer=opt.name)
    with mesh:
        programs = build_train_programs(cfg, SHAPE, opt, mesh, plan)
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SHAPE.seq_len,
                         n_workers=programs.n_workers, seed=0, non_iid=True)
        params, opt_state = programs.init_fn(jax.random.PRNGKey(0))
        batch = jax.tree_util.tree_map(jnp.asarray, make_train_batch(
            cfg, SHAPE, ds, 0, n_workers=programs.n_workers))
        _, _, metrics = programs.local_step(params, opt_state, batch)
    return metrics


def test_steps_emit_drift_metric_for_adaptive_only():
    """The compiled local step reports the divergence statistic iff the
    adaptive policy (its only consumer) is configured."""
    adaptive = OptimizerConfig(name="local_adaalter", lr=0.5, warmup_steps=0,
                               sync_policy="adaptive", sync_threshold=0.01)
    drift = float(_step_metrics(adaptive)["drift"])
    assert np.isfinite(drift) and drift > 0.0
    fixed = OptimizerConfig(name="local_adaalter", lr=0.5, H=4,
                            warmup_steps=0)
    assert "drift" not in _step_metrics(fixed)
