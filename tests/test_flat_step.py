"""Flat-plane step/sync vs the per-leaf path: bitwise, end to end.

The acceptance bar for the flat parameter plane (core/flatspace.py +
launch/steps._flat_programs): with the SAME config, the flat train step and
the per-leaf train step must produce bit-identical state — params, both B²
accumulators, and the error-feedback residuals (which pin the sync wire:
residual = v − wire) — on local steps AND sync rounds, for every codec and
for both the Pallas kernels and the jnp fallbacks. Checkpoints must restore
across the two layouts in both directions without breaking the bits.
"""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.data import SyntheticLM, make_train_batch
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs
from repro.launch.train import train_loop

CFG = reduced(get_arch("biglstm"), vocab=128)
SHAPE = ShapeConfig(name="t", seq_len=16, global_batch=4, kind="train")


def _opt(flat, compression="", use_pallas=False, fused=True, H=2,
         **kwargs):
    return OptimizerConfig.from_sync(
        SyncConfig(compression=compression, fused=fused, **kwargs),
        name="local_adaalter", lr=0.5, H=H, warmup_steps=3,
        use_pallas=use_pallas, flat=flat)


def _assert_tree_bitwise(a, b, what=""):
    fa, _ = jax.tree_util.tree_flatten(a)
    fb, _ = jax.tree_util.tree_flatten(b)
    for i, (x, y) in enumerate(zip(fa, fb)):
        np.testing.assert_array_equal(
            np.asarray(x.astype(jnp.float32)),
            np.asarray(y.astype(jnp.float32)), err_msg=f"{what}[{i}]")


# --------------------------------------------------------------------------- #
# the core pin: flat == per-leaf, state bitwise, local + sync steps
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("compression,use_pallas", [
    ("", False),            # uncompressed, jnp fallback update
    ("int8", False),        # fused EF encode, jnp fallback
    ("int8", True),         # Pallas: ONE update launch + ONE EF launch
    ("bf16", False),        # elementwise wire truncation
])
def test_flat_step_bitwise_matches_per_leaf(compression, use_pallas):
    mesh = worker_mesh()
    with mesh:
        plan = resolve_plan(CFG, mesh, optimizer="local_adaalter")
        pL = build_train_programs(CFG, SHAPE, _opt(False, compression,
                                                   use_pallas), mesh, plan)
        pF = build_train_programs(CFG, SHAPE, _opt(True, compression,
                                                   use_pallas), mesh, plan)
        fs = pF.flatspace
        R = pL.n_workers
        ds = SyntheticLM(vocab_size=CFG.vocab_size, seq_len=SHAPE.seq_len,
                         n_workers=R, seed=0, non_iid=True)
        paramsL, stateL = pL.init_fn(jax.random.PRNGKey(0))
        planeF, stateF = pF.init_fn(jax.random.PRNGKey(0))
        for step in range(3):                      # local, sync, post-sync
            batch = jax.tree_util.tree_map(
                jnp.asarray,
                make_train_batch(CFG, SHAPE, ds, step, n_workers=R))
            sync = (step + 1) % 2 == 0
            paramsL, stateL, _ = (pL.sync_step if sync
                                  else pL.local_step)(paramsL, stateL, batch)
            planeF, stateF, _ = (pF.sync_step if sync
                                 else pF.local_step)(planeF, stateF, batch)
            _assert_tree_bitwise(paramsL, fs.unpack(planeF),
                                 f"params@{step}")
            for key in ("b2_sync", "b2_local", "res_params", "res_b2"):
                if key in stateL:
                    _assert_tree_bitwise(
                        stateL[key],
                        fs.unpack(stateF[key], dtype=jnp.float32),
                        f"{key}@{step}")
            np.testing.assert_array_equal(np.asarray(stateL["step"]),
                                          np.asarray(stateF["step"]))
            np.testing.assert_array_equal(np.asarray(stateL["tprime"]),
                                          np.asarray(stateF["tprime"]))


def test_flat_requires_local_adaalter():
    mesh = worker_mesh()
    with mesh:
        plan = resolve_plan(CFG, mesh, optimizer="local_sgd")
        with pytest.raises(ValueError, match="flat"):
            build_train_programs(
                CFG, SHAPE,
                OptimizerConfig(name="local_sgd", flat=True), mesh, plan)


def test_flat_requires_positive_eps():
    mesh = worker_mesh()
    with mesh:
        plan = resolve_plan(CFG, mesh, optimizer="local_adaalter")
        with pytest.raises(ValueError, match="eps"):
            build_train_programs(
                CFG, SHAPE,
                OptimizerConfig(name="local_adaalter", eps=0.0, flat=True),
                mesh, plan)


# --------------------------------------------------------------------------- #
# checkpoints cross the layout boundary in both directions, bitwise
# --------------------------------------------------------------------------- #
def test_checkpoint_cross_layout_bitwise(tmp_path):
    """per-leaf ckpt -> flat continuation -> flat ckpt -> per-leaf
    continuation: every hand-off lands mid-H-window and the final states
    agree bit-for-bit with the never-converted per-leaf run."""
    d_leaf, d_flat = str(tmp_path / "leaf"), str(tmp_path / "flat")
    kw = dict(steps=2, checkpoint_dir=d_leaf, checkpoint_every=2,
              verbose=False, non_iid=True)
    opt_leaf = _opt(False, "int8", H=4)
    opt_flat = _opt(True, "int8", H=4)
    # prefix: per-leaf to step 2 (mid-window: H=4 syncs at 3, 7, ...)
    train_loop(CFG, SHAPE, opt_leaf, **kw)
    shutil.copytree(d_leaf, d_flat)
    # continue per-leaf vs flat (restores the LEGACY ckpt into flat mode)
    a = train_loop(CFG, SHAPE, opt_leaf, **{**kw, "steps": 6,
                                            "checkpoint_dir": d_leaf})
    b = train_loop(CFG, SHAPE, opt_flat, **{**kw, "steps": 6,
                                            "checkpoint_dir": d_flat})
    assert a.start_step == b.start_step == 2
    assert a.sync_steps == b.sync_steps
    # the step-6 checkpoints (one per-leaf, one packed planes) hold the
    # same bits
    mesh = worker_mesh()
    from repro.checkpoint import restore_checkpoint
    from repro.core.sync_engine import SyncState
    with mesh:
        plan = resolve_plan(CFG, mesh, optimizer="local_adaalter")
        pF = build_train_programs(CFG, SHAPE, opt_flat, mesh, plan)
    (sl, step_l) = restore_checkpoint(
        d_leaf, (*pF.legacy_abstract, SyncState.make()))
    (sf, step_f) = restore_checkpoint(
        d_flat, (*pF.flat_abstract, SyncState.make()))
    assert step_l == step_f == 6
    params_f, opt_f = pF.to_legacy(sf[0], sf[1])
    _assert_tree_bitwise(sl[0], params_f, "params@6")
    for key in ("b2_sync", "b2_local", "res_params", "res_b2"):
        _assert_tree_bitwise(sl[1][key], opt_f[key], f"{key}@6")
    np.testing.assert_array_equal(np.asarray(sl[2].since),
                                  np.asarray(sf[2].since))
    # and back: restore the FLAT ckpt into per-leaf mode, continue both
    c = train_loop(CFG, SHAPE, opt_leaf, **{**kw, "steps": 8,
                                            "checkpoint_dir": d_leaf})
    d = train_loop(CFG, SHAPE, opt_leaf, **{**kw, "steps": 8,
                                            "checkpoint_dir": d_flat})
    assert c.start_step == d.start_step == 6
    assert c.sync_steps == d.sync_steps
    (sl8, _) = restore_checkpoint(
        d_leaf, (*pF.legacy_abstract, SyncState.make()))
    (sf8, _) = restore_checkpoint(
        d_flat, (*pF.legacy_abstract, SyncState.make()))
    _assert_tree_bitwise(sl8[0], sf8[0], "params@8")
    for key in ("b2_sync", "b2_local", "res_params", "res_b2"):
        _assert_tree_bitwise(sl8[1][key], sf8[1][key], f"{key}@8")


def test_adaptive_midwindow_restore_into_flat(tmp_path):
    """Mid-window ADAPTIVE restore from a legacy per-leaf checkpoint into
    --flat mode: the engine's SyncState (window position + drift
    accumulator) survives the layout conversion and the run resumes the
    adaptive schedule instead of re-anchoring at the restore point."""
    ckpt = str(tmp_path / "ck")
    sync_kw = dict(policy="adaptive", threshold=0.05, h_min=2, h_max=8,
                   drift_metric="update_norm")
    opt_leaf = _opt(False, "int8", H=4, **sync_kw)
    opt_flat = _opt(True, "int8", H=4, **sync_kw)
    full = train_loop(CFG, SHAPE, opt_leaf, steps=8, verbose=False)
    train_loop(CFG, SHAPE, opt_leaf, steps=3, checkpoint_dir=ckpt,
               checkpoint_every=3, verbose=False)
    res = train_loop(CFG, SHAPE, opt_flat, steps=8, checkpoint_dir=ckpt,
                     checkpoint_every=0, verbose=False)
    assert res.start_step == 3 and res.steps == 5
    assert res.sync_policy == "adaptive"
    assert np.isfinite(res.final_loss)
    # the restored run continues a schedule, not restarts one: its syncs
    # all land after the restore point and stay within h_max of each other
    assert all(s >= 3 for s in res.sync_steps)
    assert abs(res.final_loss - full.final_loss) / abs(full.final_loss) < 0.1
