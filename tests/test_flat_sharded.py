"""Shard-aligned FlatSpace: geometry, eps guard, cross-mesh adapters, and
the 4-device bitwise pins for the sharded flat plane.

The sharded path's core invariant: on a (workers x shards) mesh the flat
plane trains *bitwise* equal to the replicated flat plane (and hence, via
the tier-1 flat pins, to the per-leaf path).  The tail-pad-only layout is
what makes the cross-mesh adapters trivial: slot offsets never move with
the shard count, only the zero tail grows or shrinks.

Multi-device cases run in subprocesses because the XLA host-device count
must be fixed before the backend initialises (same pattern as
tests/test_sharding.py).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import OptimizerConfig
from repro.configs.base import SyncConfig
from repro.core.flatspace import ALIGN, FlatSpace, adapt_flat_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, marker: str, timeout: int = 900) -> None:
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert marker in proc.stdout, proc.stdout + "\n" + proc.stderr


# --------------------------------------------------------------------- #
# shard geometry (1 device, in-process)                                 #
# --------------------------------------------------------------------- #

def _tree():
    import jax.numpy as jnp
    return {"a": jnp.zeros((2, 300, 257)), "b": jnp.zeros((2, 77)),
            "c": jnp.zeros((2, 1))}


def test_shard_geometry_offsets_stable():
    """Slot offsets must not move with the shard count (tail-pad-only);
    the plane must tile into shard-count equal, ALIGN-multiple pieces."""
    base = FlatSpace.build(_tree(), batch_ndim=1)
    for shards in (1, 2, 4):
        fs = FlatSpace.build(_tree(), batch_ndim=1, shards=shards)
        assert fs.plane_size % (shards * ALIGN) == 0
        assert fs.shard_size * shards == fs.plane_size
        for s0, s1 in zip(base.slots, fs.slots):
            assert (s0.offset, s0.padded) == (s1.offset, s1.padded)
        assert fs.plane_size >= base.plane_size


def test_shard_pack_unpack_roundtrip():
    import jax.numpy as jnp
    tree = _tree()
    fs = FlatSpace.build(tree, batch_ndim=1, shards=4)
    plane = fs.pack(tree)
    assert plane.shape == (2, fs.plane_size)
    out = fs.unpack(plane)
    for k in tree:
        assert (np.asarray(out[k]) == np.asarray(tree[k])).all()
    # the shard tail beyond the last slot is all zero padding
    end = fs.slots[-1].offset + fs.slots[-1].padded
    assert not np.asarray(plane[:, end:]).any()


# --------------------------------------------------------------------- #
# eps guard (satellite: --flat with eps == 0 corrupts the padding)      #
# --------------------------------------------------------------------- #

def test_flat_config_rejects_nonpositive_eps():
    with pytest.raises(ValueError, match="eps"):
        OptimizerConfig.from_sync(SyncConfig(), name="local_adaalter",
                                  lr=0.1, eps=0.0, flat=True)
    # per-leaf mode tolerates eps == 0 (no padding to protect)
    OptimizerConfig.from_sync(SyncConfig(), name="local_adaalter",
                              lr=0.1, eps=0.0, flat=False)


def test_flatspace_rejects_nonpositive_eps():
    with pytest.raises(ValueError, match="eps"):
        FlatSpace.build(_tree(), batch_ndim=1, eps=0.0)
    FlatSpace.build(_tree(), batch_ndim=1, eps=1e-7)   # fine
    FlatSpace.build(_tree(), batch_ndim=1, eps=None)   # per-leaf adapters


# --------------------------------------------------------------------- #
# cross-mesh host adapters                                              #
# --------------------------------------------------------------------- #

def _state(workers, plane_size, seed=0):
    rng = np.random.default_rng(seed)
    plane = rng.standard_normal((workers, plane_size)).astype(np.float32)
    state = {"b2_sync": rng.random((workers, plane_size)).astype(np.float32),
             "step": np.full((workers,), 7, np.int32),
             "tprime": np.zeros((workers,), np.float32)}
    return plane, state


def test_adapt_grow_shrink_roundtrip_bit_exact():
    p0, s0 = _state(1, 11 * ALIGN)
    p1, s1 = adapt_flat_state(p0, s0, workers=2, plane_size=12 * ALIGN)
    assert p1.shape == (2, 12 * ALIGN)
    assert (p1[0] == p1[1]).all()                    # replicated rows
    assert not p1[:, 11 * ALIGN:].any()              # zero tail pad
    p2, s2 = adapt_flat_state(p1, s1, workers=1, plane_size=11 * ALIGN)
    assert (p2 == p0).all()
    for k in s0:
        assert (s2[k] == s0[k]).all(), k


def test_adapt_shrink_merges_diverged_workers():
    p0, s0 = _state(4, 2 * ALIGN)
    p1, s1 = adapt_flat_state(p0, s0, workers=2, plane_size=2 * ALIGN)
    want = p0.reshape(2, 2, -1).mean(axis=1).astype(np.float32)
    assert (p1 == want).all()
    assert s1["step"].shape == (2,) and (s1["step"] == 7).all()


def test_adapt_refuses_lossy_truncation():
    p0, s0 = _state(1, 2 * ALIGN)
    with pytest.raises(ValueError, match="truncate"):
        adapt_flat_state(p0, s0, workers=1, plane_size=ALIGN)


def test_adapt_refuses_nondivisible_workers():
    p0, s0 = _state(3, ALIGN)
    with pytest.raises(ValueError):
        adapt_flat_state(p0, s0, workers=2, plane_size=ALIGN)


# --------------------------------------------------------------------- #
# 4-device pins (subprocess: sharded == replicated, cross-mesh ckpt)    #
# --------------------------------------------------------------------- #

_BITWISE = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.data import SyntheticLM, make_train_batch
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs

CFG = reduced(get_arch("biglstm"), vocab=128)
SHAPE = ShapeConfig(name="t", seq_len=16, global_batch=4, kind="train")
mesh = worker_mesh(2)

def run(opt_cfg, plan):
    with mesh:
        pr = build_train_programs(CFG, SHAPE, opt_cfg, mesh, plan)
        R = pr.n_workers
        ds = SyntheticLM(vocab_size=128, seq_len=16, n_workers=R, seed=0,
                         non_iid=True)
        plane, state = pr.init_fn(jax.random.PRNGKey(0))
        for step in range(3):
            b = jax.tree_util.tree_map(jnp.asarray,
                make_train_batch(CFG, SHAPE, ds, step, n_workers=R))
            fn = pr.sync_step if (step + 1) % 2 == 0 else pr.local_step
            plane, state, _ = fn(plane, state, b)
        return pr, np.asarray(plane), {k: np.asarray(v)
                                       for k, v in state.items()}

def trim(a, b):
    n = min(a.shape[-1], b.shape[-1])
    big = a if a.shape[-1] > n else b
    assert not np.asarray(big[..., n:]).any(), "nonzero shard tail"
    return a[..., :n], b[..., :n]

for comp, pallas in [("", False), ("int8", True), ("int8", False),
                     ("bf16", False)]:
    opt = OptimizerConfig.from_sync(
        SyncConfig(compression=comp, fused=True),
        name="local_adaalter", lr=0.5, H=2, warmup_steps=3,
        use_pallas=pallas, flat=True)
    plan = resolve_plan(CFG, mesh, optimizer="local_adaalter")
    prS, plS, stS = run(opt, plan)
    prR, plR, stR = run(opt, dataclasses.replace(plan, tp_axis=""))
    assert prS.n_shards == 2 and prR.n_shards == 1, (prS.n_shards,
                                                     prR.n_shards)
    a, b = trim(plS, plR)
    assert (a == b).all(), (comp, pallas, float(np.abs(a - b).max()))
    for k in sorted(set(stS) | set(stR)):
        x, y = stS[k], stR[k]
        if x.ndim and x.shape[-1] != y.shape[-1] and x.shape[-1] > 4:
            x, y = trim(x, y)
        assert x.shape == y.shape and (x == y).all(), (comp, pallas, k)
    print("ok", comp or "fp32", "pallas" if pallas else "jnp")
print("SHARDED-BITWISE-OK")
"""

_CKPT = r"""
import tempfile
import numpy as np
import jax
from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import SyncConfig
from repro.launch.mesh import worker_mesh
from repro.launch.train import train_loop

CFG = reduced(get_arch("biglstm"), vocab=128)
SHAPE = ShapeConfig(name="t", seq_len=16, global_batch=4, kind="train")
OPT = OptimizerConfig.from_sync(
    SyncConfig(compression="int8", fused=True, policy="adaptive",
               threshold=0.02, h_min=2, h_max=8),
    name="local_adaalter", lr=0.5, H=4, warmup_steps=2,
    use_pallas=True, flat=True)
small = worker_mesh(1, devices=jax.devices()[:1])
big = worker_mesh(2)
with tempfile.TemporaryDirectory() as d:
    r1 = train_loop(CFG, SHAPE, OPT, steps=3, mesh=small, checkpoint_dir=d,
                    checkpoint_every=3, verbose=False)
    # restore mid-H-window (H=4, ckpt at 3) onto the sharded mesh
    r2 = train_loop(CFG, SHAPE, OPT, steps=6, mesh=big, checkpoint_dir=d,
                    checkpoint_every=3, verbose=False)
    assert r2.start_step == 3, r2.start_step
    assert all(np.isfinite(r2.losses)), r2.losses
    # and back: the (2,2) checkpoint at step 6 restores on (1,1)
    r3 = train_loop(CFG, SHAPE, OPT, steps=8, mesh=small, checkpoint_dir=d,
                    verbose=False)
    assert r3.start_step == 6, r3.start_step
    assert all(np.isfinite(r3.losses)), r3.losses
print("CROSS-MESH-CKPT-OK")
"""


@pytest.mark.slow
def test_sharded_flat_bitwise_matches_replicated():
    """(2 workers x 2-way FSDP) flat plane == replicated flat plane,
    bitwise, across {fp32, int8 pallas, int8 jnp, bf16} after 3 steps
    including a mid-window sync."""
    _run(_BITWISE, "SHARDED-BITWISE-OK")


@pytest.mark.slow
def test_flat_checkpoint_restores_across_meshes():
    """Flat checkpoints round-trip (1,1) -> (2,2) -> (1,1), resuming the
    adaptive schedule mid-H-window with finite losses."""
    _run(_CKPT, "CROSS-MESH-CKPT-OK")
