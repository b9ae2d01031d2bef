"""Sharding rules and specs: resolution, shape-safety, worker-axis handling.

Includes the regression test for the worker-axis off-by-one (the spec used
to gain a leading None and silently lose its 'model' entry, replicating
every FFN weight across the TP axis — caught by the dry-run roofline).
"""
import subprocess
import sys

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import ParallelismPlan
from repro.models import build_model
from repro.sharding.partition import ShardingRules
from repro.sharding.specs import param_shardings, shape_safe_spec

MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(cfg, plan, mesh, with_workers):
    rules = ShardingRules(mesh, plan)
    model = build_model(cfg)
    ab = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if with_workers:
        R = 1
        for a in plan.local_axes:
            R *= mesh.shape[a]
        ab = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((R,) + l.shape, l.dtype), ab)
    sh = param_shardings(rules, ab, with_workers=with_workers)
    flat = {}
    for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", "")))
                        for p in path)
        flat[name] = s.spec
    return flat


def test_worker_axis_specs_regression():
    """wq/w1/w2 must keep their 'model' axis when a worker axis is prepended."""
    cfg = get_arch("qwen2-7b")
    plan = ParallelismPlan(local_axes=("data",), grad_axes=(), fsdp_axes=())
    flat = _specs(cfg, plan, MESH, with_workers=True)
    # stacked blocks: leading (worker, layer) axes, then the weight body
    assert flat["blocks/0/mlp/w1"] == P("data", None, None, "model")
    assert flat["blocks/0/mlp/w2"] == P("data", None, "model", None)
    assert flat["blocks/0/attn/wq"] == P("data", None, None, "model")
    assert flat["blocks/0/attn/wo"] == P("data", None, "model", None)
    assert flat["embed"] == P("data", "model", None)
    assert flat["lm_head"] == P("data", None, "model")


def test_sync_plan_specs_no_worker_axis():
    cfg = get_arch("llama3-405b")
    plan = ParallelismPlan(local_axes=(), grad_axes=("data",),
                           fsdp_axes=("data",))
    flat = _specs(cfg, plan, MESH, with_workers=False)
    # FSDP: embed dim of weights sharded over data; TP over model
    assert flat["blocks/0/mlp/w1"] == P(None, "data", "model")
    assert flat["blocks/0/attn/wo"] == P(None, "model", "data")


def test_multi_pod_worker_tuple():
    cfg = get_arch("qwen2-7b")
    plan = ParallelismPlan(local_axes=("pod", "data"), grad_axes=(),
                           fsdp_axes=())
    flat = _specs(cfg, plan, POD_MESH, with_workers=True)
    assert flat["blocks/0/mlp/w1"] == P(("pod", "data"), None, None, "model")


def test_shape_safe_drops_non_dividing_axes():
    spec = shape_safe_spec((28, 128), P("model", None), MESH)   # 28 % 16 != 0
    assert spec == P(None, None)
    spec = shape_safe_spec((32, 128), P("model", None), MESH)
    assert spec == P("model", None)


def test_shape_safe_partial_tuple():
    # ('pod','data') over dim 4: pod(2) divides, data(16) doesn't -> keep pod
    spec = shape_safe_spec((4, 8), P(("pod", "data"), None), POD_MESH)
    assert spec == P("pod", None)


def test_moe_expert_axis():
    cfg = get_arch("phi3.5-moe-42b-a6.6b")
    plan = ParallelismPlan(local_axes=(), grad_axes=("data",),
                           fsdp_axes=("data",))
    flat = _specs(cfg, plan, MESH, with_workers=False)
    assert flat["blocks/0/moe/w1"] == P(None, "model", "data", None)


# --------------------------------------------------------------------------- #
# Numerical equivalence of the SHARDED local optimizer vs the single-device
# reference, on a real 4-device host mesh (subprocess: device count must be
# set before jax initializes).
# --------------------------------------------------------------------------- #
_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import OptimizerConfig, ShapeConfig, get_arch, reduced
from repro.configs.base import ParallelismPlan
from repro.launch.mesh import auto_mesh
from repro.launch.steps import build_train_programs
from repro.data import SyntheticLM, make_train_batch

cfg = reduced(get_arch("minitron-4b"), n_layers=2, d_model=128, vocab=128)
cfg = dataclasses.replace(cfg, param_dtype="float32")
shape = ShapeConfig(name="t", seq_len=32, global_batch=8, kind="train")
opt_cfg = OptimizerConfig(name="local_adaalter", lr=0.3, H=2, warmup_steps=0)

def run(mesh_shape, axes, plan):
    mesh = auto_mesh(mesh_shape, axes)
    with mesh:
        pr = build_train_programs(cfg, shape, opt_cfg, mesh, plan)
        params, state = pr.init_fn(jax.random.PRNGKey(0))
        ds = SyntheticLM(vocab_size=128, seq_len=32, n_workers=2, seed=0)
        losses = []
        for step in range(4):
            b = make_train_batch(cfg, shape, ds, step, n_workers=2)
            b = jax.tree_util.tree_map(jnp.asarray, b)
            fn = pr.sync_step if (step+1) % 2 == 0 else pr.local_step
            params, state, m = fn(params, state, b)
            losses.append(float(m["loss"]))
        return losses, jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), params)

plan_sharded = ParallelismPlan(local_axes=("data",), grad_axes=(), fsdp_axes=())
l1, p1 = run((2, 2), ("data", "model"), plan_sharded)
l2, p2 = run((2, 1), ("data", "model"), plan_sharded)   # no TP
for a, b in zip(l1, l2):
    assert abs(a - b) < 2e-4, (l1, l2)
flat1 = jax.tree_util.tree_leaves(p1)
flat2 = jax.tree_util.tree_leaves(p2)
for a, b in zip(flat1, flat2):
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
print("EQUIV-OK")
"""


@pytest.mark.slow
def test_sharded_equivalence_subprocess():
    r = subprocess.run([sys.executable, "-c", _EQUIV_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "EQUIV-OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
