"""Per-architecture smoke tests (assignment requirement).

Every assigned architecture instantiates a REDUCED family member
(2 layers, d_model<=512, <=4 experts) and runs one forward + one train step
on CPU, asserting output shapes and the absence of NaNs. The full-size
configs are exercised via the dry-run only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCHS, OptimizerConfig, ShapeConfig, get_arch,
                           reduced)
from repro.data import SyntheticLM, make_train_batch
from repro.launch.steps import build_train_programs
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.models import build_model

SEQ, BATCH, VOCAB = 64, 4, 512


def _shape():
    return ShapeConfig(name="smoke", seq_len=SEQ, global_batch=BATCH,
                       kind="train")


def _batch(cfg, seed=0):
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SEQ, n_workers=1,
                     seed=seed)
    return {k: jnp.asarray(v) for k, v in
            make_train_batch(cfg, _shape(), ds, 0).items()}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_and_finiteness(arch):
    cfg = reduced(get_arch(arch), vocab=VOCAB)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512 and cfg.n_experts <= 4
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = model.logits_fn(params, batch)
    assert logits.shape == (BATCH, SEQ, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    loss, metrics = model.loss_fn(params, batch)
    assert np.isfinite(float(loss)), arch
    # random init should predict near-uniform: loss ~ log(V)
    assert float(loss) < np.log(cfg.vocab_size) * 1.5 + 1.0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_one_train_step(arch):
    cfg = reduced(get_arch(arch), vocab=VOCAB)
    mesh = worker_mesh()
    opt_cfg = OptimizerConfig(name="local_adaalter", lr=0.3, H=2,
                              warmup_steps=0)
    with mesh:
        plan = resolve_plan(cfg, mesh, optimizer="local_adaalter")
        programs = build_train_programs(cfg, _shape(), opt_cfg, mesh, plan)
        params, opt_state = programs.init_fn(jax.random.PRNGKey(0))
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SEQ,
                         n_workers=max(programs.n_workers, 1), seed=0)
        batch = jax.tree_util.tree_map(jnp.asarray, make_train_batch(
            cfg, _shape(), ds, 0,
            n_workers=programs.n_workers if programs.is_local else 0))
        before = [np.asarray(leaf, np.float32)
                  for leaf in jax.tree_util.tree_leaves(params)]
        p1, s1, metrics = programs.local_step(params, opt_state, batch)
        assert np.isfinite(float(metrics["loss"])), arch
        after = [np.asarray(leaf, np.float32)
                 for leaf in jax.tree_util.tree_leaves(p1)]
        for leaf in after:
            assert np.isfinite(leaf).all(), arch
        # params actually moved
        assert any(a.size > 1 and not np.array_equal(a, b)
                   for a, b in zip(before, after)), arch
