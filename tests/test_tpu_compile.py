"""Compiles for a described TPU v5e, with no chip attached.

JAX's TPU compiler compiles for a topology it is only told about. These
tests compile the training path's six Pallas entry points in Mosaic at
biglstm's real leaf sizes, and the published model's per-leaf step, whose
memory must fit one v5e chip. The topology is described inside a fixture
(never at import: only one process may load the TPU library at a time),
and the persistent compilation cache is off around these compiles.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import OptimizerConfig, ShapeConfig, get_arch
from repro.configs.base import SyncConfig
from repro.core.flatspace import FlatSpace
from repro.kernels.adaalter_update import (BLOCK_ROWS, LANES,
                                           flat_fused_update, fused_update_2d)
from repro.kernels.quantize import (BLOCK, dequantize_blocks,
                                    quantize_blocks)
from repro.kernels.sync_fused import fused_ef_blocks, flat_ef_blocks
from repro.launch.mesh import resolve_plan, worker_mesh
from repro.launch.steps import build_train_programs, train_batch_specs
from repro.models import build_model

BIGLSTM = get_arch("biglstm")
SHARE = dataclasses.replace(BIGLSTM, vocab_size=99_184)   # 1/8 of the rows
V5E_HBM_BYTES = 15.75e9     # what one v5e chip offers a program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _rows(n_elems: int, multiple: int) -> int:
    rows = -(-n_elems // LANES)
    return rows + (-rows) % multiple


def _share_plane_size() -> int:
    base = jax.eval_shape(build_model(SHARE).init, jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), base)
    return FlatSpace.build(stacked, batch_ndim=1, shards=1,
                           eps=1.0).plane_size


HEAD_W = 512 * BIGLSTM.vocab_size             # biglstm's largest leaf
HEAD_ROWS = _rows(HEAD_W, BLOCK_ROWS)          # its (rows, 128) kernel view
HEAD_BLOCKS = -(-HEAD_W // BLOCK)              # its (blocks, 256) EF view


def _kernel_case(name, sh):
    """(jitted kernel, argument shapes) for one training-path entry point."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    f32, bf16 = jnp.float32, jnp.bfloat16
    scalar = s((), f32)
    if name == "fused_update_2d":              # per-leaf update of head_w
        r = (HEAD_ROWS, LANES)
        return (functools.partial(fused_update_2d, interpret=False),
                (s(r, bf16), s(r, bf16), s(r, f32), s(r, f32), scalar,
                 scalar))
    if name == "fused_ef_blocks":              # per-leaf int8 EF of head_w
        b = (HEAD_BLOCKS, BLOCK)
        return (functools.partial(fused_ef_blocks, interpret=False),
                (s(b, bf16), s(b, f32)))
    if name == "quantize_blocks":
        return (functools.partial(quantize_blocks, interpret=False),
                (s((HEAD_BLOCKS, BLOCK), f32),))
    if name == "dequantize_blocks":
        return (functools.partial(dequantize_blocks, interpret=False),
                (s((HEAD_BLOCKS, BLOCK), jnp.int8),
                 s((HEAD_BLOCKS, 1), f32)))
    psize = _share_plane_size()
    if name == "flat_fused_update":            # the 1/8-share plane
        p = (1, psize)
        return (functools.partial(flat_fused_update, interpret=False),
                (s(p, f32), s(p, f32), s(p, f32), s(p, f32), scalar, scalar,
                 s((psize // LANES, 1), f32)))
    if name == "flat_ef_blocks":               # its [params ‖ B²] payload
        nb = 2 * psize // BLOCK
        return (functools.partial(flat_ef_blocks, interpret=False),
                (s((nb, BLOCK), f32), s((nb, BLOCK), f32), s((nb, 1), f32),
                 s((nb, 1), f32)))
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "fused_update_2d", "flat_fused_update", "fused_ef_blocks",
    "flat_ef_blocks", "quantize_blocks", "dequantize_blocks"])
def test_kernel_compiles_for_mosaic(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_published_biglstm_step_fits_one_chip(topo):
    """biglstm at its published widths (vocabulary 793,471), per-leaf,
    fp32 wire, batch 8 x seq 20: the local step fits one chip."""
    shape = ShapeConfig(name="t", seq_len=20, global_batch=8, kind="train")
    opt = OptimizerConfig.from_sync(SyncConfig(), name="local_adaalter",
                                    lr=0.5, H=2, warmup_steps=100)
    mesh = worker_mesh(devices=topo.devices[:1])
    with mesh:
        plan = resolve_plan(BIGLSTM, mesh, optimizer="local_adaalter")
        pr = build_train_programs(BIGLSTM, shape, opt, mesh, plan)
        batch = train_batch_specs(BIGLSTM, shape, pr.n_workers)
        compiled = pr.local_step.lower(*pr.legacy_abstract,
                                       batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # params (bf16) + b2_sync + b2_local (fp32) are the arguments
    assert ma.argument_size_in_bytes > 8e9, ma
    assert total < V5E_HBM_BYTES, ma
