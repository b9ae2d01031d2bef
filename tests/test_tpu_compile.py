"""Compiles for a described TPU v5e, with no chip attached.

JAX's TPU compiler compiles for a topology it is only told about. These
tests compile the training path's six Pallas entry points in Mosaic at
biglstm's real leaf sizes, and the published model's per-leaf step, whose
memory must fit one v5e chip, and whose cross-entropy must not write an
fp32 copy of the (tokens x vocabulary) logits. The topology is described
inside a fixture (never at import: only one process may load the TPU
library at a time), and the persistent compilation cache is off around
these compiles.
"""
import dataclasses
import functools
import re

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

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s=(]+)\s.*\{\s*$")
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%?\S+ = (.*?) [\w-]+\(")
_FUSED = re.compile(r"\bfusion\(.*calls=%?([\w.-]+)")
_F32 = re.compile(r"f32\[([\d,]*)\]")


def f32_token_vocab_arrays(hlo_text, batch, seq, vocab):
    """The instructions of a compiled program that write an fp32 array
    holding both token dimensions (or their product) and the vocabulary:
    a full-vocabulary fp32 copy of the logits. Instructions inside a
    fusion's body write nothing to memory and are not counted."""
    fused = set(_FUSED.findall(hlo_text))
    found, computation = [], None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None and " = " not in line:
            computation = head.group(1)
            continue
        result = _RESULT.match(line)
        if computation in fused or result is None:
            continue
        for dims in _F32.findall(result.group(1)):
            d = [int(n) for n in dims.split(",") if n]
            if vocab in d and ((batch in d and seq in d) or batch * seq in d):
                found.append(line.strip())
    return found


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
    fp32 wire, batch 8 x seq 20: the local step fits one chip, and writes
    no fp32 (tokens x vocabulary) array; the fp32 B² state of ``head_w``,
    [512, 793471], is no such array."""
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
    text = compiled.as_text()
    assert re.search(rf"f32\[(1,)?512,{BIGLSTM.vocab_size}\]", text)
    assert not f32_token_vocab_arrays(text, 8, 20, BIGLSTM.vocab_size)


def test_share_loss_grad_writes_no_fp32_logits(one_chip):
    """The LSTM loss's value_and_grad at the 1/8 vocabulary share (V =
    99,184), batch 8 x seq 20: no fp32 (tokens x vocabulary) array."""
    model = build_model(SHARE)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((8, 20), jnp.int32, sharding=one_chip)
    batch = {"tokens": tokens, "labels": tokens}
    grad = jax.value_and_grad(lambda p, b: model.loss_fn(p, b)[0])
    text = jax.jit(grad).lower(params, batch).compile().as_text()
    assert not f32_token_vocab_arrays(text, 8, 20, SHARE.vocab_size)
