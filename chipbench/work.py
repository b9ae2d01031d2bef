"""Operations and bytes the training step's work needs, from shapes alone.

FLOPs per token come from the family's reference (``models/<family>.py``),
counted as 3 x the forward matmuls: recomputed work is not counted. Bytes
are what the algorithm must move through HBM at the configuration's
dtypes (bf16 parameters and gradients, fp32 B² and residuals), with no tile
padding and no storage choice of the implementation, so a roofline share
reads the same work whatever layout runs it.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench.reference import family_module

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def flops_per_token(model: dict) -> float:
    return family_module(model["family"]).flops_per_token(model)


def n_params(model: dict) -> int:
    init = family_module(model["family"]).init
    shapes = jax.eval_shape(lambda k: init(model, k), jax.random.PRNGKey(0))
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))


def param_bytes(model: dict) -> int:
    return jnp.dtype(model["param_dtype"]).itemsize


def update_bytes(model: dict) -> float:
    """One worker's AdaAlter local update: read x, G (param dtype) and
    B²_sync, B²_local (fp32); write x and B²_local."""
    pb = param_bytes(model)
    return n_params(model) * (3 * pb + 3 * F32)


def ef_encode_bytes(model: dict) -> float:
    """One worker's error-feedback encode of one sync round, over the
    [params ‖ B²] payload: read the payload and its fp32 residual, write the
    decoded wire value (payload dtype) and the new residual."""
    pb = param_bytes(model)
    return n_params(model) * ((2 * pb + 2 * F32) + 4 * F32)
