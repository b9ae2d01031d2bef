"""Matrix products for the reference and for its control.

``highest`` is float32 at full precision: on a TPU a float32 product runs
in lower precision unless told otherwise. ``fp8`` is the control, the
step below the bfloat16 the configurations state: both operands of every
product, in the forward and in the backward pass, are scaled per tensor
onto float8 e4m3 (max 448) and rounded there; products and sums stay
float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def to_fp8(x):
    """x rounded onto float8 e4m3 under a per-tensor scale, as float32."""
    scale = jnp.max(jnp.abs(x)) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(a, b):
    return highest(to_fp8(a), to_fp8(b))


def _fp8_fwd(a, b):
    return fp8(a, b), (a, b)


def _fp8_bwd(res, g):
    a, b = res
    gq = to_fp8(g)
    return highest(gq, to_fp8(b).T), highest(to_fp8(a).T, gq)


fp8.defvjp(_fp8_fwd, _fp8_bwd)

PRODUCTS = {"highest": highest, "fp8": fp8}
