"""Pallas TPU kernel: fused Mamba-2 SSD chunk scan (forward).

The pure-jnp SSD in ``repro/models/ssm.py`` materializes ~10 chunk-shaped
intermediates per layer in HBM (logdecay/M/seg/chunk_states/...), which is
why mamba2-370m is memory-bound 70:1 at train_4k (§Roofline). This kernel
keeps the recurrent state S (nh, N, hd per batch-head) in VMEM across the
sequential chunk dimension of the grid, so HBM traffic collapses to the x/y
streams plus the per-chunk B/C/dt loads.

Layout: grid = (B, NH, NZ) with the chunk axis LAST and marked "arbitrary"
(sequential) — Pallas TPU keeps scratch alive across sequential grid steps,
which is exactly the cross-chunk state carry. Each step processes one
(chunk, head) tile:

  in:  x (c, hd), B (c, N), C (c, N), dA (c,)           [VMEM blocks]
  scratch: S (N, hd) f32                                 [persists over NZ]
  intra: M = (C B^T) ⊙ exp(cum(dA) outer-diff), y = M @ (x·dt)
  inter: y += exp(cum) · (C @ S);  S = exp(cum_last)·S + B^T diag(seg) xbar

Forward-only: used for the serving/prefill path; training keeps the jnp
path (a bwd kernel is future work — see EXPERIMENTS.md §Perf).
Validated in interpret mode against ``repro.kernels.ref.ssd_ref`` across
shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, da_ref, y_ref, s_ref):
    """One (batch, head, chunk) tile. Shapes:
    x (1,1,1,c,hd), b (1,1,c,N), c (1,1,c,N), da (1,1,1,c); y like x;
    s scratch (N, hd) f32. The D-skip term is elementwise and stays outside.
    """
    nz = pl.program_id(2)

    @pl.when(nz == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)               # (c, hd)  = dt·x pre-scaled
    B = b_ref[0, 0].astype(jnp.float32)                  # (c, N)
    C = c_ref[0, 0].astype(jnp.float32)                  # (c, N)
    dA = da_ref[0, 0, 0].astype(jnp.float32)             # (c,)
    cum = jnp.cumsum(dA)                                 # (c,)

    # intra-chunk dual form
    CB = C @ B.T                                         # (c, c)
    ld = cum[:, None] - cum[None, :]                     # (c, c)
    c_len = x.shape[0]
    tri = jnp.tril(jnp.ones((c_len, c_len), jnp.bool_))
    M = jnp.where(tri, CB * jnp.exp(ld), 0.0)
    y = M @ x                                            # (c, hd)

    # inter-chunk: contribution of the carried state, then update it
    S = s_ref[...]
    y = y + jnp.exp(cum)[:, None] * (C @ S)              # (c, hd)
    seg = jnp.exp(cum[-1] - cum)                         # decay to chunk end
    s_ref[...] = jnp.exp(cum[-1]) * S + B.T @ (seg[:, None] * x)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan(xbar, Bm, Cm, dA, *, interpret: bool = True):
    """Fused SSD forward (no D-skip — that term is elementwise, caller adds).

    xbar: (B, NZ, c, NH, hd) — dt-scaled inputs (x * dt)
    Bm/Cm: (B, NZ, c, N)
    dA:   (B, NZ, c, NH)    — dt * A (negative)
    returns y: (B, NZ, c, NH, hd) fp32
    """
    b, nz, c, nh, hd = xbar.shape
    n = Bm.shape[-1]
    # kernel-friendly layout: head-major so each tile is contiguous
    x_t = xbar.transpose(0, 3, 1, 2, 4)                  # (B, NH, NZ, c, hd)
    da_t = dA.transpose(0, 3, 1, 2)                      # (B, NH, NZ, c)

    grid = (b, nh, nz)
    y = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, c, hd), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda i, j, k: (i, k, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda i, j, k: (i, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, c), lambda i, j, k: (i, j, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, c, hd), lambda i, j, k: (i, j, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, nz, c, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_t, Bm, Cm, da_t)
    return y.transpose(0, 2, 3, 1, 4)                    # (B, NZ, c, NH, hd)
