"""Plain float32 reference of the LSTM-2048-512 language model.

Jozefowicz et al., arXiv:1602.02410: word embeddings of size P, a stack of
projected LSTM cells (LSTMP, Sak et al. 2014) with D hidden units whose
output is projected to P, and a full softmax over the vocabulary. The
equations, per layer and time step:

    gates  = x_t W_x + h_{t-1} W_h + b           (4 blocks: i, f, g, o)
    c_t    = sigmoid(f + 1) * c_{t-1} + sigmoid(i) * tanh(g)
    h_t    = (sigmoid(o) * tanh(c_t)) W_p          (projected to P)

with a residual connection around every layer after the first, and
logits = h W_head + b_head. Departures from the paper, which the system
under test shares: no peephole connections, no dropout (the benchmark
trains without it), a forget-gate bias of +1 folded into the equation, and
a full softmax where the paper trained with a sampled one.

The weights are drawn from the seed with the same recipe as the system
under test (``init``), so both start from the same bits. Everything else is
computed here in float32 with full-precision matrix products (or, for the
control, with the lower-precision product the caller passes as ``mm``).
Nothing here imports the system under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# largest logits block the head materialises at once (elements)
HEAD_BLOCK_ELEMS = 1 << 26


def init(cfg: dict, key):
    """Weights in their stored dtype, drawn as the system draws them."""
    h, p, v = cfg["d_model"], cfg["lstm_proj"], cfg["vocab_size"]
    dtype = jnp.dtype(cfg["param_dtype"])
    ks = jax.random.split(key, 3 + cfg["n_layers"])

    def dense(k, d_in, d_out):
        w = jax.random.normal(k, (d_in, d_out), dtype=jnp.float32)
        return (w * (1.0 / d_in) ** 0.5).astype(dtype)

    cells = []
    for i in range(cfg["n_layers"]):
        k = ks[3 + i]
        k1, k2 = jax.random.split(k)
        cells.append({"wx": dense(k1, p, 4 * h), "wh": dense(k2, p, 4 * h),
                      "b": jnp.zeros((4 * h,), dtype), "wp": dense(k, h, p)})
    return {"embed": (jax.random.normal(ks[0], (v, p)) * 0.05).astype(dtype),
            "head_w": dense(ks[1], p, v),
            "head_b": jnp.zeros((v,), dtype),
            "cells": cells}


def flops_per_token(cfg: dict) -> float:
    """Forward and backward matmul FLOPs per token: 3 x the forward's
    2*P*V (head) + n_layers * 2*(2*P*4D + D*P) (gates and projection).
    The embedding gather and the elementwise cell math are not counted."""
    h, p, v = cfg["d_model"], cfg["lstm_proj"], cfg["vocab_size"]
    fwd = 2 * p * v + cfg["n_layers"] * 2 * (2 * p * 4 * h + h * p)
    return 3.0 * fwd


def _highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _head_block(n_tokens: int, vocab: int) -> int:
    """Largest divisor of n_tokens whose logits block stays under
    HEAD_BLOCK_ELEMS elements."""
    cap = max(1, HEAD_BLOCK_ELEMS // vocab)
    for blk in range(min(cap, n_tokens), 0, -1):
        if n_tokens % blk == 0:
            return blk
    return 1


def loss(params, tokens, labels, cfg: dict, mm=_highest):
    """Mean next-token cross-entropy of one worker's batch, float32.

    params: float32 pytree (``init``'s structure); tokens, labels: (B, S).
    The head runs over blocks of tokens, each recomputed in the backward
    pass, so the full (tokens x vocabulary) logits never exist at once."""
    b, s = tokens.shape
    x = params["embed"][tokens]                             # (B, S, P)
    xs = x.transpose(1, 0, 2)                               # (S, B, P)
    for li, cell in enumerate(params["cells"]):
        def step(carry, xt, cell=cell):
            hp, c = carry
            gates = mm(xt, cell["wx"]) + mm(hp, cell["wh"]) + cell["b"]
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            hp = mm(jax.nn.sigmoid(o) * jnp.tanh(c), cell["wp"])
            return (hp, c), hp
        carry = (jnp.zeros((b, cfg["lstm_proj"]), jnp.float32),
                 jnp.zeros((b, cfg["d_model"]), jnp.float32))
        _, ys = jax.lax.scan(step, carry, xs)
        xs = ys + xs if li > 0 else ys
    out = xs.transpose(1, 0, 2).reshape(b * s, -1)          # (N, P)
    n = b * s
    blk = _head_block(n, cfg["vocab_size"])

    @jax.checkpoint
    def block_nll(xb, lb):
        logits = mm(xb, params["head_w"]) + params["head_b"]
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    nll = jax.lax.map(lambda a: block_nll(*a),
                      (out.reshape(n // blk, blk, -1),
                       labels.reshape(n // blk, blk)))
    return jnp.sum(nll) / n
