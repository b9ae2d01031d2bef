"""Big LSTM (LSTM-2048-512) — the paper's own evaluation architecture.

2 projected-LSTM layers (Sak et al. LSTMP cell) over 512-dim word
embeddings, full-softmax head. Time dimension via ``lax.scan``; decode is the
single recurrent step (O(1) state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dropout, init_dense


def init_lstm(key, cfg, dtype=jnp.float32):
    h, p, v = cfg.d_model, cfg.lstm_proj, cfg.vocab_size
    ks = jax.random.split(key, 3 + cfg.n_layers)
    params = {
        "embed": (jax.random.normal(ks[0], (v, p)) * 0.05).astype(dtype),
        "head_w": init_dense(ks[1], p, v, dtype=dtype),
        "head_b": jnp.zeros((v,), dtype),
        "cells": [],
    }
    cells = []
    for i in range(cfg.n_layers):
        k = ks[3 + i]
        k1, k2 = jax.random.split(k)
        cells.append({
            "wx": init_dense(k1, p, 4 * h, dtype=dtype),   # input is proj-sized
            "wh": init_dense(k2, p, 4 * h, dtype=dtype),
            "b": jnp.zeros((4 * h,), dtype),
            "wp": init_dense(k, h, p, dtype=dtype),        # recurrent projection
        })
    params["cells"] = cells
    return params


def _cell(cell, x, h_proj, c):
    gates = x @ cell["wx"] + h_proj @ cell["wh"] + cell["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h @ cell["wp"], c


def init_lstm_state(cfg, batch, dtype=jnp.float32):
    return [
        (jnp.zeros((batch, cfg.lstm_proj), dtype),
         jnp.zeros((batch, cfg.d_model), dtype))
        for _ in range(cfg.n_layers)
    ]


def lstm_logits(params, tokens, cfg, *, rng=None, dropout_rate: float = 0.0):
    """tokens: (B,S) -> logits (B,S,V)."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]                        # (B,S,P)
    deterministic = rng is None or dropout_rate == 0.0
    if not deterministic:
        rng_layers = jax.random.split(rng, cfg.n_layers + 1)
        x = dropout(rng_layers[-1], x, dropout_rate, False)

    state = init_lstm_state(cfg, b, x.dtype)

    xs = x.transpose(1, 0, 2)                              # (S,B,P)
    for li, cell in enumerate(params["cells"]):
        def step(carry, xt, cell=cell):
            hp, c = carry
            hp, c = _cell(cell, xt, hp, c)
            return (hp, c), hp
        with jax.named_scope("lstmp"):
            _, ys = jax.lax.scan(step, state[li], xs)
            if not deterministic:
                ys = dropout(rng_layers[li], ys, dropout_rate, False)
            xs = ys + xs if li > 0 else ys                 # residual after first layer
    out = xs.transpose(1, 0, 2)                            # (B,S,P)
    with jax.named_scope("head"):
        return out @ params["head_w"] + params["head_b"]


def lstm_hidden_step(params, token, state, cfg):
    """One recurrent step WITHOUT the softmax head.

    token: (B,1) int32; state: list[(h_proj, c)] -> (h (B,P), state).
    """
    x = params["embed"][token[:, 0]]
    new_state = []
    h = x
    for li, cell in enumerate(params["cells"]):
        hp, c = _cell(cell, h, state[li][0], state[li][1])
        new_state.append((hp, c))
        h = hp + h if li > 0 else hp
    return h, new_state


def lstm_decode_step(params, token, state, cfg):
    """token: (B,1) int32; state: list[(h_proj, c)] -> (logits (B,1,V), state)."""
    h, new_state = lstm_hidden_step(params, token, state, cfg)
    logits = h @ params["head_w"] + params["head_b"]
    return logits[:, None], new_state
