"""Unified model API: ``build_model(cfg)`` -> init / loss_fn / prefill / decode.

Families:
  dense / moe / hybrid / ssm : decoder-only LM over tokens
  vlm                        : decoder LM + cross-attn to stubbed patch embeds
  audio                      : encoder-decoder over stubbed frame embeds
  lstm                       : the paper's Big LSTM
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import lstm as lstm_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import init_dense, rms_norm
from repro.sharding.partition import constraint


def _compute_dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------- #
@jax.named_scope("xent")
def softmax_xent(logits, labels, mask=None):
    """Mean token cross-entropy in fp32. logits: (B,S,V), labels: (B,S).

    The gold logit is a masked sum over the vocabulary, which XLA fuses
    into the logsumexp's reductions. A gather, from the fp32 logits or
    from the logits' own dtype, makes XLA write out a full (B,S,V) copy of
    the logits just to read one entry per token back."""
    x = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(x, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], x, 0.0), axis=-1)
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# Beyond-paper (§Perf): vocab-shard-safe xent. take_along_axis on a
# model-sharded vocab axis makes GSPMD gather the full (B,S,V) f32 logits;
# the iota-compare form fuses into a single sharded reduction. The custom
# VJP emits the (softmax - onehot) cotangent in the LOGITS dtype (bf16), so
# the lm_head backward matmuls run at bf16 traffic instead of f32.
@jax.custom_vjp
def fused_softmax_xent(logits, labels):
    nll, _ = _fused_xent_fwd_impl(logits, labels)
    return nll


@jax.named_scope("xent")
def _fused_xent_fwd_impl(logits, labels):
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    z = jnp.sum(ex, axis=-1)
    logz = jnp.log(z) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    onehot = (iota == labels[..., None])
    gold = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)
    nll = jnp.mean(logz - gold)
    return nll, (m[..., 0], z)


def _fused_xent_fwd(logits, labels):
    nll, (m, z) = _fused_xent_fwd_impl(logits, labels)
    return nll, (logits, labels, m, z)


@jax.named_scope("xent")
def _fused_xent_bwd(res, g):
    logits, labels, m, z = res
    x = logits.astype(jnp.float32)
    p = jnp.exp(x - m[..., None]) / z[..., None]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    onehot = (iota == labels[..., None]).astype(jnp.float32)
    n_tokens = labels.size
    dlogits = (g / n_tokens) * (p - onehot)
    return dlogits.astype(logits.dtype), None


fused_softmax_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


@dataclasses.dataclass
class Model:
    cfg: Any
    init: Callable[[jax.Array], Dict]
    loss_fn: Callable[..., Any]          # (params, batch, rng=None) -> (loss, metrics)
    logits_fn: Callable[..., Any]        # (params, batch) -> logits
    prefill: Callable[..., Any]          # (params, batch) -> (logits, cache)
    decode_step: Callable[..., Any]      # (params, cache, token, pos) -> (logits, cache)
    init_cache: Callable[..., Any]       # (batch_size, cache_len, ctx_lens) -> cache


# --------------------------------------------------------------------------- #
# transformer families
# --------------------------------------------------------------------------- #
def _build_transformer(cfg) -> Model:
    dtype = _compute_dtype(cfg)

    def init(key):
        ks = jax.random.split(key, 6)
        params = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dtype),
            "blocks": tfm.init_stack(ks[1], cfg, dtype,
                                     encdec_dec=cfg.is_encdec),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if cfg.is_encdec:
            params["encoder"] = tfm.init_stack(ks[2], dataclasses.replace(
                cfg, n_layers=cfg.n_encoder_layers, cross_attn_every=0,
                n_experts=0, hybrid=False), dtype)
            params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = init_dense(ks[3], cfg.d_model, cfg.vocab_size,
                                           scale=0.02, dtype=dtype)
        return params

    def _encode(params, batch):
        frames = batch["audio_frames"].astype(dtype)          # (B,F,D) stub frontend
        pos = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])
        enc_cfg = dataclasses.replace(cfg, n_layers=cfg.n_encoder_layers,
                                      cross_attn_every=0, n_experts=0,
                                      hybrid=False)
        h, _, _ = tfm.apply_stack(params["encoder"], enc_cfg, frames, pos,
                                  ctx={"causal": False})
        return rms_norm(h, params["enc_norm"], cfg.norm_eps)

    def _ctx(params, batch):
        if cfg.is_encdec:
            return {"cross_src": _encode(params, batch)}
        if cfg.cross_attn_every:
            return {"cross_src": batch["image_embeds"].astype(dtype)}
        return {}

    def _trunk(params, batch, *, window=0, collect_cache=False, remat="none"):
        tokens = batch["tokens"]
        x = params["embed"][tokens].astype(dtype)
        x = constraint(x, ("batch", "seq_sp", "embed"))
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        ctx = _ctx(params, batch)
        x, aux, caches = tfm.apply_stack(
            params["blocks"], cfg, x, pos, ctx, window=window,
            collect_cache=collect_cache, encdec_dec=cfg.is_encdec, remat=remat)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux, caches

    def _head(params, x):
        w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = x @ w
        return constraint(logits, ("batch", "seq", "vocab"))

    def logits_fn(params, batch):
        x, _, _ = _trunk(params, batch)
        return _head(params, x)

    def loss_fn(params, batch, rng=None, remat: str = "none"):
        x, aux, _ = _trunk(params, batch, remat=remat)
        logits = _head(params, x)
        if getattr(cfg, "fused_xent", False) and "mask" not in batch:
            loss = fused_softmax_xent(logits, batch["labels"])
        else:
            loss = softmax_xent(logits, batch["labels"], batch.get("mask"))
        return loss + aux, {"xent": loss, "aux": aux}

    def prefill(params, batch, *, window: int = 0):
        x, _, caches = _trunk(params, batch, window=window, collect_cache=True)
        logits = _head(params, x[:, -1:])
        return logits, caches

    def init_cache(batch_size: int, cache_len: int, *, windowed: bool = False,
                   cross_len: int = 0):
        """Zero-initialized stacked decode cache (pre-allocated ring buffers)."""
        kinds = tfm.group_kinds(cfg)
        g = cfg.n_layers // len(kinds)
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        entries = []
        for kind in kinds:
            c: Dict[str, Any] = {}
            if kind in ("self_dense", "self_moe", "hybrid"):
                c["kv"] = (jnp.zeros((g, batch_size, cache_len, kv, hd), dtype),
                           jnp.zeros((g, batch_size, cache_len, kv, hd), dtype))
            if kind in ("ssm", "hybrid"):
                s, ct = ssm_mod.init_ssm_state(cfg, batch_size, dtype)
                c["ssm"] = (jnp.zeros((g,) + s.shape, s.dtype),
                            jnp.zeros((g,) + ct.shape, ct.dtype))
            if kind == "cross" or cfg.is_encdec:
                c["xkv"] = (jnp.zeros((g, batch_size, cross_len, kv, hd), dtype),
                            jnp.zeros((g, batch_size, cross_len, kv, hd), dtype))
            entries.append(c)
        return entries

    def decode_step(params, caches, token, pos, *, window: int = 0):
        """token: (B,1); pos: (B,). Returns (logits (B,1,V), caches)."""
        x = params["embed"][token].astype(dtype)
        kv_leaves = [v for e in caches for k, v in e.items() if k == "kv"]
        spec = attn_mod.KVCacheSpec(
            cache_len=kv_leaves[0][0].shape[2] if kv_leaves else 0,
            windowed=bool(window))
        x, caches = tfm.decode_stack(params["blocks"], cfg, x, pos, caches,
                                     spec=spec)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _head(params, x)
        return logits, caches

    return Model(cfg=cfg, init=init, loss_fn=loss_fn, logits_fn=logits_fn,
                 prefill=prefill, decode_step=decode_step, init_cache=init_cache)


# --------------------------------------------------------------------------- #
# LSTM family
# --------------------------------------------------------------------------- #
def _build_lstm(cfg) -> Model:
    dtype = _compute_dtype(cfg)

    def init(key):
        return lstm_mod.init_lstm(key, cfg, dtype)

    def logits_fn(params, batch):
        return lstm_mod.lstm_logits(params, batch["tokens"], cfg)

    def loss_fn(params, batch, rng=None, remat: str = "none"):
        logits = lstm_mod.lstm_logits(params, batch["tokens"], cfg, rng=rng,
                                      dropout_rate=0.1 if rng is not None else 0.0)
        loss = softmax_xent(logits, batch["labels"], batch.get("mask"))
        return loss, {"xent": loss, "aux": jnp.zeros((), jnp.float32)}

    def prefill(params, batch, *, window: int = 0):
        # Recurrent state built by running the sequence; cache = final state.
        # The softmax head runs ONCE on the final hidden state — computing
        # the 793k-vocab logits at every timestep made the 32k prefill
        # memory term 8,388s/step (caught by the §Roofline table).
        tokens = batch["tokens"]
        B = tokens.shape[0]
        state0 = lstm_mod.init_lstm_state(cfg, B, dtype)
        h0 = jnp.zeros((B, cfg.lstm_proj), dtype)

        def step(carry, tok):
            st, _ = carry
            h, st = lstm_mod.lstm_hidden_step(params, tok[:, None], st, cfg)
            return (st, h), None

        (state, h), _ = jax.lax.scan(step, (state0, h0), tokens.T)
        logits = (h @ params["head_w"] + params["head_b"])[:, None]
        return logits, state

    def init_cache(batch_size: int, cache_len: int, *, windowed: bool = False,
                   cross_len: int = 0):
        return lstm_mod.init_lstm_state(cfg, batch_size, dtype)

    def decode_step(params, caches, token, pos, *, window: int = 0):
        logits, caches = lstm_mod.lstm_decode_step(params, token, caches, cfg)
        return logits, caches

    return Model(cfg=cfg, init=init, loss_fn=loss_fn, logits_fn=logits_fn,
                 prefill=prefill, decode_step=decode_step, init_cache=init_cache)


# --------------------------------------------------------------------------- #
def build_model(cfg) -> Model:
    if cfg.family == "lstm":
        return _build_lstm(cfg)
    return _build_transformer(cfg)
