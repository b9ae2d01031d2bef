"""Serving path: prefill/decode consistency, cache geometry, per-family decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch, get_shape, reduced
from repro.launch.mesh import worker_mesh
from repro.launch.serving import (build_serve_programs, cache_geometry,
                                  decode_cache_specs, serve_batch_specs)
from repro.models import build_model

DECODE_FAMS = ["qwen2-7b", "mamba2-370m", "hymba-1.5b",
               "phi3.5-moe-42b-a6.6b", "llama-3.2-vision-11b",
               "seamless-m4t-large-v2", "biglstm"]


def _mesh():
    return worker_mesh()


@pytest.mark.parametrize("arch", DECODE_FAMS)
def test_decode_step_per_family(arch):
    cfg = reduced(get_arch(arch))
    shape = ShapeConfig(name="decode_32k", seq_len=64, global_batch=2,
                        kind="decode")
    with _mesh() as mesh:
        sp = build_serve_programs(cfg, shape, mesh)
        params = sp.init_fn(jax.random.PRNGKey(0))
        cache = jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape, l.dtype),
                                       decode_cache_specs(cfg, shape))
        tok = jnp.ones((2, 1), jnp.int32)
        pos = jnp.asarray([3, 5], jnp.int32)
        logits, cache2 = sp.decode_step(params, cache, tok, pos)
        assert logits.shape == (2, 1, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_prefill_decode_consistency_dense():
    """Greedy decode over a teacher-forced prompt must reproduce the
    full-sequence logits position by position (same math, cached path)."""
    cfg = reduced(get_arch("phi4-mini-3.8b"), n_layers=2, d_model=128,
                  vocab=128)
    cfg = dataclasses.replace(cfg, param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 128)
    full = model.logits_fn(params, {"tokens": tokens})          # (B,S,V)

    cache = model.init_cache(B, S)
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(S):
        logits, cache = step(params, cache, tokens[:, t:t + 1],
                             jnp.full((B,), t, jnp.int32))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_prefill_decode_consistency_ssm():
    cfg = reduced(get_arch("mamba2-370m"), n_layers=2, vocab=128)
    cfg = dataclasses.replace(cfg, param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 128)
    full = model.logits_fn(params, {"tokens": tokens})
    cache = model.init_cache(B, S)
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(S):
        logits, cache = step(params, cache, tokens[:, t:t + 1],
                             jnp.full((B,), t, jnp.int32))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------- cache geometry (long_500k policy) ------------------------ #
def test_long500k_dense_uses_window():
    cfg = get_arch("qwen2-7b")
    cache_len, window, _ = cache_geometry(cfg, get_shape("long_500k"))
    assert window > 0 and cache_len == window            # bounded state
    assert cache_len < 524288


def test_long500k_ssm_has_no_kv_cache():
    cfg = get_arch("mamba2-370m")
    cache_len, window, _ = cache_geometry(cfg, get_shape("long_500k"))
    assert cache_len == 0
    specs = decode_cache_specs(cfg, get_shape("long_500k"))
    leaves = jax.tree_util.tree_leaves(specs)
    total = sum(np.prod(l.shape) for l in leaves)
    # O(1) state: far smaller than the 524k context
    assert total < 524288 * 64


def test_decode32k_full_cache():
    cfg = get_arch("phi4-mini-3.8b")
    cache_len, window, _ = cache_geometry(cfg, get_shape("decode_32k"))
    assert cache_len == 32768 and window == 0


def test_encdec_cross_cache_len():
    cfg = get_arch("seamless-m4t-large-v2")
    _, _, cross = cache_geometry(cfg, get_shape("decode_32k"))
    assert cross == 32768


def test_serve_batch_specs_modalities():
    vlm = get_arch("llama-3.2-vision-11b")
    specs = serve_batch_specs(vlm, get_shape("prefill_32k"))
    assert "image_embeds" in specs["prefill"]
    audio = get_arch("seamless-m4t-large-v2")
    specs = serve_batch_specs(audio, get_shape("prefill_32k"))
    assert "audio_frames" in specs["prefill"]
