"""Quantized sync subsystem: kernel-vs-oracle, error feedback, accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig
from repro.core import optimizers as opt
from repro.core.comm import payload_bytes, sync_bytes_per_step
from repro.kernels.quantize import (BLOCK, dequantize, fake_quantize,
                                    quantize)
from repro.kernels.ref import dequantize_blocks_ref, quantize_blocks_ref
from repro.kernels.tiling import to_blocks

SHAPES = [
    (100,),                  # sub-block 1-D (padded path)
    (256,),                  # exactly one block
    (3000,),                 # non-multiple 1-D
    (4, 1000),               # batched leaf (worker axis)
    (2, 3, 130),             # 3-D leaf
    (600, 256),              # > one grid tile when tile_blocks is small
]


def _mk(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return (x * 0.5).astype(dtype)


# --------------------------------------------------------------------------- #
# kernel == oracle
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_kernel_matches_oracle(shape, dtype):
    x = _mk(shape, dtype, sum(shape) + len(shape))
    bnd = 1 if len(shape) > 1 else 0
    qk, sk = quantize(x, batch_ndim=bnd, use_pallas=True)
    qr, sr = quantize(x, batch_ndim=bnd, use_pallas=False)
    assert qk.dtype == jnp.int8 and sk.dtype == jnp.float32
    # scales may differ by 1 ulp (interpret-mode fusion); q by 1 LSB then
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    assert np.abs(np.asarray(qk, np.int32) - np.asarray(qr, np.int32)).max() <= 1
    yk = dequantize(qk, sk, x.shape, batch_ndim=bnd, use_pallas=True)
    yr = dequantize(qr, sr, x.shape, batch_ndim=bnd, use_pallas=False)
    # a 1-LSB q difference moves the dequant by at most one scale step
    step = float(np.max(np.asarray(sr)))
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-5, atol=step * 1.01)


@pytest.mark.parametrize("shape", SHAPES)
def test_roundtrip_error_bounded(shape):
    """|x − dq(q(x))| ≤ scale/2 per block: amax/254 of that block, so
    ≤ 1e-2 wherever the block's amax stays under 2.54."""
    x = _mk(shape, jnp.float32, 7)
    bnd = 1 if len(shape) > 1 else 0
    y = fake_quantize(x, batch_ndim=bnd)
    err = np.abs(np.asarray(y) - np.asarray(x)).max()
    bound = float(np.abs(np.asarray(x)).max()) / 253.0   # scale/2 = amax/254
    assert err <= bound * 1.01, (err, bound)
    xb = np.asarray(to_blocks(x, BLOCK, bnd))
    eb = np.abs(np.asarray(to_blocks(y, BLOCK, bnd)) - xb).max(axis=1)
    amax = np.abs(xb).max(axis=1)
    assert (eb <= amax / 253.0 * 1.01).all()
    assert (eb[amax <= 2.54] <= 1e-2).all()


def test_oracle_blocks_zero_and_extremes():
    x = jnp.concatenate([jnp.zeros((1, BLOCK)),                 # all-zero block
                         jnp.full((1, BLOCK), -3.0),            # constant block
                         jnp.eye(1, BLOCK) * 1e4])              # one spike
    q, s = quantize_blocks_ref(x)
    assert np.all(np.asarray(q[0]) == 0) and float(s[0, 0]) == 0.0
    assert np.all(np.asarray(q[1]) == -127)
    y = dequantize_blocks_ref(q, s)
    np.testing.assert_allclose(np.asarray(y[1]), -3.0, rtol=1e-6)
    assert float(y[2, 0]) == pytest.approx(1e4, rel=1e-6)


# --------------------------------------------------------------------------- #
# compressed_sync: error feedback + identity guarantees
# --------------------------------------------------------------------------- #
def test_no_compression_returns_base():
    base = opt.local_adaalter(H=4)
    assert opt.compressed_sync(base, "") is base
    o = opt.make_optimizer(OptimizerConfig(name="local_adaalter"))
    assert "res_params" not in o.init({"w": jnp.zeros(4)})


def test_unknown_compression_raises():
    with pytest.raises(ValueError, match="compression"):
        opt.compressed_sync(opt.local_adaalter(), "fp4")


def test_compression_rejected_for_sync_optimizers():
    """Silently ignoring it would misreport comm volume ~4x (train_loop
    feeds cfg.compression straight into sync_bytes_per_step)."""
    for name in ("sgd", "adagrad", "adaalter"):
        with pytest.raises(ValueError, match="local optimizer"):
            opt.make_optimizer(OptimizerConfig(name=name, compression="int8"))


def test_residual_is_exact_quantization_error():
    """After a sync, wire + residual must reconstruct params + old residual."""
    o = opt.make_optimizer(OptimizerConfig(
        name="local_adaalter", lr=0.3, H=1, warmup_steps=0,
        compression="int8"))
    params = {"w": jnp.asarray(np.random.default_rng(0).normal(size=500),
                               jnp.float32)}
    state = o.init(params)
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=500) * 0.1,
                          jnp.float32)}
    params, state = o.local_step(g, state, params)
    pre_sync = np.asarray(params["w"]).copy()
    synced, state = o.sync(params, state)       # identity mean_fn (1 worker)
    # error-feedback identity: sent value + residual == true value
    np.testing.assert_allclose(
        np.asarray(synced["w"]) + np.asarray(state["res_params"]["w"]),
        pre_sync, rtol=0, atol=1e-6)
    # residuals bounded by half a quantization step
    amax = np.abs(pre_sync).max()
    assert np.abs(np.asarray(state["res_params"]["w"])).max() <= amax / 253.0


def test_local_step_preserves_residuals_and_matches_base():
    o = opt.make_optimizer(OptimizerConfig(
        name="local_adaalter", lr=0.3, H=4, warmup_steps=0,
        compression="int8"))
    base = opt.local_adaalter(lr=0.3, H=4, warmup_steps=0)
    params = {"w": jnp.ones(300)}
    s, sb = o.init(params), base.init(params)
    res_marker = jax.tree_util.tree_map(lambda z: z + 7.0, s["res_params"])
    s["res_params"] = res_marker
    g = {"w": jnp.full(300, 0.1)}
    (p1, s1), (p2, s2) = o.local_step(g, s, params), base.local_step(g, sb, params)
    # local steps are communication-free: identical to the base optimizer
    np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(p2["w"]))
    np.testing.assert_array_equal(np.asarray(s1["b2_local"]["w"]),
                                  np.asarray(s2["b2_local"]["w"]))
    # ... and the residuals ride along untouched
    np.testing.assert_array_equal(np.asarray(s1["res_params"]["w"]),
                                  np.asarray(res_marker["w"]))


def test_b2_sync_stays_nonnegative():
    o = opt.make_optimizer(OptimizerConfig(
        name="local_adaalter", lr=0.3, H=1, warmup_steps=0,
        compression="int8", b0=0.01))
    params = {"w": jnp.linspace(-1.0, 1.0, 512)}
    state = o.init(params)
    for t in range(3):
        g = {"w": jnp.sin(jnp.arange(512.0) + t) * 0.01}
        params, state = o.local_step(g, state, params)
        params, state = o.sync(params, state)
    assert float(jnp.min(state["b2_sync"]["w"])) >= 0.0


def test_compressed_convergence_tracks_uncompressed():
    """Toy non-IID quadratic, 2 workers: int8+EF within 20% of fp32 sync."""
    n, d, H, T = 2, 512, 4, 64
    target = np.random.default_rng(0).normal(size=d).astype(np.float32)

    def mean_fn(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True),
                                       x.shape), tree)

    def run(compression):
        o = opt.make_optimizer(OptimizerConfig(
            name="local_adaalter", lr=0.3, H=H, warmup_steps=0,
            compression=compression))
        params = {"w": jnp.zeros((n, d), jnp.float32)}
        state = jax.vmap(o.init)(params)
        vstep = jax.vmap(o.local_step)
        rng = np.random.default_rng(1)
        for t in range(1, T + 1):
            g = (np.asarray(params["w"]) - target[None]
                 + rng.normal(size=(n, d)) * 0.1)
            params, state = vstep({"w": jnp.asarray(g, jnp.float32)},
                                  state, params)
            if t % H == 0:
                params, state = o.sync(params, state, mean_fn)
        return float(np.mean((np.asarray(params["w"]) - target[None]) ** 2))

    l_fp32, l_int8 = run(""), run("int8")
    assert l_int8 < l_fp32 * 1.2 + 1e-4, (l_fp32, l_int8)


def test_compressed_sync_pallas_path():
    """cfg.use_pallas routes quantization through the Pallas kernels."""
    o = opt.make_optimizer(OptimizerConfig(
        name="local_adaalter", lr=0.3, H=1, warmup_steps=0,
        compression="int8", use_pallas=True))
    params = {"w": jnp.asarray(np.random.default_rng(3).normal(size=600),
                               jnp.float32)}
    state = o.init(params)
    g = {"w": jnp.full(600, 0.05)}
    params, state = o.local_step(g, state, params)
    pre = np.asarray(params["w"]).copy()
    synced, state = o.sync(params, state)
    np.testing.assert_allclose(
        np.asarray(synced["w"]) + np.asarray(state["res_params"]["w"]),
        pre, rtol=0, atol=1e-6)


# --------------------------------------------------------------------------- #
# communication accounting
# --------------------------------------------------------------------------- #
def test_payload_bytes_model():
    assert payload_bytes(256) == 1024.0                       # fp32
    assert payload_bytes(256, compression="int8") == 260.0    # 256 + 1 scale
    with pytest.raises(ValueError, match="compression"):
        payload_bytes(256, compression="fp4")


def test_sync_bytes_compression_ratio():
    """int8 + per-256 fp32 scales must shrink 2P/H by ~4x (to ~P/2H)."""
    P, H = 10_000_000, 4
    full = sync_bytes_per_step("local_adaalter", P, H)
    comp = sync_bytes_per_step("local_adaalter", P, H, compression="int8")
    assert full / comp == pytest.approx(4.0 / (1.0 + 4.0 / 256))  # ~3.94
    assert comp == pytest.approx(2.0 * P * (1 + 4 / 256) / H)
