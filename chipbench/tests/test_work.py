"""The work the roofline and MFU metrics divide by, checked on the CPU."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import work

ROOT = Path(__file__).resolve().parents[2]
BIGLSTM = json.loads((ROOT / "chipbench/configs/biglstm.json").read_text())
VOCAB8 = json.loads((ROOT / "chipbench/configs/biglstm-vocab8.json")
                    .read_text())


def test_flops_per_token_matches_the_formula():
    # 3 x (2*P*V + n_layers * 2*(2*P*4D + D*P)), no embedding gather
    P, V, D, L = 512, 793_471, 2048, 2
    want = 3 * (2 * P * V + L * 2 * (2 * P * 4 * D + D * P))
    assert work.flops_per_token(BIGLSTM) == want
    assert work.flops_per_token(BIGLSTM) == pytest.approx(2.5508e9, rel=1e-4)
    assert work.flops_per_token(VOCAB8) == pytest.approx(0.41794e9, rel=1e-4)


def test_flops_per_token_matches_xla_cost_of_a_reduced_forward():
    """XLA's own count of the program's forward pass, at a reduced size
    where the matmuls dominate, lies within 3% above a third of the
    formula (the rest is the cell's and the softmax's elementwise math).
    One position per sequence: XLA counts a loop's body once, whatever
    its trip count, so the scan over time must run once."""
    from repro.configs import get_arch
    from repro.models import build_model
    cfg = dataclasses.replace(get_arch("biglstm"), d_model=256,
                              lstm_proj=128, vocab_size=2048,
                              param_dtype="float32")
    model = build_model(cfg)
    B, S = 64, 1
    tokens = jnp.zeros((B, S), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cost = jax.jit(lambda p, t: model.logits_fn(p, {"tokens": t})).lower(
        params, tokens).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    small = {"family": "lstm", "n_layers": 2, "d_model": 256,
             "lstm_proj": 128, "vocab_size": 2048, "param_dtype": "float32"}
    fwd = work.flops_per_token(small) / 3 * B * S
    assert fwd <= cost["flops"] <= 1.03 * fwd, (cost["flops"], fwd)


def test_roofline_bytes_count_model_dtypes():
    n = work.n_params(BIGLSTM)
    assert n == 832_198_527
    # bf16 x, G read + x written; fp32 B2_sync, B2_local read + B2_local
    assert work.update_bytes(BIGLSTM) == n * (3 * 2 + 3 * 4)
    # [params | B2] payload: read it and its residual, write wire + residual
    assert work.ef_encode_bytes(BIGLSTM) == n * ((2 * 2 + 2 * 4) + 4 * 4)
    assert math.isclose(work.update_bytes(VOCAB8) / 1e9, 2.17, rel_tol=1e-2)


def test_peaks_are_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
