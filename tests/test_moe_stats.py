"""MoE observability: drop-rate / load-imbalance stats through the stack.

The convention under test (ISSUE 10): a path that cannot measure reports
NaN — never a fake 0.0 — and NaN becomes ``null`` only at the JSON
boundary (``RuntimeMetrics.snapshot`` via ``nan_to_none``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import ModelConfig
from repro.models import model as model_lib
from repro.models.layers import moe
from repro.models.model import FwdCtx
from repro.runtime.metrics import RuntimeMetrics
from repro.train.optim import AdamWConfig, adamw_init
from repro.train.step import make_train_step


def _moe_cfg(**over):
    base = dict(name="t", family="moe", n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=64, vocab_size=64,
                ffn_pattern=("moe",), n_experts=4, top_k=2,
                dtype="float32", param_dtype="float32")
    base.update(over)
    return ModelConfig(**base)


def _x(cfg, B=2, S=16, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model))


def test_capacity_stats_drop_and_imbalance():
    cfg = _moe_cfg()
    params = moe.init(jax.random.PRNGKey(1), cfg)
    x = _x(cfg)
    # tight capacity must drop assignments; generous capacity must not
    _, _, tight = moe.apply_capacity(params, x, cfg, capacity_factor=0.25)
    y, lb, loose = moe.apply_capacity(params, x, cfg, capacity_factor=8.0)
    assert 0.0 < float(tight["drop_rate"]) <= 1.0
    assert float(loose["drop_rate"]) == 0.0
    for st in (tight, loose):
        imb = float(st["imbalance"])
        assert np.isfinite(imb) and imb >= 0.0
    # a capacity that drops nothing gives the dense oracle's output and loss
    y2, lb2, _ = moe.apply_dense(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(lb), float(lb2))


def test_dense_and_chunked_stats():
    cfg = _moe_cfg()
    params = moe.init(jax.random.PRNGKey(2), cfg)
    x = _x(cfg)
    _, _, st = moe.apply_dense(params, x, cfg)
    assert float(st["drop_rate"]) == 0.0          # dense never drops
    assert np.isfinite(float(st["imbalance"]))
    _, _, stc = moe.apply_capacity_chunked(params, x, cfg,
                                           capacity_factor=0.5,
                                           chunk_tokens=8)
    assert 0.0 <= float(stc["drop_rate"]) <= 1.0
    assert np.isfinite(float(stc["imbalance"]))


def test_forward_aux_carries_moe_stats():
    cfg = _moe_cfg()
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, 64)
    ctx = FwdCtx(mode="train", attn_impl="naive", capacity_factor=0.5)
    _, _, aux = model_lib.forward(params, cfg, tokens=toks, ctx=ctx)
    assert 0.0 <= float(aux["moe_drop_rate"]) <= 1.0
    assert np.isfinite(float(aux["moe_imbalance"]))
    # a model with no MoE layers reports NaN, not a fake 0.0
    dense = _moe_cfg(ffn_pattern=("dense",), n_experts=0, top_k=0,
                     family="dense")
    dparams = model_lib.init(jax.random.PRNGKey(0), dense)
    _, _, daux = model_lib.forward(dparams, dense, tokens=toks, ctx=ctx)
    assert np.isnan(float(daux["moe_drop_rate"]))
    assert np.isnan(float(daux["moe_imbalance"]))


@pytest.mark.parametrize("has_moe", [True, False])
def test_train_step_metrics_keys(has_moe):
    cfg = _moe_cfg() if has_moe else _moe_cfg(ffn_pattern=("dense",),
                                              n_experts=0, top_k=0,
                                              family="dense")
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig()
    opt = adamw_init(params)
    step = make_train_step(cfg, opt_cfg,
                           ctx=FwdCtx(mode="train", attn_impl="naive",
                                      capacity_factor=0.5))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 2, 16), 1, 64)
    batch = {"tokens": toks, "labels": toks}
    _, _, metrics = step(params, opt, batch, 1e-3)
    assert set(metrics) >= {"loss", "moe_drop_rate", "moe_imbalance"}
    assert np.isfinite(float(metrics["loss"]))
    if has_moe:
        assert 0.0 <= float(metrics["moe_drop_rate"]) <= 1.0
        assert np.isfinite(float(metrics["moe_imbalance"]))
    else:
        assert np.isnan(float(metrics["moe_drop_rate"]))
        assert np.isnan(float(metrics["moe_imbalance"]))


def test_runtime_metrics_record_moe_nan_to_none():
    m = RuntimeMetrics(window=8)
    snap = m.snapshot()
    assert snap["moe_drop_rate_mean"] is None        # empty window -> null
    assert snap["moe_imbalance_max"] is None
    m.record_moe(float("nan"), float("nan"))         # NaN observations skipped
    snap = m.snapshot()
    assert snap["moe_drop_rate_mean"] is None
    assert snap["moe_imbalance_max"] is None
    m.record_moe(0.1, 0.5)
    m.record_moe(0.3, 1.5)
    m.record_moe(float("nan"), 0.25)                 # per-field skip
    snap = m.snapshot()
    assert snap["moe_drop_rate_mean"] == pytest.approx(0.2)
    assert snap["moe_drop_rate_last"] == pytest.approx(0.3)
    assert snap["moe_imbalance_max"] == pytest.approx(1.5)
    assert snap["moe_imbalance_mean"] == pytest.approx(0.75)
