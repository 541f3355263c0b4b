"""``bench/flops.py`` against XLA's own count of the same step.

XLA's ``cost_analysis`` counts what the compiled program executes, so the
two differ by known amounts, which the test adds back before comparing:

* the program projects every position through the LM head, media ones too,
  then drops them; the required count takes text positions only;
* it computes causal attention over the whole square and masks it; the
  required count takes the lower triangle;
* XLA also counts elementwise work (norms, softmax, activations, the loss),
  which the required count leaves out: about 1% at this size.

The step is built without rematerialization and without a layer scan, and
with one microbatch, so that XLA counts every layer once and nothing twice.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import flops
from bench.harness.train import to_desc
from bench.tests import tiny


def _model():
    m = dict(tiny.MODEL)
    wide = dict(d_model=256, n_heads=4, head_dim=64, d_ff=1024,
                scan_layers=False, remat=False, dtype="float32")
    m["encoder"] = {**m["encoder"], **wide, "n_kv_heads": 4}
    m["llm"] = {**m["llm"], **wide, "n_kv_heads": 2, "vocab_size": 2048}
    m["stub"] = {**m["stub"], "n_tokens": 64}
    m["connector_hidden"] = 512
    m["tokens_per_item_out"] = 16
    return m


def test_required_flops_match_xla_count():
    from repro.models import mllm as mllm_lib
    from repro.models.model import FwdCtx
    from repro.train.step import make_loss_fn

    m = _model()
    rows, t_media, t_text = 2, 128, 96
    desc = to_desc(m)
    loss = make_loss_fn(desc, FwdCtx(mode="train", attn_impl="naive",
                                     remat=False))
    params = jax.eval_shape(lambda k: mllm_lib.init(k, desc),
                            jax.random.PRNGKey(0))
    sd = jax.ShapeDtypeStruct
    batch = {"media_embeds": sd((rows, t_media, 32), jnp.float32),
             "media_mask": sd((rows, t_media), jnp.int32),
             "text_tokens": sd((rows, t_text), jnp.int32),
             "text_mask": sd((rows, t_text), jnp.int32),
             "labels": sd((rows, t_text), jnp.int32)}
    cost = jax.jit(jax.grad(loss)).lower(params, batch).compile()
    xla = cost.cost_analysis()
    xla = (xla[0] if isinstance(xla, list) else xla)["flops"]

    required = flops.step_flops(m, rows, t_media, t_text)
    f = flops.row_forward(m, t_media, t_text)
    t_out = t_media // (t_media // m["tokens_per_item_out"])
    llm = m["llm"]
    media_head = 2.0 * t_out * llm["d_model"] * llm["vocab_size"]
    seq = t_out + t_text
    upper_half = 0.5 * 4.0 * seq * seq * llm["n_heads"] * llm["head_dim"] \
        * llm["n_layers"]
    executed = required + rows * 3.0 * (media_head + upper_half)
    assert f["head"] == pytest.approx(2.0 * t_text * 256 * 2048)
    assert executed <= xla <= 1.03 * executed, (required, executed, xla)
