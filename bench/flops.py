"""Floating-point operations a training step requires, from its shapes.

Counts the work the model needs, not what the program happens to execute:

* forward plus backward, where the backward of a matrix product is two
  products of the same size (input gradient and weight gradient);
* no recomputation (the program rematerializes every layer; that work does
  not count);
* the LM head over text positions only (the program projects media positions
  too, then drops them);
* causal attention over its lower triangle; bidirectional attention over the
  whole row, padding included, since the row's shape is the step's;
* the first projection of the encoder has no input gradient (its input is
  data).

Elementwise work (norms, softmax, activations, the optimizer) is left out; it
is a few tenths of a percent of these counts.  The same arithmetic as
``src/repro/core/profiling/flops.py`` without its ``TRAIN_MULT`` and
whole-sequence head.
"""
from __future__ import annotations


def _stack_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one row through a stack of ``c['n_layers']`` layers."""
    d, h, kh = c["d_model"], c["n_heads"], c["n_kv_heads"]
    hd = c["head_dim"] or d // h
    n_mat = 3 if c["activation"] in ("swiglu", "geglu") else 2
    proj = 2.0 * seq * d * (h + 2 * kh) * hd + 2.0 * seq * h * hd * d
    attn = 4.0 * seq * seq * h * hd
    if c["causal"]:
        attn *= 0.5
    ffn = 2.0 * seq * n_mat * d * c["d_ff"]
    return c["n_layers"] * (proj + attn + ffn)


def row_forward(m: dict, t_media: int, t_text: int) -> dict:
    """Forward FLOPs of one row by part: encoder, connector, llm, head, and
    the encoder's input projection (which needs no input gradient)."""
    enc, llm = m["encoder"], m["llm"]
    de, dl = enc["d_model"], llm["d_model"]
    ch = m["connector_hidden"]
    in_proj = 2.0 * t_media * enc["input_embed_dim"] * de
    encoder = _stack_forward(enc, t_media)
    if ch:
        connector = 2.0 * t_media * (de * ch + ch * dl)
    else:
        connector = 2.0 * t_media * de * dl
    tpo = m["tokens_per_item_out"]
    t_out = t_media
    if tpo and t_media // tpo > 1:
        t_out = t_media // (t_media // tpo)
    return {"in_proj": in_proj, "encoder": encoder, "connector": connector,
            "llm": _stack_forward(llm, t_out + t_text),
            "head": 2.0 * t_text * dl * llm["vocab_size"]}


def step_flops(m: dict, n_rows: int, t_media: int, t_text: int) -> float:
    """Required FLOPs of one training step over ``n_rows`` rows of
    ``t_media`` encoder tokens and ``t_text`` text tokens."""
    f = row_forward(m, t_media, t_text)
    per_row = 2.0 * f.pop("in_proj") + 3.0 * sum(f.values())
    return n_rows * per_row
