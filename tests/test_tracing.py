"""Per-module device time: the program's ``dflop.`` named scopes in the
compiled train step, its host spans in the profiler's trace, and
``bench/trace_reduce.py``, which attributes device ops to them."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as ts
from repro.common.trace import TraceRecorder
from repro.data.items import DataItem
from repro.data.synthetic import MixedDataset

SCOPES = ("encoder", "connector", "llm", "head", "attention", "grad_accum",
          "optimizer")


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/while/body/jvp(dflop.encoder)/dot_general",
     ("encoder", "forward", False)),
    ("jit(step)/while/body/transpose(jvp(dflop.llm))/dflop.head/dot_general",
     ("head", "backward", False)),
    ("jit(step)/while/body/transpose(jvp(dflop.llm))/jvp(dflop.llm)/"
     "checkpoint/rematted_computation/dflop.attention/exp",
     ("llm", "recompute", True)),
    ("jit(step)/while/body/transpose(jvp(dflop.encoder))/checkpoint/"
     "dflop.attention/while/body/dot_general",
     ("encoder", "backward", True)),
    ("jit(step)/while/body/closed_call/dflop.attention/eq",
     (None, "forward", True)),
    ("jit(step)/while/body/dflop.grad_accum/add",
     ("grad_accum", "forward", False)),
    ("jit(step)/dflop.optimizer/sub", ("optimizer", "forward", False)),
    ("jit(step)/while/body/dynamic_slice", (None, "forward", False)),
])
def test_scope_of(op_name, want):
    """Innermost module, phase (recompute before backward) and the
    attention cut; no ``dflop.`` module is unscoped."""
    assert ts.scope_of(op_name) == want


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %sub.1 = f32[2]{0} subtract(%p, %p), metadata={op_name="jit(train_step)/dflop.optimizer/sub"}
}

ENTRY %main.9 (a: f32[2]) -> f32[2] {
  %a = f32[2]{0} parameter(0)
  %fusion.2 = f32[2]{0} fusion(%a), kind=kLoop, calls=%x, metadata={op_name="jit(train_step)/while/body/jvp(dflop.encoder)/dot_general"}
  %fusion.3 = f32[2]{0} fusion(%a), kind=kLoop, calls=%x, metadata={op_name="jit(train_step)/while/body/transpose(jvp(dflop.encoder))/dflop.attention/dot_general"}
  %fusion.4 = f32[2]{0} fusion(%a), kind=kLoop, calls=%x, metadata={op_name="jit(train_step)/while/body/transpose(jvp(dflop.llm))/jvp(dflop.llm)/checkpoint/rematted_computation/mul"}
  %fusion.5 = f32[2]{0} fusion(%a), kind=kLoop, calls=%x, metadata={op_name="jit(train_step)/while/body/transpose(jvp(dflop.llm))/dflop.head/dot_general"}
  %copy.6 = f32[2]{0} copy(%a)
  ROOT %fusion.8 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
}
"""


def test_hlo_scopes():
    name, table = ts.hlo_scopes(HLO)
    assert name == "jit_train_step"
    assert table["fusion.2"] == ("encoder", "forward", False)
    assert table["fusion.3"] == ("encoder", "backward", True)
    assert table["fusion.4"] == ("llm", "recompute", False)
    assert table["fusion.5"] == ("head", "backward", False)
    assert "copy.6" not in table                   # no metadata: unscoped
    # a fusion without metadata takes its fused computation's root's
    assert table["fusion.8"] == ("optimizer", "forward", False)


def _op(instr: str, a: float, b: float) -> tuple:
    return (f"%{instr} = f32[2]{{0}} fusion(f32[2]{{0}} %a)", a, b)


def test_scopes_over_hand_made_events():
    main, replan = ("/host:CPU", 2), ("/host:CPU", 5)
    spans = [("bench.window", 0.0, 10.0, main),
             ("bench.materialize", 1.0, 4.0, main),
             ("dflop.data.materialize", 1.5, 3.5, main),
             ("dflop.replan.replan-search", 2.0, 3.0, replan),
             ("bench.step", 4.0, 10.0, main)]
    dev = "/device:TPU:0"
    devices = {dev: [_op("fusion.1", 0.0, 1.0),           # another module
                     ("%while.7 = (f32[2]) while(%a)", 4.0, 9.0),
                     _op("fusion.2", 4.0, 5.0), _op("fusion.3", 5.0, 6.0),
                     _op("fusion.4", 6.0, 6.5), _op("fusion.5", 6.5, 7.0),
                     ("%copy.6 = f32[2]{0} copy(%a)", 7.0, 7.5),
                     _op("fusion.8", 7.5, 9.5)]}           # ends past run
    modules = {dev: [("jit_other(5)", 0.0, 1.0),
                     ("jit_train_step(123)", 4.0, 9.5)]}
    r = ts.reduce(spans, devices, modules, HLO)
    sc = r["scopes"]
    assert sc["encoder"] == {"forward": 1.0, "backward": 1.0,
                             "recompute": 0.0}
    assert sc["llm"]["recompute"] == 0.5
    assert sc["head"]["backward"] == 0.5
    assert sc["optimizer"]["forward"] == pytest.approx(2.0)
    assert sc["attention"] == {"forward": 0.0, "backward": 1.0,
                               "recompute": 0.0}
    assert sc["connector"] == sc["grad_accum"] == dict.fromkeys(
        ts.PHASES, 0.0)
    assert sc["unscoped"] == 0.5                      # the copy
    assert sc["other_modules"] == 1.0
    assert sc["step_module_s"] == 5.5
    modules_s = sum(sum(sc[m].values()) for m in ts.MODULES)
    assert modules_s + sc["unscoped"] == pytest.approx(sc["step_module_s"])
    # gaps [1, 4] and [9.5, 10]: the first labelled by the program's span
    # on the window's thread, not by the replan thread's shorter one
    assert r["idle_gaps"] == [["dflop.data.materialize", 3.0],
                              ["bench.step", 0.5]]
    assert r["host_spans"]["dflop.data.materialize"] == {"count": 1,
                                                         "s": 2.0}
    assert r["host_spans"]["dflop.replan.replan-search"]["count"] == 1
    # trace_reduce's own numbers stand beside the scopes
    assert r["busy_s"] == pytest.approx(6.5) and r["window_s"] == 10.0


def test_compiled_train_step_carries_every_scope():
    """The tiny cell's step, compiled as the benchmark compiles it: all
    seven scopes, and encoder, LLM, head and attention in a forward and
    in a backward op."""
    from bench.harness.train import to_desc
    from bench.tests import tiny
    from repro.models import mllm as mllm_lib
    from repro.models.model import FwdCtx
    from repro.train.optim import AdamWConfig, adamw_init
    from repro.train.step import make_train_step

    desc = to_desc(tiny.MODEL)
    params = jax.eval_shape(lambda k: mllm_lib.init(k, desc),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    tr = tiny.TRAFFIC
    lead = (tr["microbatches"], tr["rows_per_microbatch"])
    t_media = tr["media_cap"] * desc.stub.n_tokens
    sd = jax.ShapeDtypeStruct
    batch = {"media_embeds": sd(lead + (t_media, desc.stub.embed_dim),
                                jnp.float32),
             "media_mask": sd(lead + (t_media,), jnp.int32),
             **{k: sd(lead + (tr["text_cap"],), jnp.int32)
                for k in ("text_tokens", "text_mask", "labels")}}
    step = jax.jit(make_train_step(desc, AdamWConfig(**tiny.CONFIG[
        "optimizer"]), ctx=FwdCtx(mode="train")), donate_argnums=(0, 1))
    text = step.lower(params, opt, batch, sd((), jnp.float32)).compile(
    ).as_text()
    for s in SCOPES:
        assert f"dflop.{s}" in text, s
    name, table = ts.hlo_scopes(text)
    assert name == "jit_train_step"
    seen = {(m, p) for m, p, _ in table.values()}
    seen |= {("attention", p) for _, p, a in table.values() if a}
    for m in ("encoder", "llm", "head", "attention"):
        assert (m, "forward") in seen and (m, "backward") in seen, m
    assert ("llm", "recompute") in seen and ("encoder", "recompute") in seen
    for m in ("connector", "grad_accum", "optimizer"):
        assert any(k[0] == m for k in seen), m


def test_trace_recorder_spans_reach_the_profiler(tmp_path, monkeypatch):
    """``TraceRecorder.span`` and ``MixedDataset.materialize`` write
    ``dflop.`` host events into a profiler trace, on the calling thread and
    on the profiler's clock, whether or not the recorder is enabled.  A
    draw large enough for the pool adds one ``dflop.data.draw`` a worker
    task, on the workers' threads, inside its ``materialize``."""
    from repro.data import synthetic
    monkeypatch.setattr(synthetic, "_WORKERS", 3)
    monkeypatch.setattr(synthetic, "_pool", None)
    quiet, loud = TraceRecorder(enabled=False), TraceRecorder()
    ds = MixedDataset("single_image", seed=0, tokens_per_media_item=4)
    big = MixedDataset("single_image", seed=0, tokens_per_media_item=4096)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with quiet.span("probe", cat="test"):
                pass
            with loud.span("loud", cat="test"):
                pass
            ds.materialize([DataItem(1, 8)], embed_dim=8, vocab_size=64,
                           max_media=8, max_text=16)
            # 2 rows of 4096 x 128: 1.05 M samples in 32 chunks, 3 tasks
            big.materialize([DataItem(1, 8)] * 2, embed_dim=128,
                            vocab_size=64, max_media=4096, max_text=16)
    finally:
        jax.profiler.stop_trace()
        synthetic._pool.shutdown(wait=True)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans, _, _ = ts.read_events(path)
    by_name = {n: (a, b, t) for n, a, b, t in spans}
    w0, w1, thread = by_name["bench.window"]
    for n in ("dflop.test.probe", "dflop.test.loud",
              "dflop.data.materialize"):
        a, b, t = by_name[n]
        assert t == thread and w0 <= a <= b <= w1, n
    assert len(quiet) == 0 and len(loud) == 1
    host = ts.host_spans(spans)
    assert host["dflop.data.materialize"]["count"] == 2
    assert host["dflop.data.draw"]["count"] == 3
    m0, m1, _ = by_name["dflop.data.materialize"]      # the big one
    draws = [(a, b, t) for n, a, b, t in spans if n == "dflop.data.draw"]
    assert all(m0 <= a <= b <= m1 and t != thread for a, b, t in draws)
