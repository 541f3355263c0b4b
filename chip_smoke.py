"""Smoke run of DFLOP's training path on a TPU, through its normal entry points.

    python chip_smoke.py              # one chip: train phase + kernel phase
    python chip_smoke.py --chips 4    # a 2x2 host: sharded train phase only

Train phase: ``internvl2-2b`` at its published widths, depth cut to 4 LLM +
4 ViT layers, trained for a few steps through DFLOPEngine.profile ->
engine.runtime -> ctl.schedule -> MixedDataset.materialize -> the jitted
make_train_step -> ctl.observe_step.  The step-0 loss is checked against a
float32 reference of the same params and batch.

Kernel phase: forward and backward of the three Pallas kernels through
``repro.kernels.ops``, compiled by Mosaic at published head and state
widths, against ``jax.grad`` of their oracles at highest precision.

Sharded phase (``--chips 4``): ``launch.dryrun.build_train``'s heterogeneous
DFLOP assignment (ZeRO-3, vocab-parallel CE, encoder->LLM communicator) on a
(data=2, model=2) mesh with real arrays, against the unsharded loss of the
same params and batch on one chip, then a ``reshard_params`` round trip
(1,1,1) -> (dp2, tp2) -> (1,1,1) that must return bit-equal params.

Everything runs in this one process.  With no TPU it exits non-zero before
printing any result.  Timings printed are smoke timings of one run, not
benchmark numbers.  The last line of stdout is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.common import compile_cache                        # noqa: E402
from repro.common.types import ShapeSpec                      # noqa: E402
from repro.configs import get_config                          # noqa: E402
from repro.core.engine import DFLOPEngine                     # noqa: E402
from repro.core.optimizer.space import (                      # noqa: E402
    ClusterSpec, ModuleParallelism, ParallelismPlan)
from repro.data.synthetic import MixedDataset                 # noqa: E402
from repro.kernels import ops, ref                            # noqa: E402
from repro.launch.dryrun import build_train                   # noqa: E402
from repro.launch.mesh import make_mesh                       # noqa: E402
from repro.launch.reshard import reshard_params               # noqa: E402
from repro.models import mllm as mllm_lib                     # noqa: E402
from repro.models.model import FwdCtx                         # noqa: E402
from repro.train.optim import AdamWConfig, adamw_init         # noqa: E402
from repro.train.step import make_loss_fn, make_train_step    # noqa: E402

ARCH = "internvl2-2b"
DEPTH = 4                 # LLM and ViT layers kept of the published 24 each
N_MB = 4                  # microbatches of one row per step
STEPS = 3
MAX_TEXT = 1024
SEED = 0
# Random init: the final RMSNorm gives unit-RMS hidden states and the
# unembedding has std d^-1/2, so logits are ~N(0, 1) and the mean CE is
# ln V + 1/2.  The band allows ln V +- 1 nat.
LOSS_BAND = 1.0
# One bf16 ulp of the loss value itself is 2^-4 = 0.0625 at 8 <= x < 16:
# activations rounded to bf16 cannot promise more than agreement inside one
# ulp of the result, so bf16 compute vs the f32 reference must agree to
# 0.05 nats, and so must the sharded and unsharded bf16 runs.
LOSS_TOL = 0.05
# Kernels take bf16 inputs and emit bf16 outputs and gradients (relative
# rounding 2^-9 ~ 2e-3 of each value); errors are taken relative to the
# oracle's largest magnitude and allowed 10x that rounding.
KERNEL_TOL = 2e-2
# Sharded phase: rows of 2 images (2048 patches) and 768 text tokens, 8
# microbatches (dryrun.build_train's n_mb) of 4 rows, so the encoder's batch
# splits over all four chips.
SHARD_SEQ = 1280
SHARD_GBS = 32
SHARD_STEPS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    return dev


def cut_depth(desc, depth: int):
    return dataclasses.replace(
        desc, encoder=dataclasses.replace(desc.encoder, n_layers=depth),
        llm=dataclasses.replace(desc.llm, n_layers=depth))


def in_float32(desc):
    return dataclasses.replace(
        desc, encoder=dataclasses.replace(desc.encoder, dtype="float32"),
        llm=dataclasses.replace(desc.llm, dtype="float32"))


def n_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def gb(n: float) -> str:
    return f"{n / 1e9:.2f} GB"


def memory_line(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def check_loss_band(loss: float, vocab: int, what: str) -> None:
    lnv = math.log(vocab)
    check(abs(loss - lnv) <= LOSS_BAND,
          f"{what} loss {loss} outside ln({vocab}) +- {LOSS_BAND}")


def compile_timed(jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def microbatches(ds, items, groups, desc, max_text: int) -> dict:
    """One row per microbatch: the scheduler's groups, tensorized."""
    sizes = [len(g) for g in groups]
    check(sizes == [1] * len(groups),
          f"expected one item per microbatch group, scheduler gave {sizes}")
    rows = [ds.materialize([items[g[0]]], embed_dim=desc.stub.embed_dim,
                           vocab_size=desc.llm.vocab_size,
                           max_media=desc.stub.n_tokens, max_text=max_text)
            for g in groups]
    return {k: jnp.asarray(np.stack([r[k] for r in rows])) for k in rows[0]}


# --------------------------------------------------------------------------- #
# train phase (one chip)
# --------------------------------------------------------------------------- #
def train_phase(desc, *, full_desc=None, steps: int = STEPS, n_mb: int = N_MB,
                max_text: int = MAX_TEXT, seed: int = SEED) -> dict:
    dev = jax.devices()[0]
    ds = MixedDataset("single_image", seed=seed,
                      tokens_per_media_item=desc.stub.n_tokens)
    eng = DFLOPEngine(llm_cfg=desc.llm, enc_cfg=desc.encoder,
                      e_seq_len=desc.stub.n_tokens,
                      cluster=ClusterSpec(n_chips=1, chips_per_node=1),
                      tokens_per_media_item=desc.tokens_per_item_out)
    eng.profile(ds)
    plan = ParallelismPlan(llm=ModuleParallelism(1, 1, 1),
                           encoder=ModuleParallelism(1, 1, 1), n_mb=n_mb)

    params = jax.jit(lambda k: mllm_lib.init(k, desc))(jax.random.PRNGKey(seed))
    log(f"[train] {desc.name}: {n_params(params) / 1e6:.1f}M params, "
        f"LLM {desc.llm.n_layers}L d={desc.llm.d_model} "
        f"vocab={desc.llm.vocab_size}, ViT {desc.encoder.n_layers}L "
        f"d={desc.encoder.d_model}, {desc.stub.n_tokens} patches/image")

    step_fn = jax.jit(make_train_step(desc, AdamWConfig(lr=1e-4),
                                      ctx=FwdCtx(mode="train")),
                      donate_argnums=(0, 1))
    losses = []
    ref_loss = None
    with eng.runtime(n_mb, plan=plan, auto_replan=False) as ctl:
        for k in range(steps):
            items = ds.sample(n_mb)
            out = ctl.schedule(items)
            batch = microbatches(ds, items, out.groups, desc, max_text)
            if ref_loss is None:
                # float32 reference before the optimizer state exists
                ref_fn = jax.jit(make_loss_fn(
                    in_float32(desc),
                    FwdCtx(mode="train", attn_impl="naive", remat=False)))
                with jax.default_matmul_precision("highest"):
                    ref_loss = float(np.mean([
                        float(ref_fn(params, jax.tree.map(lambda x: x[i],
                                                          batch)))
                        for i in range(n_mb)]))
                opt = jax.jit(adamw_init)(params)
                compiled, compile_s = compile_timed(step_fn, params, opt,
                                                    batch, 1e-4)
                ma = compiled.memory_analysis()
                log(f"[train] compile {compile_s:.2f} s (smoke timing)")
                log(f"[train] memory_analysis: arguments="
                    f"{gb(ma.argument_size_in_bytes)} outputs="
                    f"{gb(ma.output_size_in_bytes)} temps="
                    f"{gb(ma.temp_size_in_bytes)} aliased="
                    f"{gb(ma.alias_size_in_bytes)}")
                if full_desc is not None:
                    log(f"[train] depth cut: LLM {full_desc.llm.n_layers}->"
                        f"{desc.llm.n_layers} layers, ViT "
                        f"{full_desc.encoder.n_layers}->"
                        f"{desc.encoder.n_layers} layers, widths as "
                        f"published; at full depth the f32 params + AdamW "
                        f"moments alone are "
                        f"{gb(12 * full_desc.param_count())}, over one "
                        f"chip's HBM")
            t0 = time.perf_counter()
            params, opt, m = compiled(params, opt, batch, 1e-4)
            loss = float(jax.block_until_ready(m["loss"]))
            step_s = time.perf_counter() - t0
            ctl.observe_step(out, step_s)
            losses.append(loss)
            log(f"[train] step {k}: loss={loss:.6f} step_s={step_s:.4f} "
                f"pred_cmax_s={out.cmax:.4f} solver={out.solver} "
                f"(smoke timing)")
        snap = ctl.metrics.snapshot()
    log(f"[train] runtime: schedules={snap['n_schedules']} "
        f"steps_observed={snap['n_steps']} "
        f"imbalance_mean={snap['imbalance_mean']}")
    log(f"[train] device memory: {memory_line(dev)}")
    check(snap["n_steps"] == steps, "the controller did not see every step")

    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check_loss_band(losses[0], desc.llm.vocab_size, "step-0")
    diff = abs(losses[0] - ref_loss)
    log(f"[train] step-0 loss {losses[0]:.6f} vs f32 reference "
        f"{ref_loss:.6f}: |diff|={diff:.6f} (tol {LOSS_TOL})")
    check(diff <= LOSS_TOL, f"step-0 loss off the f32 reference by {diff}")
    return {"losses": losses, "ref_loss": ref_loss}


# --------------------------------------------------------------------------- #
# kernel phase (one chip): Mosaic-compiled kernels vs their oracles
# --------------------------------------------------------------------------- #
def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def linear_loss(fn):
    """Scalar loss with a fixed cotangent cos(flat index) at every output:
    linear, so the kernel's rounded outputs do not perturb the cotangent."""
    def go(*args):
        outs = fn(*args)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum(jnp.sum(o.astype(jnp.float32) * jnp.cos(
            jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape))
            for o in outs)
    return go


def compare_kernel(name, kernel_fn, ref_fn, args) -> None:
    """Forward and gradients of ``kernel_fn`` against ``ref_fn`` on float32
    copies of the same inputs, at highest precision."""
    argnums = tuple(range(len(args)))
    kfn = jax.jit(kernel_fn)
    kgrad = jax.jit(jax.grad(linear_loss(kernel_fn), argnums=argnums))
    args32 = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_fn)(*args32)
        want_g = jax.jit(jax.grad(linear_loss(ref_fn), argnums=argnums))(*args32)
    got = kfn(*args)
    got_g = kgrad(*args)
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    errs = [rel_err(g, w) for g, w in zip(got, want)]
    errs_g = [rel_err(g, w) for g, w in zip(got_g, want_g)]
    log(f"[kernel] {name}: fwd rel err "
        f"{' '.join(f'{e:.2e}' for e in errs)}, grads rel err "
        f"{' '.join(f'{e:.2e}' for e in errs_g)} (tol {KERNEL_TOL})")
    check(all(math.isfinite(e) for e in errs + errs_g), f"{name} non-finite")
    check(max(errs + errs_g) <= KERNEL_TOL, f"{name} off its oracle")


def packed_segments(rng, B: int, S: int, n_seg: int):
    """Contiguous segments 1..n_seg with a 0-id padding tail."""
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, S - S // 8), n_seg - 1,
                                  replace=False))
        bounds = [0, *cuts, S - S // 8]
        for i in range(n_seg):
            seg[b, bounds[i]:bounds[i + 1]] = i + 1
    return jnp.asarray(seg)


def kernel_phase(*, attn=(1, 2048, 16, 8, 128), mamba=(1, 1024, 8192, 16),
                 rwkv=(1, 1024, 64, 64), seed: int = SEED) -> None:
    """The model-facing kernel entry points (`repro.kernels.ops`), which
    compile through Mosaic on a TPU."""
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(keys), shape) * scale

    # packed flash attention, GQA heads as in InternLM2-1.8B
    B, S, H, KH, D = attn
    seg = packed_segments(rng, B, S, n_seg=3)
    compare_kernel(
        f"packed_flash_attention B{B} S{S} H{H} KH{KH} D{D}",
        lambda q, k, v: ops.packed_flash_attention(q, k, v, segment_ids=seg),
        lambda q, k, v: ref.packed_attention_ref(q, k, v, seg_q=seg,
                                                 seg_k=seg),
        [normal((B, S, H, D)).astype(bf16), normal((B, S, KH, D)).astype(bf16),
         normal((B, S, KH, D)).astype(bf16)])

    # Mamba-1 selective scan, d_inner and d_state as in Jamba
    B, S, di, N = mamba
    compare_kernel(
        f"mamba_scan B{B} S{S} d_inner{di} N{N}",
        lambda *a: ops.mamba_scan(*a)[0], lambda *a: ref.mamba_scan_ref(*a)[0],
        [normal((B, S, di)).astype(bf16),
         jax.nn.softplus(normal((B, S, di)) - 1).astype(bf16),
         normal((B, S, N)).astype(bf16), normal((B, S, N)).astype(bf16),
         -jnp.exp(normal((di, N), 0.3)), normal((di,))])

    # RWKV-6 WKV recurrence, heads as in RWKV6-7B
    B, S, H, M = rwkv
    compare_kernel(
        f"rwkv6_scan B{B} S{S} H{H} M{M}", ops.rwkv6_scan, ref.rwkv6_scan_ref,
        [normal((B, S, H, M)).astype(bf16), normal((B, S, H, M)).astype(bf16),
         normal((B, S, H, M)).astype(bf16),
         jax.nn.sigmoid(normal((B, S, H, M))).astype(bf16),
         normal((H, M), 0.1).astype(bf16)])


# --------------------------------------------------------------------------- #
# sharded phase (four chips)
# --------------------------------------------------------------------------- #
def sharded_phase(desc, devices, *, seq_len: int = SHARD_SEQ,
                  gbs: int = SHARD_GBS, steps: int = SHARD_STEPS,
                  seed: int = SEED) -> dict:
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    spec = dataclasses.replace(get_config(ARCH), desc=desc)
    jitted, args, extra = build_train(
        spec, ShapeSpec("chip-smoke", seq_len, gbs, "train"), mesh)
    with mesh:
        compiled, compile_s = compile_timed(jitted, *args)
    ma = compiled.memory_analysis()
    p_sh, o_sh, b_sh = compiled.input_shardings[0]
    log(f"[sharded] {extra['assignment']} on mesh {dict(mesh.shape)}, "
        f"n_mb={extra['n_mb']}: compile {compile_s:.2f} s (smoke timing); "
        f"per chip arguments={gb(ma.argument_size_in_bytes)} "
        f"temps={gb(ma.temp_size_in_bytes)}")

    # real arrays in the step's own layouts
    params = jax.jit(
        lambda k: jax.tree.map(lambda x, s: x.astype(s.dtype),
                               mllm_lib.init(k, desc), args[0]),
        out_shardings=p_sh)(jax.random.PRNGKey(seed))
    opt = jax.jit(adamw_init, out_shardings=o_sh)(params)
    batch_spec = args[2]
    n_mb, mb, enc_tok = batch_spec["media_mask"].shape
    text = batch_spec["text_tokens"].shape[-1]
    ds = MixedDataset("multi_image", seed=seed,
                      tokens_per_media_item=desc.stub.n_tokens)
    host = ds.materialize(ds.sample(n_mb * mb), embed_dim=desc.stub.embed_dim,
                          vocab_size=desc.llm.vocab_size, max_media=enc_tok,
                          max_text=text)
    host = {k: v.reshape((n_mb, mb) + v.shape[1:]).astype(batch_spec[k].dtype)
            for k, v in host.items()}
    batch = {k: jax.device_put(v, b_sh[k]) for k, v in host.items()}

    # unsharded loss of the same params and batch on one chip
    dev0 = devices[0]
    loss_fn = jax.jit(make_loss_fn(desc))
    params0 = jax.device_put(params, dev0)
    one_chip = float(np.mean([
        float(loss_fn(params0, {k: jax.device_put(v[i], dev0)
                                for k, v in host.items()}))
        for i in range(n_mb)]))
    del params0

    losses = []
    for k in range(steps):
        t0 = time.perf_counter()
        params, opt, m = compiled(params, opt, batch)
        losses.append(float(jax.block_until_ready(m["loss"])))
        log(f"[sharded] step {k}: loss={losses[-1]:.6f} "
            f"step_s={time.perf_counter() - t0:.4f} (smoke timing)")
    in_use = []
    for d in devices:
        log(f"[sharded] device {d.id} memory: {memory_line(d)}")
        in_use.append((d.memory_stats() or {}).get("bytes_in_use", 0))

    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check_loss_band(losses[0], desc.llm.vocab_size, "sharded step-0")
    diff = abs(losses[0] - one_chip)
    log(f"[sharded] step-0 loss {losses[0]:.6f} vs unsharded one-chip "
        f"{one_chip:.6f}: |diff|={diff:.6f} (tol {LOSS_TOL})")
    check(diff <= LOSS_TOL, f"sharded loss off the one-chip loss by {diff}")
    check(min(in_use) >= 0.25 * max(in_use),
          f"train state not spread over the chips: bytes_in_use {in_use}")

    # reshard round trip (1,1,1) -> (dp2, tp2) -> (1,1,1)
    want = jax.device_get(params)
    one = ParallelismPlan(llm=ModuleParallelism(1, 1, 1),
                          encoder=ModuleParallelism(1, 1, 1), n_mb=n_mb)
    wide = ParallelismPlan(llm=ModuleParallelism(tp=2, pp=1, dp=2),
                           encoder=ModuleParallelism(1, 1, 1), n_mb=n_mb)
    hops = [(one, one), (one, wide), (wide, one)]
    for old, new in hops:
        params, rep = reshard_params(params, old, new, stage_stacked=False)
        n_dev = len(jax.tree_util.tree_leaves(params)[0].sharding.device_set)
        log(f"[reshard] {rep.old_plan} -> {rep.new_plan}: "
            f"moved {gb(rep.bytes_moved)} in {rep.elapsed_s:.3f} s onto "
            f"{n_dev} device(s) (smoke timing)")
        if new is wide:
            check(n_dev == 4, f"(dp2, tp2) layout spans {n_dev} devices")
            for d in devices:
                log(f"[reshard] device {d.id} memory: {memory_line(d)}")
    got = jax.device_get(params)
    equal = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                for a, b in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want)))
    log(f"[reshard] round trip bit-equal: {equal}")
    check(equal, "reshard round trip changed the params")
    return {"losses": losses, "one_chip": one_chip}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    dev = require_tpu()
    devices = jax.devices()
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    log(f"[cache] {compile_cache.enable()}")
    full = get_config(ARCH).desc
    desc = cut_depth(full, DEPTH)
    if args.chips == 4:
        check(len(devices) >= 4, f"--chips 4 needs 4 devices, "
                                 f"found {len(devices)}")
        sharded_phase(desc, devices[:4])
    else:
        train_phase(desc, full_desc=full)
        kernel_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
