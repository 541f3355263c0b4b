"""Multi-pod dry-run (deliverable e).

Lowers + compiles the production step function for every
(architecture × input shape × mesh) combination on 512 placeholder host
devices, proving the sharding configuration is coherent, and records
memory_analysis / HLO statistics (FLOPs, HBM bytes, collective bytes — via
``repro.launch.hlo_stats``, which corrects for while-loop trip counts) into
JSON artifacts consumed by §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k [--multi-pod] [--out benchmarks/results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.types import INPUT_SHAPES, MLLMConfig, ModelConfig, ShapeSpec
from repro.configs import ASSIGNED, ArchSpec, get_config
from repro.core.communicator import make_communicator
from repro.core.profiling.flops import model_flops_6nd, module_flops
from repro.launch.hlo_stats import analyze
from repro.launch.mesh import batch_axes, make_production_mesh, model_axes
from repro.models import mllm as mllm_lib
from repro.models import model as model_lib
from repro.models.model import FwdCtx
from repro.serve.steps import make_decode_step, make_prefill_step
from repro.sharding.partition import (
    AxisAssignment,
    ModuleAssignment,
    param_specs,
    opt_state_specs,
    sanitize_spec,
)
from repro.train.optim import AdamWConfig, adamw_init
from repro.train.step import make_train_step

# per-arch microbatch counts for train_4k (memory-driven)
N_MB = {"default": 8, "jamba-v0.1-52b": 16, "mixtral-8x7b": 16,
        "starcoder2-15b": 16}
# per-arch MoE dispatch chunk (tokens)
MOE_CHUNK = {"default": 8192}

MEM_CAP_BYTES = 16e9        # v5e HBM


# --------------------------------------------------------------------------- #
# Sharding plans
# --------------------------------------------------------------------------- #
def make_assignment(mesh, spec: ArchSpec, *, heterogeneous: bool = True,
                    fsdp: bool = True) -> ModuleAssignment:
    """DFLOP plan on the fixed mesh: LLM uses the model axis for tensor
    sharding; the encoder (small, batch-rich) runs tp=1 with the model axis
    joined to its batch sharding — the SPMD realization of independent
    per-module 3D parallelism (DESIGN.md §2)."""
    b, m = batch_axes(mesh), model_axes(mesh)
    zero = b          # ZeRO over all batch axes (pod + data on multi-pod)
    llm = AxisAssignment(batch=b, tensor=m, zero=zero, fsdp=fsdp)
    enc = None
    if spec.is_mllm:
        if heterogeneous:
            enc = AxisAssignment(batch=b + m, tensor=(), zero=zero, fsdp=fsdp)
        else:
            enc = AxisAssignment(batch=b, tensor=m, zero=zero, fsdp=fsdp)
    return ModuleAssignment(llm=llm, encoder=enc)


def moe_constrain_fn(mesh, cfg: ModelConfig, assignment: AxisAssignment):
    """Sharding constraint for the (E, C, d) MoE dispatch buffers: expert
    parallelism when E divides the tensor axes, else shard capacity over the
    batch axes (DESIGN.md §4 notes on granite/mixtral)."""
    if cfg.n_experts == 0:
        return None
    t = assignment.tensor
    tsize = int(np.prod([mesh.shape[a] for a in t], initial=1))
    if t and cfg.n_experts % tsize == 0:
        spec = P(tuple(t), tuple(assignment.batch) or None, None)
    else:
        spec = P(None, tuple(assignment.batch) or None, None)

    def constrain(x):
        s = sanitize_spec(spec, x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))

    return constrain


def block_gather_constrain(mesh, blocks_shapes, assignment: AxisAssignment):
    """ZeRO-3 weight gather for one scanned block: constrain the sliced
    block params to their non-FSDP layout (tensor-sharded, replicated over
    the zero axes).  Applied inside the layer scan it is loop-variant — the
    all-gather is per-block, and its transpose reduce-scatters dW."""
    if not (assignment.fsdp and assignment.zero):
        return None
    a2 = dataclasses.replace(assignment, fsdp=False)
    specs = param_specs({"blocks": blocks_shapes},
                        ModuleAssignment(llm=a2), mesh)["blocks"]

    def drop0(s):
        return P(*list(s)[1:]) if len(s) else s

    specs = jax.tree.map(drop0, specs, is_leaf=lambda x: isinstance(x, P))

    def constrain(lp, j):
        return jax.tree.map(
            lambda x, sp: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, sanitize_spec(sp, x.shape, mesh))),
            lp, specs[f"pos{j}"])

    return constrain


def hidden_constrain_fn(mesh, assignment: AxisAssignment):
    """Anchor (B, S, d) activations: batch over the module's batch axes."""
    b = tuple(assignment.batch)

    def constrain(x):
        s = sanitize_spec(P(b or None, None, None), x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))

    return constrain


def logits_constrain_fn(mesh, cfg: ModelConfig, assignment: AxisAssignment):
    """Shard the (B, S, vocab) logits over the tensor axes on the vocab dim
    — keeps the fp32 CE working set per chip small for 200k+ vocabs."""
    b = tuple(assignment.batch)
    t = tuple(assignment.tensor)
    spec = P(b or None, None, t or None)

    def constrain(x):
        s = sanitize_spec(spec, x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))

    return constrain


def cache_specs(cfg: ModelConfig, caches_shapes, mesh, assignment: AxisAssignment,
                batch: int):
    """KV/state cache PartitionSpecs.  Sequence dim of KV caches shards over
    the model axis (flash-decoding style) — kv-head counts (1–8) rarely
    divide a 16-wide axis; for batch=1 long-context the data axes join in."""
    b = tuple(assignment.batch)
    m = tuple(assignment.tensor)
    seq_axes = m if batch > 1 else tuple(assignment.batch) + m

    def rule(path: str, leaf):
        shape = leaf.shape
        if path.endswith("/k") or path.endswith("/v"):
            spec = P(None, b or None, seq_axes or None, None, None)
        elif path.endswith("/kpos"):
            # per-row validity: (n_blocks, B, C) — row dim follows k/v batch
            spec = P(None, b or None, seq_axes or None)
        elif path.endswith("/conv"):
            spec = P(None, b or None, None, m or None)
        elif path.endswith("/ssm"):
            spec = P(None, b or None, m or None, None)
        elif path.endswith("/wkv"):
            spec = P(None, b or None, m or None, None, None)
        elif path.endswith("_prev"):
            spec = P(None, b or None, m or None)
        else:
            spec = P()
        return sanitize_spec(spec, shape, mesh)

    from repro.common.pytree import tree_map_with_path_str

    return tree_map_with_path_str(rule, caches_shapes)


# --------------------------------------------------------------------------- #
# Batch specs (ShapeDtypeStructs) per family × shape kind
# --------------------------------------------------------------------------- #
def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, sanitize_spec(spec, shape, mesh)))


def media_split(spec: ArchSpec, seq_len: int) -> tuple[int, int, int]:
    """(media items, encoder tokens, text tokens) for an MLLM sample whose
    LLM sequence is `seq_len` (≈half media, half text)."""
    mcfg: MLLMConfig = spec.desc
    tpm = spec.tokens_per_media_item or mcfg.tokens_per_item_out or 196
    n_items = max(1, (seq_len // 2) // tpm)
    enc_tokens = n_items * mcfg.stub.n_tokens
    text = seq_len - n_items * tpm
    return n_items, enc_tokens, text


def input_specs(spec: ArchSpec, shape: ShapeSpec, mesh, n_mb: int):
    """ShapeDtypeStruct stand-ins for the step's data inputs (train kind)."""
    assignment = make_assignment(mesh, spec)
    b_axes = tuple(assignment.llm.batch)
    desc = spec.desc
    mb = shape.global_batch // n_mb
    S = shape.seq_len
    bspec3 = P(None, b_axes or None, None)
    bspec4 = P(None, b_axes or None, None, None)
    if isinstance(desc, MLLMConfig):
        n_items, enc_tok, text = media_split(spec, S)
        e_spec = P(None, tuple(assignment.for_module("encoder").batch) or None,
                   None, None)
        return {
            "media_embeds": _sds((n_mb, mb, enc_tok, desc.stub.embed_dim),
                                 jnp.bfloat16, mesh, e_spec),
            "media_mask": _sds((n_mb, mb, enc_tok), jnp.int32, mesh, bspec3),
            "text_tokens": _sds((n_mb, mb, text), jnp.int32, mesh, bspec3),
            "text_mask": _sds((n_mb, mb, text), jnp.int32, mesh, bspec3),
            "labels": _sds((n_mb, mb, text), jnp.int32, mesh, bspec3),
        }
    if desc.input_embed_dim > 0:
        return {
            "frame_embeds": _sds((n_mb, mb, S, desc.input_embed_dim),
                                 jnp.bfloat16, mesh, bspec4),
            "labels": _sds((n_mb, mb, S), jnp.int32, mesh, bspec3),
        }
    return {
        "tokens": _sds((n_mb, mb, S), jnp.int32, mesh, bspec3),
        "labels": _sds((n_mb, mb, S), jnp.int32, mesh, bspec3),
        "segment_ids": _sds((n_mb, mb, S), jnp.int32, mesh, bspec3),
        "positions": _sds((n_mb, mb, S), jnp.int32, mesh, bspec3),
    }


def _shapes_of(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# --------------------------------------------------------------------------- #
# Step builders
# --------------------------------------------------------------------------- #
def _dryrun_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, dtype="bfloat16", param_dtype="bfloat16")


def _dryrun_desc(spec: ArchSpec):
    d = spec.desc
    if isinstance(d, MLLMConfig):
        return dataclasses.replace(d, encoder=_dryrun_cfg(d.encoder),
                                   llm=_dryrun_cfg(d.llm))
    return _dryrun_cfg(d)


def build_train(spec: ArchSpec, shape: ShapeSpec, mesh):
    desc = _dryrun_desc(spec)
    assignment = make_assignment(mesh, spec)
    n_mb = N_MB.get(spec.arch_id, N_MB["default"])
    llm_cfg = desc.llm if isinstance(desc, MLLMConfig) else desc

    params_shapes = jax.eval_shape(
        lambda: (mllm_lib.init if isinstance(desc, MLLMConfig)
                 else model_lib.init)(jax.random.PRNGKey(0), desc))
    opt_shapes = jax.eval_shape(lambda: adamw_init(params_shapes))
    pspecs = param_specs(params_shapes, assignment, mesh)
    moment_specs = opt_state_specs(params_shapes, pspecs, assignment, mesh)
    ospecs = {"m": moment_specs, "v": moment_specs, "step": P()}

    batch = input_specs(spec, shape, mesh, n_mb)
    communicator = None
    if isinstance(desc, MLLMConfig):
        communicator = make_communicator(mesh, assignment.for_module("encoder"),
                                         assignment.llm)
    ctx = FwdCtx(mode="train", attn_impl="chunked", attn_block=1024,
                 ssm_impl="chunked", moe_impl="ep",
                 capacity_factor=1.25,
                 moe_chunk_tokens=MOE_CHUNK.get(spec.arch_id,
                                                MOE_CHUNK["default"]),
                 moe_constrain=moe_constrain_fn(mesh, llm_cfg, assignment.llm),
                 hidden_constrain=hidden_constrain_fn(mesh, assignment.llm),
                 logits_constrain=logits_constrain_fn(mesh, llm_cfg,
                                                      assignment.llm),
                 shard_ctx=(mesh, tuple(assignment.llm.batch),
                            tuple(assignment.llm.tensor)))
    from repro.sharding.vocab_ce import make_vocab_parallel_ce

    vocab_ce = make_vocab_parallel_ce(
        mesh, tuple(assignment.llm.batch), tuple(assignment.llm.tensor),
        llm_cfg.vocab_size, tied=llm_cfg.tie_embeddings)
    # ZeRO-3 per-block weight gathers (reduce-scattered dW in the backward)
    enc_ctx = None
    if isinstance(desc, MLLMConfig):
        llm_blocks = params_shapes["llm"]["blocks"]
        enc_blocks = params_shapes["encoder"]["blocks"]
        ctx.block_constrain = block_gather_constrain(mesh, llm_blocks,
                                                     assignment.llm)
        enc_ctx = dataclasses.replace(
            ctx, moe_constrain=None, logits_constrain=None,
            block_constrain=block_gather_constrain(
                mesh, enc_blocks, assignment.for_module("encoder")))
    else:
        ctx.block_constrain = block_gather_constrain(
            mesh, params_shapes["blocks"], assignment.llm)
    step = make_train_step(desc, AdamWConfig(), ctx=ctx,
                           communicator=communicator, vocab_ce=vocab_ce,
                           enc_ctx=enc_ctx)

    def wrapped(params, opt_state, batch):
        return step(params, opt_state, batch, 1e-4)

    in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
             jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                          is_leaf=lambda x: isinstance(x, P)),
             jax.tree.map(lambda b: b.sharding, batch))
    out_sh = (in_sh[0], in_sh[1], NamedSharding(mesh, P()))
    jitted = jax.jit(wrapped, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))
    args = (params_shapes, opt_shapes, batch)
    return jitted, args, {"n_mb": n_mb, "assignment": "dflop-heterogeneous"}


def build_prefill(spec: ArchSpec, shape: ShapeSpec, mesh):
    desc = _dryrun_desc(spec)
    # FSDP-sharded weights WITHOUT explicit per-block gathers: for the
    # forward-only prefill, XLA's own slice-wise handling of scan-xs weights
    # is the most memory-efficient option measured (the CPU backend converts
    # bf16 dot operands to f32; resident model-axis-only weights double, and
    # explicit gathers add copies).
    assignment = make_assignment(mesh, spec, fsdp=True)
    llm_cfg = desc.llm if isinstance(desc, MLLMConfig) else desc
    b_axes = tuple(assignment.llm.batch)
    B, S = shape.global_batch, shape.seq_len
    params_shapes = jax.eval_shape(
        lambda: (mllm_lib.init if isinstance(desc, MLLMConfig)
                 else model_lib.init)(jax.random.PRNGKey(0), desc))
    llm_blocks = (params_shapes["llm"]["blocks"]
                  if isinstance(desc, MLLMConfig)
                  else params_shapes["blocks"])
    ctx = FwdCtx(mode="prefill", remat=False, attn_impl="chunked",
                 attn_block=1024, ssm_impl="chunked", moe_impl="ep",
                 capacity_factor=1.25,
                 moe_chunk_tokens=8192,
                 moe_constrain=moe_constrain_fn(mesh, llm_cfg, assignment.llm),
                 hidden_constrain=hidden_constrain_fn(mesh, assignment.llm),
                 logits_constrain=logits_constrain_fn(mesh, llm_cfg,
                                                      assignment.llm))

    if isinstance(desc, MLLMConfig):
        n_items, enc_tok, text = media_split(spec, S)
        e_spec = P(tuple(assignment.for_module("encoder").batch) or None,
                   None, None)
        batch = {
            "media_embeds": _sds((B, enc_tok, desc.stub.embed_dim),
                                 jnp.bfloat16, mesh, e_spec),
            "media_mask": _sds((B, enc_tok), jnp.int32, mesh,
                               P(b_axes or None, None)),
            "text_tokens": _sds((B, text), jnp.int32, mesh,
                                P(b_axes or None, None)),
            "text_mask": _sds((B, text), jnp.int32, mesh,
                              P(b_axes or None, None)),
        }
        communicator = make_communicator(mesh, assignment.for_module("encoder"),
                                         assignment.llm)

        ctx = dataclasses.replace(ctx, return_hidden=True)

        def prefill(params, batch):
            # serving prefill: last-position logits only (next token)
            h, _ = mllm_lib.forward_train(
                params, desc, {**batch, "labels": batch["text_tokens"]},
                ctx=ctx, communicator=communicator)
            from repro.models.layers import embed as embed_lib
            h_last = h[:, -1:]
            llm_p = params["llm"]
            if desc.llm.tie_embeddings or "unembed" not in llm_p:
                return embed_lib.decode(llm_p["embed"], h_last)
            return embed_lib.unembed(llm_p["unembed"], h_last)
    elif desc.input_embed_dim > 0:
        batch = {"frame_embeds": _sds((B, S, desc.input_embed_dim),
                                      jnp.bfloat16, mesh,
                                      P(b_axes or None, None, None))}
        prefill = make_prefill_step(desc, ctx)
    else:
        batch = {"tokens": _sds((B, S), jnp.int32, mesh, P(b_axes or None, None))}
        prefill = make_prefill_step(desc, ctx)

    assignment_full = assignment
    pspecs = param_specs(params_shapes, assignment_full, mesh)
    in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
             jax.tree.map(lambda b: b.sharding, batch))
    m_axes = tuple(assignment.llm.tensor)
    msize = int(np.prod([mesh.shape[a] for a in m_axes], initial=1))
    vocab_spec = m_axes if (m_axes and llm_cfg.vocab_size % msize == 0) else None
    out_spec = NamedSharding(mesh, P(b_axes or None, None, vocab_spec))
    jitted = jax.jit(prefill, in_shardings=in_sh, out_shardings=out_spec)
    return jitted, (params_shapes, batch), {"assignment": "dflop-heterogeneous"}


def build_decode(spec: ArchSpec, shape: ShapeSpec, mesh):
    desc = _dryrun_desc(spec)
    llm_cfg = desc.llm if isinstance(desc, MLLMConfig) else desc
    # FSDP weights + per-block ZeRO-3 gathers inside the decode layer scan:
    # the gathers are loop-variant (one block per iteration), so weights stay
    # data-sharded at rest and only one block's gathered copy is live —
    # required for the 47-52B MoE/hybrid archs to fit 16 GB at decode.
    assignment = make_assignment(mesh, spec, fsdp=True)
    a = assignment.llm
    B, S = shape.global_batch, shape.seq_len
    params_shapes = jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), llm_cfg))
    if isinstance(desc, MLLMConfig):
        full = jax.eval_shape(lambda: mllm_lib.init(jax.random.PRNGKey(0), desc))
        pspecs_full = param_specs(full, assignment, mesh)
        pspecs = pspecs_full["llm"]
    else:
        pspecs = param_specs(params_shapes, assignment, mesh)
    caches_shapes = jax.eval_shape(
        lambda: model_lib.init_cache(llm_cfg, B, S, kv_dtype=jnp.bfloat16))
    cspecs = cache_specs(llm_cfg, caches_shapes, mesh, a, B)
    b_axes = tuple(a.batch)
    tok = _sds((B,), jnp.int32, mesh, P(b_axes if B > 1 else None))

    blocks_shapes = (full["llm"]["blocks"] if isinstance(desc, MLLMConfig)
                     else params_shapes["blocks"])
    decode_ctx = FwdCtx(mode="decode", remat=False,
                        block_constrain=block_gather_constrain(
                            mesh, blocks_shapes, assignment.llm))
    decode = make_decode_step(llm_cfg, ctx=decode_ctx)

    in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
             jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                          is_leaf=lambda x: isinstance(x, P)),
             tok.sharding, NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, P(b_axes if B > 1 else None, None)),
              jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                           is_leaf=lambda x: isinstance(x, P)))
    jitted = jax.jit(decode, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(1,))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    args = (params_shapes, caches_shapes, jax.ShapeDtypeStruct((B,), jnp.int32),
            pos)
    return jitted, args, {"cache_len": S, "assignment": "dflop-heterogeneous"}


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


# --------------------------------------------------------------------------- #
# Runner
# --------------------------------------------------------------------------- #
def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: Optional[str] = None, verbose: bool = True) -> dict:
    spec = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    support = spec.shape_support(shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": support, "ok": False}
    if support.startswith("skip"):
        rec.update(ok=True, skipped=True, reason=support)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: {support}")
        return _dump(rec, out_dir)

    mesh = make_production_mesh(multi_pod=multi_pod)
    builder = BUILDERS[support]
    t0 = time.monotonic()
    try:
        jitted, args, extra = builder(spec, shape, mesh)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.monotonic() - t0
            t1 = time.monotonic()
            compiled = lowered.compile()
            t_compile = time.monotonic() - t1
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        stats = analyze(compiled.as_text())
        n_chips = int(np.prod(list(mesh.shape.values())))
        llm_cfg = spec.llm_cfg
        mode = support
        tokens = shape.global_batch * (1 if mode == "decode" else shape.seq_len)
        n_active = llm_cfg.active_param_count()
        if spec.is_mllm and mode != "decode":
            n_active += spec.desc.encoder.param_count()
        # 6·N·D for training (fwd+bwd), 2·N·D for inference forward
        model_fl = (6.0 if mode == "train" else 2.0) * n_active * tokens
        rec.update(
            ok=True, skipped=False,
            n_chips=n_chips,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "code_bytes": ma.generated_code_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_per_chip": ma.argument_size_in_bytes
                + ma.output_size_in_bytes + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes,
            },
            xla_cost={"flops": ca.get("flops", 0.0),
                      "bytes_accessed": ca.get("bytes accessed", 0.0)},
            hlo=stats.as_dict(),
            model_flops=model_fl,
            tokens=tokens,
            params=spec.desc.param_count(),
            active_params=(llm_cfg.active_param_count()
                           + (spec.desc.encoder.param_count()
                              if spec.is_mllm else 0)),
            **extra,
        )
        fits = rec["memory"]["peak_per_chip"] <= MEM_CAP_BYTES
        rec["fits_16gb"] = bool(fits)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
                  f"compile={t_compile:.1f}s "
                  f"peak={rec['memory']['peak_per_chip']/1e9:.2f}GB "
                  f"flops/chip={stats.flops:.3e} "
                  f"coll={stats.total_collective_bytes:.3e}B")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAIL {e}")
    return _dump(rec, out_dir)


def _dump(rec: dict, out_dir: Optional[str]) -> dict:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1, default=float)
    return rec


def main():
    # 512 placeholder host devices; must precede JAX's backend start-up,
    # which the first device query below triggers
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    combos = []
    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))
    failures = 0
    for a, s, mp in combos:
        rec = run_one(a, s, mp, args.out)
        failures += 0 if rec["ok"] else 1
    print(f"[dryrun] done: {len(combos)} combos, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
