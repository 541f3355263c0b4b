"""Generic transformer stack covering all assigned families.

One parameter/apply convention serves dense, MoE, SSM (RWKV6), hybrid
(Jamba: Mamba+attention interleave with MoE-every-other-layer), encoder-only
(HuBERT) and VLM-backbone models.  Layers are *stacked per pattern position*
and iterated with ``lax.scan`` over blocks (compile-time critical at 512
devices); heterogeneous patterns (Jamba's period-8 interleave) unroll within
the block and scan across blocks.

Params tree:
    embed/w            (vocab, d)          [if vocab_size > 0]
    in_proj/w          (input_embed_dim,d) [if input_embed_dim > 0]
    blocks/pos{j}/...  stacked (n_blocks, ...) per pattern position j
    final_norm/scale
    unembed/w          (d, vocab)          [if has_lm_head and not tied]
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.common.types import FFNKind, LayerKind, ModelConfig
from repro.models.layers import attention, embed, ffn, mamba, moe, norms, rwkv6


@dataclass
class FwdCtx:
    """Per-call forward options (static except decode_pos)."""

    mode: str = "train"              # train | prefill | decode
    attn_impl: str = "chunked"       # naive | chunked | pallas
    attn_block: int = 512            # flash (block_q, block_k) tile
    ssm_impl: str = "xla"            # xla | pallas
    moe_impl: str = "capacity"       # dense | capacity
    capacity_factor: float = 2.0
    moe_chunk_tokens: int = 0        # >0: chunked+checkpointed dispatch
    moe_constrain: Optional[Callable] = None
    logits_constrain: Optional[Callable] = None   # e.g. shard vocab dim
    block_constrain: Optional[Callable] = None    # ZeRO-3 per-block weight
                                                  # gather (bwd: reduce-scatter)
    hidden_constrain: Optional[Callable] = None   # pin (B,S,d) activation
                                                  # sharding per block
    shard_ctx: Any = None            # (mesh, batch_axes, model_axes) for
                                     # shard_map'd recurrent scans
    return_hidden: bool = False      # skip the LM head (vocab-parallel CE)
    decode_pos: Any = None           # traced scalar in decode mode
    remat: bool = True


# --------------------------------------------------------------------------- #
# Layer init / apply
# --------------------------------------------------------------------------- #
def _layer_init(key, cfg: ModelConfig, kind: LayerKind, ffn_kind: FFNKind,
                dtype):
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    p: dict = {"ln1": norms.rms_init(d, dtype)}
    if kind == LayerKind.ATTENTION:
        p["attn"] = attention.init(k1, cfg, dtype)
    elif kind == LayerKind.MAMBA:
        p["mamba"] = mamba.init(k1, cfg, dtype)
    elif kind == LayerKind.RWKV6:
        p["rwkv"] = rwkv6.init(k1, cfg, dtype)
        p["ln2"] = norms.rms_init(d, dtype)
        return p                      # rwkv block has its own channel mix
    p["ln2"] = norms.rms_init(d, dtype)
    if ffn_kind == FFNKind.MOE:
        p["moe"] = moe.init(k2, cfg, dtype)
    else:
        p["ffn"] = ffn.init(k2, cfg, dtype)
    return p


def _layer_apply(lp, x, cfg: ModelConfig, kind: LayerKind, ffn_kind: FFNKind,
                 ctx: FwdCtx, cache, positions, segment_ids):
    """Returns (x, new_cache, lb, moe_stats); moe_stats is None for
    non-MoE layers and a (drop_rate, imbalance) pair (possibly NaN —
    shard_map dispatch doesn't measure) for MoE layers."""
    lb = jnp.zeros((), jnp.float32)
    moe_stats = None
    h = norms.rms_apply(lp["ln1"], x, cfg.norm_eps)
    if kind == LayerKind.ATTENTION:
        attn_cache = cache.get("attn") if cache else None
        y, new_attn_cache = attention.apply(
            lp["attn"], h, cfg, positions=positions, segment_ids=segment_ids,
            cache=attn_cache, decode_pos=ctx.decode_pos, impl=ctx.attn_impl,
            block=ctx.attn_block)
        new_cache = {"attn": new_attn_cache} if cache else None
    elif kind == LayerKind.MAMBA:
        m_cache = cache.get("mamba") if cache else None
        # chunked selective scan only outside training: its closed-form
        # intra-chunk tensor is cheap to run but expensive to keep as
        # autodiff residuals (remat recompute makes them all live)
        m_impl = ctx.ssm_impl
        if ctx.mode == "train" and m_impl == "chunked":
            m_impl = "xla"
        y, new_m = mamba.apply(lp["mamba"], h, cfg, cache=m_cache,
                               impl=m_impl,
                               shard_ctx=None if m_cache is not None
                               else ctx.shard_ctx)
        new_cache = {"mamba": new_m} if cache else None
    elif kind == LayerKind.RWKV6:
        r_cache = cache.get("rwkv") if cache else None
        y, new_r = rwkv6.time_mix(lp["rwkv"], h, cfg, cache=r_cache,
                                  impl=ctx.ssm_impl)
        x = x + y
        h2 = norms.rms_apply(lp["ln2"], x, cfg.norm_eps)
        y2, new_r2 = rwkv6.channel_mix(lp["rwkv"], h2, cfg, cache=new_r)
        new_cache = {"rwkv": new_r2} if cache else None
        return x + y2, new_cache, lb, moe_stats
    else:
        raise ValueError(kind)
    x = x + y
    h2 = norms.rms_apply(lp["ln2"], x, cfg.norm_eps)
    if ffn_kind == FFNKind.MOE:
        y2, lb, st = moe.apply(lp["moe"], h2, cfg, impl=ctx.moe_impl,
                               capacity_factor=ctx.capacity_factor,
                               constrain=ctx.moe_constrain,
                               chunk_tokens=ctx.moe_chunk_tokens,
                               shard_ctx=ctx.shard_ctx, with_stats=True)
        moe_stats = (jax.lax.stop_gradient(st["drop_rate"]),
                     jax.lax.stop_gradient(st["imbalance"]))
    else:
        y2 = ffn.apply(lp["ffn"], h2, cfg)
    return x + y2, new_cache, lb, moe_stats


# --------------------------------------------------------------------------- #
# Model init
# --------------------------------------------------------------------------- #
def init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    period = cfg.block_period
    n_blocks = cfg.n_layers // period
    kinds, ffns = cfg.layer_kinds, cfg.ffn_kinds
    keys = jax.random.split(key, cfg.n_layers + 3)

    params: dict = {}
    if cfg.vocab_size > 0 and cfg.input_embed_dim == 0:
        params["embed"] = embed.init(keys[-1], cfg.vocab_size, cfg.d_model, dtype)
    if cfg.input_embed_dim > 0:
        params["in_proj"] = {
            "w": (jax.random.normal(keys[-2], (cfg.input_embed_dim, cfg.d_model))
                  * cfg.input_embed_dim ** -0.5).astype(dtype)}

    blocks: dict = {}
    for j in range(period):
        per_block = [
            _layer_init(keys[b * period + j], cfg, kinds[j], ffns[j], dtype)
            for b in range(n_blocks)
        ]
        blocks[f"pos{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_block)
    params["blocks"] = blocks
    params["final_norm"] = norms.rms_init(cfg.d_model, dtype)
    if cfg.has_lm_head and cfg.vocab_size > 0 and not cfg.tie_embeddings:
        params["unembed"] = embed.unembed_init(keys[-3], cfg.d_model,
                                               cfg.vocab_size, dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_dtype=jnp.bfloat16):
    """Stacked per-position caches matching the params layout."""
    period = cfg.block_period
    n_blocks = cfg.n_layers // period
    kinds = cfg.layer_kinds
    caches: dict = {}
    for j in range(period):
        kind = kinds[j]
        if kind == LayerKind.ATTENTION:
            c = {"attn": attention.init_cache(cfg, batch, max_len, kv_dtype)}
        elif kind == LayerKind.MAMBA:
            c = {"mamba": mamba.init_cache(cfg, batch)}
        else:
            c = {"rwkv": rwkv6.init_cache(cfg, batch)}
        caches[f"pos{j}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_blocks,) + a.shape), c)
    return caches


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, segment_ids=None, caches=None,
            ctx: Optional[FwdCtx] = None):
    """Returns (logits_or_hidden, new_caches, aux dict)."""
    ctx = ctx or FwdCtx()
    compute_dtype = jnp.dtype(cfg.dtype)

    if embeds is not None:
        x = embeds.astype(compute_dtype)
        if "in_proj" in params:
            x = jnp.einsum("bse,ed->bsd", x,
                           params["in_proj"]["w"].astype(compute_dtype))
    else:
        x = embed.encode(params["embed"], tokens, compute_dtype)

    B, S = x.shape[0], x.shape[1]
    if positions is None and ctx.mode != "decode":
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    period = cfg.block_period
    kinds, ffns = cfg.layer_kinds, cfg.ffn_kinds

    def block_body(carry, xs):
        x, lb, drop, imb = carry
        bp, bc = xs
        if ctx.hidden_constrain is not None:
            # anchor the activation layout every block: stops SPMD sharding
            # drift (e.g. MQA's unshardable kv head replicating the batch)
            x = ctx.hidden_constrain(x)
        new_bc = {} if bc is not None else None
        for j in range(period):
            cache_j = bc[f"pos{j}"] if bc is not None else None
            lp = bp[f"pos{j}"]
            if ctx.block_constrain is not None:
                # ZeRO-3: gather THIS layer's FSDP-sharded weights just
                # before use (loop-variant — the scan slices a different
                # block each iteration, so the all-gather is not hoisted;
                # per-position granularity keeps only one layer's gathered
                # weights live).  Its VJP reduce-scatters dW.
                lp = ctx.block_constrain(lp, j)

            x, nc, l, st = _layer_apply(lp, x, cfg, kinds[j], ffns[j],
                                        ctx, cache_j, positions, segment_ids)
            if new_bc is not None:
                new_bc[f"pos{j}"] = nc
            lb = lb + l
            if st is not None:
                # mean drop across MoE layers; worst-layer imbalance (the
                # straggler expert matmul).  NaN (shard_map: unmeasured)
                # propagates through both — never coerced to 0.0.
                drop = drop + st[0]
                imb = jnp.maximum(imb, st[1])
        return (x, lb, drop, imb), new_bc

    body = block_body
    if ctx.mode == "train" and cfg.remat and ctx.remat:
        body = jax.checkpoint(block_body, prevent_cse=False)

    zero = jnp.zeros((), jnp.float32)
    carry0 = (x, zero, zero, zero)       # (x, lb, moe drop sum, moe imb max)
    n_blocks = cfg.n_layers // period
    if cfg.scan_layers and caches is not None and ctx.mode == "decode":
        # decode: keep the stacked caches in the scan CARRY and update the
        # current block's slice in place — scan xs/ys would double-buffer
        # the whole multi-GB cache (input and output live simultaneously).
        def decode_body(carry, xs):
            x_lb, caches_all = carry
            bp, i = xs
            bc = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                caches_all)
            new_x_lb, new_bc = body(x_lb, (bp, bc))
            caches_all = jax.tree.map(
                lambda a, nc: jax.lax.dynamic_update_index_in_dim(
                    a, nc.astype(a.dtype), i, 0),
                caches_all, new_bc)
            return (new_x_lb, caches_all), None

        ((x, lb, drop, imb), new_caches), _ = jax.lax.scan(
            decode_body, (carry0, caches),
            (params["blocks"], jnp.arange(n_blocks)))
    elif cfg.scan_layers:
        (x, lb, drop, imb), new_caches = jax.lax.scan(
            body, carry0, (params["blocks"], caches))
    else:
        new_list = []
        carry = carry0
        for b in range(n_blocks):
            bp = jax.tree.map(lambda a: a[b], params["blocks"])
            bc = jax.tree.map(lambda a: a[b], caches) if caches is not None else None
            carry, nc = body(carry, (bp, bc))
            new_list.append(nc)
        x, lb, drop, imb = carry
        new_caches = None
        if caches is not None:
            new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)

    x = norms.rms_apply(params["final_norm"], x, cfg.norm_eps)
    n_moe_layers = sum(1 for f in ffns if f == FFNKind.MOE)
    total_moe = n_moe_layers * n_blocks         # MoE applications per forward
    nan = jnp.full((), jnp.nan, jnp.float32)
    aux = {
        "lb_loss": lb / max(1, n_moe_layers),
        # NaN (not 0.0) when the model has no MoE layers at all
        "moe_drop_rate": drop / total_moe if total_moe else nan,
        "moe_imbalance": imb if total_moe else nan,
    }
    if ctx.return_hidden or not (cfg.has_lm_head and cfg.vocab_size > 0):
        return x, new_caches, aux
    with jax.named_scope("dflop.head"):
        if cfg.tie_embeddings:
            logits = embed.decode(params["embed"], x)
        else:
            logits = embed.unembed(params["unembed"], x)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    if ctx.logits_constrain is not None:
        logits = ctx.logits_constrain(logits)
    return logits, new_caches, aux


def decode_step(params, cfg: ModelConfig, token, caches, pos,
                ctx: Optional[FwdCtx] = None):
    """One decode step. token: (B,) int32 (or (B,1)); pos: scalar int, or a
    (B,) array of per-row positions when batch rows hold independent
    requests at different depths (continuous batching — see repro.serve)."""
    ctx = ctx or FwdCtx(mode="decode", remat=False)
    ctx.mode = "decode"
    ctx.decode_pos = pos
    if token.ndim == 1:
        token = token[:, None]
    logits, new_caches, aux = forward(params, cfg, tokens=token,
                                      caches=caches, ctx=ctx)
    return logits[:, 0], new_caches, aux
