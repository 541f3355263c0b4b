"""MLLM composition: modality encoder -> connector -> LLM (paper §2.1).

This is the object DFLOP optimizes: two architecturally distinct modules with
independent sharding plans, bridged by a connector whose boundary reshard is
the TPU realization of the paper's Inter-model Communicator (§4).

Batch convention (modality frontend stubbed per assignment):
    media_embeds : (B, T_media, embed_dim)  precomputed patch/frame embeds
    media_mask   : (B, T_media)             1 = real media token
    text_tokens  : (B, T_text) int32
    text_mask    : (B, T_text)              1 = real text token
    labels       : (B, T_text) int32        next-token targets (-1 = ignore)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.types import MLLMConfig
from repro.models import model as model_lib
from repro.models.layers import embed as embed_lib
from repro.models.model import FwdCtx


def init(key, mcfg: MLLMConfig):
    ke, kc, kl = jax.random.split(key, 3)
    de, dl = mcfg.encoder.d_model, mcfg.llm.d_model
    dtype = jnp.dtype(mcfg.llm.param_dtype)
    connector: dict = {}
    if mcfg.connector_hidden:
        connector["w1"] = (jax.random.normal(kc, (de, mcfg.connector_hidden))
                           * de ** -0.5).astype(dtype)
        connector["w2"] = (jax.random.normal(jax.random.fold_in(kc, 1),
                                             (mcfg.connector_hidden, dl))
                           * mcfg.connector_hidden ** -0.5).astype(dtype)
    else:
        connector["w1"] = (jax.random.normal(kc, (de, dl)) * de ** -0.5).astype(dtype)
    return {
        "encoder": model_lib.init(ke, mcfg.encoder),
        "connector": connector,
        "llm": model_lib.init(kl, mcfg.llm),
    }


def apply_connector(params, h, mcfg: MLLMConfig):
    w1 = params["w1"].astype(h.dtype)
    if "w2" in params:
        h = jax.nn.gelu(jnp.einsum("bsd,dh->bsh", h, w1))
        return jnp.einsum("bsh,hd->bsd", h, params["w2"].astype(h.dtype))
    return jnp.einsum("bsd,dh->bsh", h, w1)


def encode_media(params, mcfg: MLLMConfig, media_embeds, media_mask=None,
                 ctx: Optional[FwdCtx] = None, communicator=None):
    """Encoder + connector. Returns LLM-space media tokens (B, T_out, dl).

    `ctx` here is the ENCODER's forward context (the encoder may carry its
    own sharding constraints under DFLOP's heterogeneous plans)."""
    ctx = ctx or FwdCtx(mode="train")
    seg = None
    if media_mask is not None:
        # mask -> segment ids: padding gets segment 0, real tokens segment 1.
        # (multi-image packing can supply richer ids via media_mask directly.)
        seg = media_mask.astype(jnp.int32)
    with jax.named_scope("dflop.encoder"):
        h, _, _ = model_lib.forward(params["encoder"], mcfg.encoder,
                                    embeds=media_embeds, segment_ids=seg,
                                    ctx=ctx)
    with jax.named_scope("dflop.connector"):
        if communicator is not None:
            # Inter-model Communicator: reshard encoder output from the
            # encoder's data-parallel layout to the LLM's (paper Fig. 6).
            h = communicator(h)
        h = apply_connector(params["connector"], h, mcfg)
        if mcfg.tokens_per_item_out:
            t_in = h.shape[1]
            factor = max(1, t_in // mcfg.tokens_per_item_out)
            if factor > 1:
                b, _, d = h.shape
                h = h[:, : (t_in // factor) * factor]
                h = h.reshape(b, t_in // factor, factor, d).mean(axis=2)
    return h


def forward_train(params, mcfg: MLLMConfig, batch, ctx: Optional[FwdCtx] = None,
                  communicator=None, enc_ctx: Optional[FwdCtx] = None):
    """Full multimodal forward: returns (logits over text span, aux).

    `enc_ctx` (optional) carries encoder-specific sharding constraints —
    DFLOP's independent per-module parallelism."""
    ctx = ctx or FwdCtx(mode="train")
    media = encode_media(params, mcfg, batch["media_embeds"],
                         batch.get("media_mask"), ctx=enc_ctx or ctx,
                         communicator=communicator)
    with jax.named_scope("dflop.llm"):
        llm_cfg = mcfg.llm
        compute_dtype = jnp.dtype(llm_cfg.dtype)
        text_emb = embed_lib.encode(params["llm"]["embed"],
                                    batch["text_tokens"], compute_dtype)
        x = jnp.concatenate([media.astype(compute_dtype), text_emb], axis=1)
        B, T_m = media.shape[0], media.shape[1]
        T_t = text_emb.shape[1]
        positions = jnp.broadcast_to(jnp.arange(T_m + T_t)[None],
                                     (B, T_m + T_t))
        seg = None
        if "media_mask" in batch and "text_mask" in batch:
            m_seg = jnp.ones((B, T_m), jnp.int32)
            t_seg = jnp.where(batch["text_mask"] > 0, 1, 0).astype(jnp.int32)
            seg = jnp.concatenate([m_seg, t_seg], axis=1)
        logits, _, aux = model_lib.forward(params["llm"], llm_cfg, embeds=x,
                                           positions=positions,
                                           segment_ids=seg, ctx=ctx)
        return logits[:, T_m:], aux
