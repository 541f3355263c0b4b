"""Train-step builders: per-family loss + microbatch-scan gradient
accumulation.

The global batch arrives pre-partitioned by the Online Microbatch Scheduler
into N_mb microbatches (leading axis); the step scans over them accumulating
fp32 gradients — the TPU realization of the paper's pipeline microbatching
degrees of freedom (which items share a microbatch is the scheduler's
decision; the step consumes whatever composition it produced).
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.common.types import MLLMConfig, ModelConfig
from repro.models import mllm as mllm_lib
from repro.models import model as model_lib
from repro.models.model import FwdCtx
from repro.train.loss import cross_entropy
from repro.train.optim import AdamWConfig, adamw_update

ModelDesc = Union[ModelConfig, MLLMConfig]

LB_LOSS_WEIGHT = 0.01


def _head_weight(cfg, params):
    """(weight, tied) for the LM head of a decoder param tree."""
    if cfg.tie_embeddings or "unembed" not in params:
        return params["embed"]["w"], True
    return params["unembed"]["w"], False


def make_loss_fn(desc: ModelDesc, ctx: Optional[FwdCtx] = None,
                 communicator=None, vocab_ce: Optional[Callable] = None,
                 enc_ctx: Optional[FwdCtx] = None,
                 with_aux: bool = False) -> Callable:
    """vocab_ce: optional vocab-parallel CE `ce(w, h, labels)` — when given,
    the forward returns hidden states and the head+CE run sharded
    (repro.sharding.vocab_ce).  With ``with_aux`` the loss fn returns
    (loss, aux) for ``jax.value_and_grad(..., has_aux=True)`` so the train
    step can surface the forward's observability aux (MoE drop rate /
    imbalance) without a second forward."""
    ctx = ctx or FwdCtx(mode="train")
    if vocab_ce is not None:
        import dataclasses
        ctx = dataclasses.replace(ctx, return_hidden=True)

    def finish(ce, aux):
        loss = ce + LB_LOSS_WEIGHT * aux["lb_loss"]
        return (loss, aux) if with_aux else loss

    if isinstance(desc, MLLMConfig):
        def loss_fn(params, mb):
            logits, aux = mllm_lib.forward_train(params, desc, mb, ctx=ctx,
                                                 communicator=communicator,
                                                 enc_ctx=enc_ctx)
            with jax.named_scope("dflop.head"):
                if vocab_ce is not None:
                    # with return_hidden, forward_train yields the text-span
                    # hidden states; head + CE run vocab-parallel
                    w, _ = _head_weight(desc.llm, params["llm"])
                    ce = vocab_ce(w, logits, mb["labels"])
                else:
                    ce = cross_entropy(logits, mb["labels"])
            return finish(ce, aux)
        return loss_fn

    if desc.input_embed_dim > 0:
        # encoder-only masked prediction (HuBERT-style): labels -1 = unmasked
        def loss_fn(params, mb):
            out, _, aux = model_lib.forward(
                params, desc, embeds=mb["frame_embeds"],
                segment_ids=mb.get("segment_ids"), ctx=ctx)
            with jax.named_scope("dflop.head"):
                if vocab_ce is not None:
                    w, _ = _head_weight(desc, params)
                    ce = vocab_ce(w, out, mb["labels"])
                else:
                    ce = cross_entropy(out, mb["labels"])
            return finish(ce, aux)
        return loss_fn

    def loss_fn(params, mb):
        out, _, aux = model_lib.forward(
            params, desc, tokens=mb["tokens"],
            positions=mb.get("positions"),
            segment_ids=mb.get("segment_ids"), ctx=ctx)
        with jax.named_scope("dflop.head"):
            if vocab_ce is not None:
                w, _ = _head_weight(desc, params)
                ce = vocab_ce(w, out, mb["labels"])
            else:
                ce = cross_entropy(out, mb["labels"])
        return finish(ce, aux)
    return loss_fn


def make_train_step(desc: ModelDesc, opt_cfg: AdamWConfig,
                    ctx: Optional[FwdCtx] = None, communicator=None,
                    vocab_ce: Optional[Callable] = None,
                    enc_ctx: Optional[FwdCtx] = None,
                    donate: bool = True) -> Callable:
    """step(params, opt_state, batch, lr) -> (params, opt_state, metrics).

    `batch` leaves carry a leading (N_mb,) microbatch axis."""
    loss_fn = make_loss_fn(desc, ctx, communicator, vocab_ce=vocab_ce,
                           enc_ctx=enc_ctx, with_aux=True)

    def train_step(params, opt_state, batch, lr):
        n_mb = jax.tree_util.tree_leaves(batch)[0].shape[0]
        zero = jnp.zeros((), jnp.float32)

        def mb_step(carry, mb):
            loss_sum, drop_sum, imb_max, grads = carry
            (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            with jax.named_scope("dflop.grad_accum"):
                grads = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), grads, g)
            drop_sum = drop_sum + aux["moe_drop_rate"]
            imb_max = jnp.maximum(imb_max, aux["moe_imbalance"])
            return (loss_sum + l, drop_sum, imb_max, grads), None

        with jax.named_scope("dflop.grad_accum"):
            acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
        (loss_sum, drop_sum, imb_max, grads), _ = jax.lax.scan(
            mb_step, (zero, zero, zero, acc0), batch)
        with jax.named_scope("dflop.grad_accum"):
            grads = jax.tree.map(lambda g: g / n_mb, grads)
        with jax.named_scope("dflop.optimizer"):
            new_params, new_opt = adamw_update(opt_cfg, params, grads,
                                               opt_state, lr=lr)
        # NaN-preserving aggregates (no-MoE models report NaN, never 0.0)
        metrics = {"loss": loss_sum / n_mb,
                   "moe_drop_rate": drop_sum / n_mb,
                   "moe_imbalance": imb_max}
        return new_params, new_opt, metrics

    return train_step
