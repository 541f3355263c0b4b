"""GQA/MQA attention with packing-aware masking, sliding window and caches.

Three interchangeable implementations of the same math:

  * ``naive``   — materializes the full score matrix (oracle, small shapes)
  * ``chunked`` — pure-XLA flash attention: double ``lax.scan`` over
                  (q-block, kv-block) tiles with online softmax.  This is the
                  implementation the multi-pod dry-run lowers (bounded memory
                  at 32k sequence length, no Pallas custom-calls on CPU).
  * ``pallas``  — the TPU Pallas kernel (``repro.kernels.packed_flash_attention``),
                  validated in interpret mode against ``naive``.

Segment-id masking implements the paper's sequence packing (§3.2.1):
"Attention operations ... must process each original instance separately to
maintain causal integrity."
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.types import ModelConfig
from repro.models.layers.rope import apply_rope

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Params
# --------------------------------------------------------------------------- #
def init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(kq, (d, h, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, kh, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, kh, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (h, hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }


# --------------------------------------------------------------------------- #
# Masking
# --------------------------------------------------------------------------- #
def make_mask(qpos, kpos, *, causal: bool, window: int,
              seg_q=None, seg_k=None):
    """Boolean mask (broadcast batch, Sq, Sk). True = attend."""
    m = jnp.ones(qpos.shape[-1:] + kpos.shape[-1:], dtype=bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window and window > 0:
        m &= qpos[:, None] - kpos[None, :] < window
    m = m[None]  # add batch dim
    if seg_q is not None and seg_k is not None:
        m = m & (seg_q[:, :, None] == seg_k[:, None, :])
    return m


# --------------------------------------------------------------------------- #
# Naive oracle
# --------------------------------------------------------------------------- #
def attend_naive(q, k, v, *, causal=True, window=0, seg_q=None, seg_k=None,
                 q_offset=0, scale: Optional[float] = None):
    """q: (B,Sq,H,D); k,v: (B,Sk,Kh,D). Returns (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Sq, Kh, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = make_mask(qpos, kpos, causal=causal, window=window,
                     seg_q=seg_q, seg_k=seg_k)          # (B?,Sq,Sk)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (e.g. padding segments) -> zero output
    any_valid = jnp.any(mask, axis=-1)[:, None, None, :, None]
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    out = jnp.where(any_valid.transpose(0, 3, 1, 2, 4), out, 0.0)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Chunked XLA flash attention (custom VJP: FlashAttention-2-style backward)
# --------------------------------------------------------------------------- #
def _pick_block(s: int, target: int) -> int:
    b = min(s, target)
    while s % b:
        b -= 1
    return b


def flash_attention_xla(q, k, v, *, causal=True, window=0, seg_q=None,
                        seg_k=None, q_offset=0, scale=None,
                        block_q=512, block_k=512):
    """Flash attention built from nested lax.scans, with a custom VJP.

    Without the custom VJP, differentiating the scan forward saves the
    per-block probabilities as residuals — the full S^2 attention matrix
    (8+ GB at 32k) — defeating the chunked formulation.  The backward pass
    recomputes p block-by-block from the saved log-sum-exp instead
    (FlashAttention-2), so train-time memory stays O(S * block)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if seg_q is None:
        seg_q = jnp.zeros(q.shape[:2], jnp.int32)
    if seg_k is None:
        seg_k = jnp.zeros(k.shape[:2], jnp.int32)
    return _flash(q, k, v, seg_q, seg_k, causal, window, q_offset, scale,
                  block_q, block_k)


def _blockify(q, k, v, seg_q, seg_k, block_q, block_k):
    B, Sq, H, D = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    bq, bk = _pick_block(Sq, block_q), _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    qb = q.reshape(B, nq, bq, Kh, G, D).astype(jnp.float32)
    kb = k.reshape(B, nk, bk, Kh, D).astype(jnp.float32)
    vb = v.reshape(B, nk, bk, Kh, D).astype(jnp.float32)
    sqb = seg_q.reshape(B, nq, bq)
    skb = seg_k.reshape(B, nk, bk)
    return qb, kb, vb, sqb, skb, (B, Sq, H, D, Sk, Kh, G, bq, bk, nq, nk)


def _block_scores(q_i, k_j, qpos, kpos, sq_i, sk_j, causal, window, scale):
    """q_i: (B,bq,Kh,G,D); k_j: (B,bk,Kh,D) -> masked scores (B,Kh,G,bq,bk)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_i, k_j) * scale
    mask = make_mask(qpos, kpos, causal=causal, window=window,
                     seg_q=sq_i, seg_k=sk_j)
    return s, mask


def _flash_fwd_impl(q, k, v, seg_q, seg_k, causal, window, q_offset, scale,
                    block_q, block_k):
    qb, kb, vb, sqb, skb, dims = _blockify(q, k, v, seg_q, seg_k,
                                           block_q, block_k)
    B, Sq, H, D, Sk, Kh, G, bq, bk, nq, nk = dims

    def q_block(_, qi):
        q_i = qb[:, qi]
        sq_i = sqb[:, qi]
        qpos = q_offset + qi * bq + jnp.arange(bq)
        m0 = jnp.full((B, Kh, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kh, G, bq), jnp.float32)
        a0 = jnp.zeros((B, Kh, G, bq, D), jnp.float32)

        def kv_block(c, ki):
            m, l, acc = c
            kpos = ki * bk + jnp.arange(bk)
            s, mask = _block_scores(q_i, kb[:, ki], qpos, kpos, sq_i,
                                    skb[:, ki], causal, window, scale)
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + \
                jnp.einsum("bkgqs,bskd->bkgqd", p, vb[:, ki])
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.where((l > 0)[..., None], out, 0.0)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
        return None, (out.transpose(0, 3, 1, 2, 4), lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, D).astype(q.dtype)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, Kh, G, Sq)
    return out, lse


def _flash_bwd_impl(q, k, v, seg_q, seg_k, out, lse, dout, causal, window,
                    q_offset, scale, block_q, block_k):
    qb, kb, vb, sqb, skb, dims = _blockify(q, k, v, seg_q, seg_k,
                                           block_q, block_k)
    B, Sq, H, D, Sk, Kh, G, bq, bk, nq, nk = dims
    dob = dout.reshape(B, nq, bq, Kh, G, D).astype(jnp.float32)
    # delta_i = rowsum(dout_i * out_i)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)
    delta = delta.reshape(B, nq, bq, Kh, G).transpose(0, 3, 4, 1, 2)
    lseb = lse.reshape(B, Kh, G, nq, bq)

    def p_block(qi, ki):
        q_i = qb[:, qi]
        qpos = q_offset + qi * bq + jnp.arange(bq)
        kpos = ki * bk + jnp.arange(bk)
        s, mask = _block_scores(q_i, kb[:, ki], qpos, kpos, sqb[:, qi],
                                skb[:, ki], causal, window, scale)
        p = jnp.exp(s - lseb[:, :, :, qi][..., None])
        p = jnp.where(mask[:, None, None, :, :], p, 0.0)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dob[:, qi], vb[:, ki])
        ds = p * (dp - delta[:, :, :, qi][..., None]) * scale
        return p, ds

    def dq_block(_, qi):
        def inner(dq_i, ki):
            p, ds = p_block(qi, ki)
            return dq_i + jnp.einsum("bkgqs,bskd->bqkgd", ds, kb[:, ki]), None
        dq0 = jnp.zeros((B, bq, Kh, G, D), jnp.float32)
        dq_i, _ = jax.lax.scan(inner, dq0, jnp.arange(nk))
        return None, dq_i

    _, dqs = jax.lax.scan(dq_block, None, jnp.arange(nq))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, D)

    def dkv_block(_, ki):
        def inner(c, qi):
            dk_j, dv_j = c
            p, ds = p_block(qi, ki)
            dv_j = dv_j + jnp.einsum("bkgqs,bqkgd->bskd", p, dob[:, qi])
            dk_j = dk_j + jnp.einsum("bkgqs,bqkgd->bskd", ds, qb[:, qi])
            return (dk_j, dv_j), None
        z = jnp.zeros((B, bk, Kh, D), jnp.float32)
        (dk_j, dv_j), _ = jax.lax.scan(inner, (z, z), jnp.arange(nq))
        return None, (dk_j, dv_j)

    _, (dks, dvs) = jax.lax.scan(dkv_block, None, jnp.arange(nk))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Kh, D)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Kh, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seg_q, seg_k, causal, window, q_offset, scale,
           block_q, block_k):
    out, _ = _flash_fwd_impl(q, k, v, seg_q, seg_k, causal, window, q_offset,
                             scale, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, seg_q, seg_k, causal, window, q_offset, scale,
                    block_q, block_k):
    out, lse = _flash_fwd_impl(q, k, v, seg_q, seg_k, causal, window,
                               q_offset, scale, block_q, block_k)
    return out, (q, k, v, seg_q, seg_k, out, lse)


def _flash_bwd_rule(causal, window, q_offset, scale, block_q, block_k,
                    res, dout):
    q, k, v, seg_q, seg_k, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, seg_q, seg_k, out, lse, dout,
                                 causal, window, q_offset, scale,
                                 block_q, block_k)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# --------------------------------------------------------------------------- #
# Decode against a KV cache
# --------------------------------------------------------------------------- #
def attend_cache(q, cache_k, cache_v, kpos, pos, *, window=0, scale=None):
    """Single-step decode. q: (B,1,H,D); cache_k/v: (B,C,Kh,D); kpos: (B,C).

    ``pos`` is a scalar (lockstep batch) or a ``(B,)`` array — continuous
    batching mixes requests at different decode positions in one batch, so
    each row carries its own validity mask ``kpos[b] <= pos[b]``: a row
    only ever attends to its own request's cache entries, never to stale
    slots left by a request that previously occupied the row.

    The cache stays in its storage dtype end-to-end: upcasting it (or
    requesting f32 dot accumulation on the CPU backend) materializes an fp32
    copy of the entire stacked cache — XLA hoists the convert out of the
    layer loop.  Scores dot accumulates in the cache dtype (D ≤ 256 terms),
    softmax runs in fp32 on the small score tensor, and the p·V reduction
    accumulates in the cache dtype (p sums to 1; relative error ~1e-3 in
    bf16 — the standard serving trade-off, exact when caches are fp32)."""
    B, _, H, D = q.shape
    C, Kh = cache_k.shape[1], cache_k.shape[2]
    G = H // Kh
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Kh, G, D).astype(cache_k.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k).astype(jnp.float32) * scale
    pos_b = check_decode_pos(pos, B)[:, None]                       # (B, 1)
    valid = (kpos >= 0) & (kpos <= pos_b)
    if window and window > 0:
        valid &= pos_b - kpos < window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(cache_v.dtype), cache_v)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Cache plumbing
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """KV cache for one attention layer. Sliding-window archs use a ring
    buffer of size window (TPU-friendly: fixed shapes, modular write).

    ``kpos`` is per-row ``(batch, C)``: batch rows hold independent
    requests under continuous batching, each with its own position clock
    and validity mask."""
    C = min(max_len, cfg.window_size) if cfg.window_size else max_len
    return {
        "k": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.head_dim), dtype),
        "kpos": jnp.full((batch, C), -1, jnp.int32),
    }


def kv_cache_bytes(cfg: ModelConfig, seq_len: int,
                   bytes_per_value: int = 2) -> float:
    """Bytes of live KV state for one request at context ``seq_len``
    (K + V across all layers) — the payload a prefill→decode handoff
    moves, priced by the serving loop as bytes/bandwidth + latency.
    Lives next to `init_cache` so the transfer cost model and the cache
    layout can never drift apart."""
    kv_heads = cfg.n_kv_heads or cfg.n_heads or 1
    head_dim = cfg.head_dim or (cfg.d_model // max(cfg.n_heads, 1))
    return 2.0 * cfg.n_layers * kv_heads * head_dim \
        * bytes_per_value * seq_len


def check_decode_pos(pos, B: int):
    """Enforce the decode-position contract: a scalar (all rows advance in
    lockstep) or a ``(B,)`` vector of per-row positions (continuous
    batching).  Returns the ``(B,)`` int32 form; any other shape raises —
    silently broadcasting e.g. a ``(B, 1)`` or wrong-batch array would
    write KV rows at the wrong slots with no error."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.broadcast_to(pos, (B,))
    if pos.shape != (B,):
        raise ValueError(
            f"decode_pos must be a scalar or shape ({B},), got {pos.shape}")
    return pos


def cache_write(cache, k_new, v_new, pos):
    """Write one token (k_new: (B,1,Kh,D)) at each row's ring slot
    ``pos % C``.  ``pos``: scalar (all rows in lockstep) or ``(B,)``
    per-row positions (continuous batching)."""
    B, C = cache["k"].shape[0], cache["k"].shape[1]
    pos_b = check_decode_pos(pos, B)
    slot = pos_b % C
    rows = jnp.arange(B)
    return {
        "k": cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype)),
        "kpos": cache["kpos"].at[rows, slot].set(pos_b),
    }


# --------------------------------------------------------------------------- #
# Layer apply
# --------------------------------------------------------------------------- #
def apply(params, x, cfg: ModelConfig, *, positions=None, segment_ids=None,
          cache=None, decode_pos=None, impl: str = "chunked",
          block: int = 512):
    """Self-attention layer.

    Train/prefill: cache is None, x is (B,S,d).
    Decode: cache is the layer cache, x is (B,1,d), decode_pos a scalar
    or a (B,) array of per-row positions (continuous batching).
    Returns (y, new_cache).
    """
    B, S, d = x.shape
    window = cfg.window_size if cfg.attention_kind == "sliding" else 0
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))

    if cache is not None:
        pos = check_decode_pos(decode_pos, B)
        if cfg.use_rope:
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
        cache = cache_write(cache, k, v, pos)
        with jax.named_scope("dflop.attention"):
            out = attend_cache(q, cache["k"], cache["v"], cache["kpos"], pos,
                               window=window)
    else:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("dflop.attention"):
            if impl == "naive":
                out = attend_naive(q, k, v, causal=cfg.causal, window=window,
                                   seg_q=segment_ids, seg_k=segment_ids)
            elif impl == "pallas":
                from repro.kernels import ops as kops
                out = kops.packed_flash_attention(
                    q, k, v, segment_ids=segment_ids, causal=cfg.causal,
                    window=window)
            else:
                out = flash_attention_xla(q, k, v, causal=cfg.causal,
                                          window=window, seg_q=segment_ids,
                                          seg_k=segment_ids,
                                          block_q=block, block_k=block)

    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, cache
