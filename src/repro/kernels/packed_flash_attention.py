"""Packed flash attention — Pallas TPU kernel, forward + custom VJP.

The paper's sequence packing (§3.2.1) requires attention to "process each
original instance separately to maintain causal integrity": these kernels
fuse segment-id masking (packing boundaries), causality and an optional
sliding window into an online-softmax flash attention with explicit VMEM
tiling.

Layout: q is pre-arranged as (B, KH, G, S, D) (G = query groups per KV
head — GQA/MQA-native, so each KV block is loaded once for all G groups),
k/v as (B, KH, S, D).  Grid (B, KH, nq, nk) with the kv axis innermost and
sequential; the online-softmax running max / denominator / accumulator live
in VMEM scratch carried across kv steps.  Default (bq, bk) = (512, 512) —
MXU-aligned multiples of 128 — keeps the working set
    q (G·bq·D) + k,v (2·bk·D) + acc (G·bq·D) + p (G·bq·bk)       [f32]
at a few MiB, inside the 16 MiB v5e VMEM for G ≤ 8, D ≤ 256.

Backward (FlashAttention-2 style, ``docs/kernels.md``): the forward also
emits the per-row log-sum-exp; the backward recomputes the probabilities
p = exp(s − lse) block-by-block from the saved (o, lse) residuals instead
of storing the S² attention matrix, with the delta trick
Δ = rowsum(dout ⊙ o) so ds = p·(dp − Δ)·scale.  Two kernels share the
forward's masking: dq accumulates over kv blocks (same grid orientation as
the forward), dk/dv accumulate over q blocks (grid (B, KH, nk, nq), the q
axis innermost).  Non-multiple sequence lengths are padded to the block
grid (``repro.kernels.blocking``); padded positions carry segment id −1 so
the segment mask hides them, and the pad/slice transposes drop their
cotangents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import PAD_SEGMENT, pad_axis, pick_block

NEG_INF = -1e30


def _tile_mask(iq, ik, seg_q, seg_k, *, causal: bool, window: int,
               g: int, bq: int, bk: int):
    """Boolean (G·bq, bk) attend-mask for tile (iq, ik) — the ONE masking
    definition all four kernels (fwd, dq, dkv) share.  Row r of the folded
    tile is query position iq·bq + r mod bq of group r div bq.  Positions
    and segment ids are folded as int32; no bool is ever reshaped."""
    def fold(x):                                       # (G, bq, bk) -> 2-D
        return x.reshape(g * bq, bk)

    qpos = iq * bq + fold(jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1))
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 1)
    sq = fold(jnp.broadcast_to(seg_q[None, :, None], (g, bq, bk)))
    mask = sq == seg_k[None, :]
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= qpos - kpos < window
    return mask


def _rows(ref):
    """(G, bq, X) block -> folded (G·bq, X) f32 tile."""
    x = ref[0, 0].astype(jnp.float32)
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _store_rows(ref, x):
    """Folded (G·bq, X) tile -> (G, bq, X) block."""
    ref[0, 0] = x.reshape(ref.shape[2:]).astype(ref.dtype)


def _nt(a, b):
    """a · bᵀ on the MXU: (m, k) × (n, k) -> (m, n)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """aᵀ · b on the MXU: (k, m) × (k, n) -> (m, n)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                window: int, nk: int, g: int, bq: int, bk: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = _rows(q_ref)                                 # (G·bq, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)              # (bk, D)

    mask = _tile_mask(iq, ik, seg_q_ref[0, 0], seg_k_ref[0, 0],
                      causal=causal, window=window, g=g, bq=bq, bk=bk)
    s = jnp.where(mask, _nt(q, k) * scale, NEG_INF)  # (G·bq, bk)

    m_prev = m_scr[...]                              # (G·bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    # explicit mask select: on a row masked in every tile m_new stays at
    # NEG_INF and exp(s - m_new) would be exp(0) = 1, silently averaging
    # v; zeroed p keeps l at 0 so the finalize guard emits exact zeros
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + _nn(p, v)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        live = l > 0
        inv = jnp.where(live, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        _store_rows(o_ref, acc_scr[...] * inv)
        lse = jnp.where(live, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)),
                        NEG_INF)
        _store_rows(lse_ref, lse)


def _tile_p_ds(q, k, v, do, lse, delta, mask, *, scale: float):
    """Recompute (p, ds) for one folded tile from the saved residuals.

    s − lse ≤ 0 for every unmasked entry (lse = m + log l ≥ m), so the exp
    cannot overflow; fully-masked rows have lse = NEG_INF and are zeroed by
    the mask select."""
    s = jnp.where(mask, _nt(q, k) * scale, NEG_INF)  # (G·bq, bk)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    ds = p * (_nt(do, v) - delta) * scale
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_scr, *, scale: float,
                   causal: bool, window: int, nk: int, g: int, bq: int,
                   bk: int):
    """dq = Σ_j ds_ij · k_j.  Grid (B, KH, nq, nk), kv innermost."""
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    mask = _tile_mask(iq, ik, seg_q_ref[0, 0], seg_k_ref[0, 0],
                      causal=causal, window=window, g=g, bq=bq, bk=bk)
    k = k_ref[0, 0].astype(jnp.float32)
    _, ds = _tile_p_ds(_rows(q_ref), k, v_ref[0, 0].astype(jnp.float32),
                       _rows(do_ref), _rows(lse_ref), _rows(delta_ref), mask,
                       scale=scale)
    dq_scr[...] += _nn(ds, k)

    @pl.when(ik == nk - 1)
    def _finalize():
        _store_rows(dq_ref, dq_scr[...])


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale: float, causal: bool, window: int, nq: int,
                    g: int, bq: int, bk: int):
    """dk_j = Σ_i ds_ijᵀ q_i, dv_j = Σ_i p_ijᵀ do_i.
    Grid (B, KH, nk, nq), the q axis innermost/sequential."""
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q, do = _rows(q_ref), _rows(do_ref)              # (G·bq, D)
    mask = _tile_mask(iq, ik, seg_q_ref[0, 0], seg_k_ref[0, 0],
                      causal=causal, window=window, g=g, bq=bq, bk=bk)
    p, ds = _tile_p_ds(q, k_ref[0, 0].astype(jnp.float32),
                       v_ref[0, 0].astype(jnp.float32), do,
                       _rows(lse_ref), _rows(delta_ref), mask, scale=scale)
    # the folded G·bq rows are one contraction axis: (G·bq, bk)ᵀ(G·bq, D)
    dv_scr[...] += _tn(p, do)
    dk_scr[...] += _tn(ds, q)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------- #
# pallas_call wrappers (shapes already padded to the block grid)
#
# Row statistics (lse, delta) travel as (B, KH, G, S, 1) columns and segment
# ids as (B, 1, S) rows: a block's last two dims must equal the array's or
# be (8, 128)-aligned, and both then load without a relayout.
# --------------------------------------------------------------------------- #
def _fwd_call(q, k, v, seg_q, seg_k, causal, window, bq, bk, interpret):
    B, KH, G, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    kernel = functools.partial(_fwd_kernel, scale=D ** -0.5, causal=causal,
                               window=window, nk=nk, g=G, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(B, KH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, G, bq, 1), lambda b, h, i, j: (b, h, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, G, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, KH, G, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, seg_q[:, None], seg_k[:, None])


def _bwd_call(q, k, v, seg_q, seg_k, out, lse, dout, causal, window,
              bq, bk, interpret):
    B, KH, G, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    common = dict(scale=D ** -0.5, causal=causal, window=window, g=G,
                  bq=bq, bk=bk)
    do32 = dout.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1,
                    keepdims=True)                   # (B, KH, G, Sq, 1)
    seg_q, seg_k = seg_q[:, None], seg_k[:, None]

    q_spec = pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0))
    row_spec = pl.BlockSpec((1, 1, G, bq, 1), lambda b, h, i, j: (b, h, 0, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0))
    sq_spec = pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i))
    sk_spec = pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **common),
        grid=(B, KH, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, sq_spec, sk_spec, q_spec,
                  row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G * bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, seg_q, seg_k, dout, lse, delta)

    # q axis innermost: same index maps, grid dims (j, i) swapped
    def swap(spec):
        return pl.BlockSpec(spec.block_shape,
                            lambda b, h, j, i: spec.index_map(b, h, i, j))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **common),
        grid=(B, KH, nk, nq),
        in_specs=[swap(s) for s in (q_spec, kv_spec, kv_spec, sq_spec,
                                    sk_spec, q_spec, row_spec, row_spec)],
        out_specs=[swap(kv_spec), swap(kv_spec)],
        out_shape=[jax.ShapeDtypeStruct((B, KH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, KH, Sk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, seg_q, seg_k, dout, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom VJP (block sizes are static; shapes arrive pre-padded)
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, seg_q, seg_k, causal, window, bq, bk, interpret):
    out, _ = _fwd_call(q, k, v, seg_q, seg_k, causal, window, bq, bk,
                       interpret)
    return out


def _flash_fwd_rule(q, k, v, seg_q, seg_k, causal, window, bq, bk, interpret):
    out, lse = _fwd_call(q, k, v, seg_q, seg_k, causal, window, bq, bk,
                         interpret)
    return out, (q, k, v, seg_q, seg_k, out, lse)


def _flash_bwd_rule(causal, window, bq, bk, interpret, res, dout):
    q, k, v, seg_q, seg_k, out, lse = res
    dq, dk, dv = _bwd_call(q, k, v, seg_q, seg_k, out, lse, dout, causal,
                           window, bq, bk, interpret)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def packed_flash_attention_bkgsd(q, k, v, seg_q, seg_k, *, causal: bool = True,
                                 window: int = 0, block_q: int = 512,
                                 block_k: int = 512, interpret: bool = False):
    """q: (B, KH, G, Sq, D); k, v: (B, KH, Sk, D); seg_*: (B, S) int32.
    Returns (B, KH, G, Sq, D).  Differentiable in (q, k, v)."""
    B, KH, G, Sq, D = q.shape
    Sk = k.shape[2]
    bq, Sq_p = pick_block(Sq, block_q)
    bk, Sk_p = pick_block(Sk, block_k)
    q = pad_axis(q, Sq_p, axis=3)
    seg_q = pad_axis(seg_q, Sq_p, axis=1, value=PAD_SEGMENT)
    k = pad_axis(k, Sk_p, axis=2)
    v = pad_axis(v, Sk_p, axis=2)
    seg_k = pad_axis(seg_k, Sk_p, axis=1, value=PAD_SEGMENT)
    out = _flash(q, k, v, seg_q, seg_k, causal, window, bq, bk, interpret)
    return out[:, :, :, :Sq]
