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
in VMEM scratch carried across kv steps.

Operands reach the MXU in the dtype they arrive in (bfloat16 in a bfloat16
model) with float32 accumulation; the running max, denominator,
accumulators, ``lse`` and ``delta`` are float32, and the probabilities
``p`` and ``ds`` are rounded to the operand dtype only where they enter a
matmul (PV, dV, dQ, dK).  Float32 inputs run float32 throughout.  The
softmax scale multiplies the float32 scores.

Tile skipping: the wrapper reduces the segment ids to each q block's and
k block's [min, max] and hands them, with the range of k blocks (q blocks
for dk/dv) each row of tiles may touch, to the kernels as scalar prefetch.
A tile whose segment ranges cannot meet, or (causal) that lies wholly
above the diagonal, or (window) wholly outside the window, does no work;
a tile that no mask entry can hide (one segment on both sides, wholly
below the diagonal and inside the window) skips the mask.  The index maps
clamp the block they fetch into the row's live range, so a skipped tile
fetches no new block.  A skipped tile adds exactly what a fully masked
one would (nothing), so outputs, ``lse`` and gradients are unchanged.

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

from repro.kernels.blocking import pad_axis, pad_segments

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Tile classes: one definition for the wrapper (arrays) and kernels (scalars)
# --------------------------------------------------------------------------- #
def _tile_live(qlo, qhi, klo, khi, iq, ik, *, causal: bool, window: int,
               bq: int, bk: int):
    """True where tile (iq, ik) may hold an entry no mask hides: the
    blocks' segment ranges [lo, hi] meet, and the tile is not wholly above
    the diagonal (causal) or wholly outside the window."""
    live = (qlo <= khi) & (klo <= qhi)
    if causal:
        live &= ik * bk <= iq * bq + bq - 1
    if window > 0:
        live &= iq * bq - (ik * bk + bk - 1) < window
    return live


def _tile_full(qlo, qhi, klo, khi, iq, ik, *, causal: bool, window: int,
               bq: int, bk: int):
    """True where no entry of tile (iq, ik) is masked: one segment on both
    sides, wholly on or below the diagonal and inside the window."""
    full = (qlo == qhi) & (klo == khi) & (qlo == klo)
    if causal:
        full &= ik * bk + bk - 1 <= iq * bq
    if window > 0:
        full &= iq * bq + bq - 1 - ik * bk < window
    return full


def _bounds(seg, block: int):
    """(B, S) segment ids -> each block's (min, max), (B, S // block)."""
    s = seg.reshape(seg.shape[0], -1, block)
    return s.min(axis=-1), s.max(axis=-1)


def _live_tiles(seg_q, seg_k, *, causal: bool, window: int, bq: int,
               bk: int):
    """(B, nq, nk) bool: the tiles the kernels compute, for segment ids
    already padded to the block grid."""
    qlo, qhi = _bounds(seg_q, bq)
    klo, khi = _bounds(seg_k, bk)
    iq = jnp.arange(qlo.shape[1])[None, :, None]
    ik = jnp.arange(klo.shape[1])[None, None, :]
    return _tile_live(qlo[:, :, None], qhi[:, :, None], klo[:, None, :],
                      khi[:, None, :], iq, ik, causal=causal, window=window,
                      bq=bq, bk=bk)


def _fetch_range(live):
    """First and last live tile of each row of ``live`` (B, n, m), flat
    (B·n,) int32; a row with none fetches block 0."""
    m = live.shape[-1]
    any_ = live.any(axis=-1)
    first = jnp.where(any_, jnp.argmax(live, axis=-1), 0)
    last = jnp.where(any_, m - 1 - jnp.argmax(live[..., ::-1], axis=-1), 0)
    return (first.reshape(-1).astype(jnp.int32),
            last.reshape(-1).astype(jnp.int32))


def _skip_table(seg_q, seg_k, *, causal, window, bq, bk):
    """The scalar-prefetch operands every kernel takes: block bounds
    (qlo, qhi, klo, khi), the kv range each q row fetches and the q range
    each kv column fetches — flat int32 vectors."""
    qlo, qhi = _bounds(seg_q, bq)
    klo, khi = _bounds(seg_k, bk)
    live = _live_tiles(seg_q, seg_k, causal=causal, window=window, bq=bq,
                      bk=bk)
    flat = [a.reshape(-1).astype(jnp.int32) for a in (qlo, qhi, klo, khi)]
    return (*flat, *_fetch_range(live),
            *_fetch_range(live.transpose(0, 2, 1)))


def _classes(refs, b, iq, ik, *, nq, nk, causal, window, bq, bk):
    """(live, full) of tile (iq, ik) of row b, read from the prefetch."""
    qlo_r, qhi_r, klo_r, khi_r = refs[:4]
    bounds = (qlo_r[b * nq + iq], qhi_r[b * nq + iq],
              klo_r[b * nk + ik], khi_r[b * nk + ik])
    kw = dict(causal=causal, window=window, bq=bq, bk=bk)
    return (_tile_live(*bounds, iq, ik, **kw),
            _tile_full(*bounds, iq, ik, **kw))


def _clamp(x, lo, hi):
    return jnp.minimum(jnp.maximum(x, lo), hi)


N_PREFETCH = 8                # the vectors _skip_table returns


# --------------------------------------------------------------------------- #
# Kernel bodies
# --------------------------------------------------------------------------- #
def _tile_mask(iq, ik, seg_q, seg_k, *, causal: bool, window: int,
               g: int, bq: int, bk: int):
    """Boolean (G·bq, bk) attend-mask for tile (iq, ik) — the ONE masking
    definition all three kernels (fwd, dq, dkv) share.  Row r of the folded
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


def _when_tile(live, full, body):
    """Run ``body(masked)`` on a live tile: unmasked where it is full."""
    pl.when(full)(lambda: body(False))
    pl.when(live & jnp.logical_not(full))(lambda: body(True))


def _rows(ref):
    """(G, bq, X) block -> folded (G·bq, X) tile, in the block's dtype."""
    x = ref[0, 0]
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _store_rows(ref, x):
    """Folded (G·bq, X) tile -> (G, bq, X) block."""
    ref[0, 0] = x.reshape(ref.shape[2:]).astype(ref.dtype)


def _nt(a, b):
    """a · bᵀ on the MXU: (m, k) × (n, k) -> (m, n), float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """aᵀ · b on the MXU: (k, m) × (k, n) -> (m, n), a rounded to b's
    dtype."""
    return jax.lax.dot_general(a.astype(b.dtype), b,
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """a · b on the MXU, a rounded to b's dtype, float32 result."""
    return jnp.dot(a.astype(b.dtype), b, preferred_element_type=jnp.float32)


def _fwd_kernel(*refs, scale: float, causal: bool, window: int, nq: int,
                nk: int, g: int, bq: int, bk: int):
    pre = refs[:N_PREFETCH]
    (q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, o_ref, lse_ref,
     m_scr, l_scr, acc_scr) = refs[N_PREFETCH:]
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked):
        q = _rows(q_ref)                             # (G·bq, D)
        k, v = k_ref[0, 0], v_ref[0, 0]              # (bk, D)
        s = _nt(q, k) * scale                        # (G·bq, bk) f32
        if masked:
            mask = _tile_mask(iq, ik, seg_q_ref[0, 0], seg_k_ref[0, 0],
                              causal=causal, window=window, g=g, bq=bq,
                              bk=bk)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                          # (G·bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            # explicit mask select: on a row masked in every tile m_new
            # stays at NEG_INF and exp(s - m_new) would be exp(0) = 1,
            # silently averaging v; zeroed p keeps l at 0 so the finalize
            # guard emits exact zeros
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + _nn(p, v)
        m_scr[...] = m_new

    _when_tile(*_classes(pre, b, iq, ik, nq=nq, nk=nk, causal=causal,
                         window=window, bq=bq, bk=bk), tile)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        live = l > 0
        inv = jnp.where(live, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        _store_rows(o_ref, acc_scr[...] * inv)
        lse = jnp.where(live, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)),
                        NEG_INF)
        _store_rows(lse_ref, lse)


def _tile_p_ds(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, do_ref, lse_ref,
               delta_ref, iq, ik, masked, *, scale, causal, window, g, bq,
               bk):
    """Recompute (q, k, do, p, ds) for one folded tile from the saved
    residuals.

    s − lse ≤ 0 for every unmasked entry (lse = m + log l ≥ m), so the exp
    cannot overflow; fully-masked rows have lse = NEG_INF and are zeroed by
    the mask select, which a full tile (no masked entry) does not need."""
    q, do = _rows(q_ref), _rows(do_ref)              # (G·bq, D)
    k, v = k_ref[0, 0], v_ref[0, 0]                  # (bk, D)
    p = jnp.exp(_nt(q, k) * scale - _rows(lse_ref))  # (G·bq, bk) f32
    if masked:
        mask = _tile_mask(iq, ik, seg_q_ref[0, 0], seg_k_ref[0, 0],
                          causal=causal, window=window, g=g, bq=bq, bk=bk)
        p = jnp.where(mask, p, 0.0)
    ds = p * (_nt(do, v) - _rows(delta_ref)) * scale
    return q, k, do, p, ds


def _bwd_dq_kernel(*refs, scale: float, causal: bool, window: int, nq: int,
                   nk: int, g: int, bq: int, bk: int):
    """dq = Σ_j ds_ij · k_j.  Grid (B, KH, nq, nk), kv innermost."""
    pre = refs[:N_PREFETCH]
    ins, (dq_ref, dq_scr) = refs[N_PREFETCH:-2], refs[-2:]
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(masked):
        _, k, _, _, ds = _tile_p_ds(*ins, iq, ik, masked, scale=scale,
                                    causal=causal, window=window, g=g,
                                    bq=bq, bk=bk)
        dq_scr[...] += _nn(ds, k)

    _when_tile(*_classes(pre, b, iq, ik, nq=nq, nk=nk, causal=causal,
                         window=window, bq=bq, bk=bk), tile)

    @pl.when(ik == nk - 1)
    def _finalize():
        _store_rows(dq_ref, dq_scr[...])


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, window: int, nq: int,
                    nk: int, g: int, bq: int, bk: int):
    """dk_j = Σ_i ds_ijᵀ q_i, dv_j = Σ_i p_ijᵀ do_i.
    Grid (B, KH, nk, nq), the q axis innermost/sequential."""
    pre = refs[:N_PREFETCH]
    ins = refs[N_PREFETCH:-4]
    dk_ref, dv_ref, dk_scr, dv_scr = refs[-4:]
    b, ik, iq = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(masked):
        q, _, do, p, ds = _tile_p_ds(*ins, iq, ik, masked, scale=scale,
                                     causal=causal, window=window, g=g,
                                     bq=bq, bk=bk)
        # the folded G·bq rows are one contraction axis: (G·bq, bk)ᵀ(G·bq, D)
        dv_scr[...] += _tn(p, do)
        dk_scr[...] += _tn(ds, q)

    _when_tile(*_classes(pre, b, iq, ik, nq=nq, nk=nk, causal=causal,
                         window=window, bq=bq, bk=bk), tile)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------- #
# pallas_call wrappers (shapes already padded to the block grid)
#
# Row statistics (lse, delta) travel as (B, KH, G, S, 1) columns and segment
# ids as (B, 1, S) rows: a block's last two dims must equal the array's or
# be (8, 128)-aligned, and both then load without a relayout.  Index maps
# take the grid indices then the prefetch refs: a q-side block of tile
# (i, j) is block i, a kv-side block is block j, each clamped into the
# range its row (fwd, dq: kv range of q row i; dkv: q range of kv column
# j) fetches, so skipped tiles re-use the block already in VMEM.
# --------------------------------------------------------------------------- #
def _specs(G, bq, bk, D, nq, nk, *, kv_inner: bool):
    """BlockSpecs (q, row, kv, seg_q, seg_k) for a grid (b, h, x, y) whose
    innermost axis y runs over kv blocks (``kv_inner``) or over q blocks.
    Only the innermost index is clamped, so output blocks, indexed by the
    outer one, are written where they belong."""
    if kv_inner:                     # grid (b, h, i, j)
        def ij(b, x, y, pre):
            return x, _clamp(y, pre[4][b * nq + x], pre[5][b * nq + x])
    else:                            # grid (b, h, j, i)
        def ij(b, x, y, pre):
            return _clamp(y, pre[6][b * nk + x], pre[7][b * nk + x]), x

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda b, h, x, y, *pre: index(
            b, h, *ij(b, x, y, pre)))

    return (spec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            spec((1, 1, G, bq, 1), lambda b, h, i, j: (b, h, 0, i, 0)),
            spec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            spec((1, 1, bq), lambda b, h, i, j: (b, 0, i)),
            spec((1, 1, bk), lambda b, h, i, j: (b, 0, j)))


def _fwd_call(q, k, v, seg_q, seg_k, causal, window, bq, bk, interpret):
    B, KH, G, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    common = dict(scale=D ** -0.5, causal=causal, window=window, nq=nq,
                  nk=nk, g=G, bq=bq, bk=bk)
    pre = _skip_table(seg_q, seg_k, causal=causal, window=window, bq=bq,
                      bk=bk)
    q_spec, row_spec, kv_spec, sq_spec, sk_spec = _specs(
        G, bq, bk, D, nq, nk, kv_inner=True)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=N_PREFETCH,
            grid=(B, KH, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, sq_spec, sk_spec],
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, G, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, KH, G, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*pre, q, k, v, seg_q[:, None], seg_k[:, None])


def _bwd_call(q, k, v, seg_q, seg_k, out, lse, dout, causal, window,
              bq, bk, interpret):
    B, KH, G, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    common = dict(scale=D ** -0.5, causal=causal, window=window, nq=nq,
                  nk=nk, g=G, bq=bq, bk=bk)
    pre = _skip_table(seg_q, seg_k, causal=causal, window=window, bq=bq,
                      bk=bk)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)          # (B, KH, G, Sq, 1)
    args = (*pre, q, k, v, seg_q[:, None], seg_k[:, None], dout, lse, delta)

    q_spec, row_spec, kv_spec, sq_spec, sk_spec = _specs(
        G, bq, bk, D, nq, nk, kv_inner=True)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=N_PREFETCH,
            grid=(B, KH, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, sq_spec, sk_spec, q_spec,
                      row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((G * bq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Sq, D), q.dtype),
        interpret=interpret,
    )(*args)

    q_spec, row_spec, kv_spec, sq_spec, sk_spec = _specs(
        G, bq, bk, D, nq, nk, kv_inner=False)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=N_PREFETCH,
            grid=(B, KH, nk, nq),
            in_specs=[q_spec, kv_spec, kv_spec, sq_spec, sk_spec, q_spec,
                      row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, KH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, KH, Sk, D), v.dtype)],
        interpret=interpret,
    )(*args)
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
    Sq = q.shape[3]
    bq, Sq_p, seg_q = pad_segments(seg_q, block_q)
    bk, Sk_p, seg_k = pad_segments(seg_k, block_k)
    q = pad_axis(q, Sq_p, axis=3)
    k = pad_axis(k, Sk_p, axis=2)
    v = pad_axis(v, Sk_p, axis=2)
    out = _flash(q, k, v, seg_q, seg_k, causal, window, bq, bk, interpret)
    return out[:, :, :, :Sq]


def skip_counts(seg_q, seg_k, *, causal: bool = True, window: int = 0,
                block_q: int = 512, block_k: int = 512):
    """(skipped, total) score elements per head of the call's grid, float32:
    the elements of the tiles ``packed_flash_attention_bkgsd`` skips, from
    the same padding and block bounds."""
    bq, _, seg_q = pad_segments(seg_q, block_q)
    bk, _, seg_k = pad_segments(seg_k, block_k)
    live = _live_tiles(seg_q, seg_k, causal=causal, window=window, bq=bq,
                      bk=bk)
    area = float(bq * bk)
    return (jnp.sum(~live, dtype=jnp.float32) * area,
            jnp.float32(live.size * area))
