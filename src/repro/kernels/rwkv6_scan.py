"""RWKV-6 WKV recurrence — chunked Pallas TPU kernel, forward + custom VJP.

    S_t = diag(w_t)·S_{t-1} + k_t v_tᵀ
    y_t = r_t·(S_{t-1} + diag(u)·k_t v_tᵀ)

TPU adaptation: the recurrence is chunked along time.  Grid (B, H, n_chunks)
with the chunk axis innermost/sequential; the (M, M) state lives in VMEM
scratch and crosses chunk iterations without HBM round-trips.  Inside a
chunk a fori_loop moves aligned 8-row tiles of r/k/v/w and unrolls the 8
steps of each (``blocking.row_tile``) — the O(M²) state update is VPU
work on an (M, M) tile, M = 64 lanes wide.

Inputs are pre-arranged (B, H, S, M); outputs match.  The final state
(B, H, M, M) is emitted for decode hand-off.

Backward (``docs/kernels.md``): the forward also emits each chunk's
*initial* state (B, H, n_chunks, M, M); the backward walks chunks in
reverse (index maps close over ``n_chunks − 1 − i``), replays the chunk
into a (chunk, M, M) VMEM history of pre-states S_{t-1}, then runs the
state-adjoint recurrence

    G_{t-1} = diag(w_t)·G_t + r_t ŷ_tᵀ        (G carried across chunks)

per step t descending — the final-state cotangent seeds G at the last
chunk.  dr/dk/dv/dw are written in place; du is emitted as a per-batch
partial (accumulating an output block is only safe across consecutive
innermost-grid revisits) and summed over batch outside the kernel.
Non-multiple lengths are padded (``repro.kernels.blocking``) with
w = 1, r = k = v = 0, so a padded step passes the state through untouched
and the emitted final state stays exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import ROWS, pad_axis, pick_block, round_up, row_tile


def _load(ref, rows):
    return ref[0, 0, rows].astype(jnp.float32)         # (ROWS, M)


def _fwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref,
                sinit_ref, state_scr, *, n_chunks: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    sinit_ref[0, 0, 0] = state_scr[...]                # this chunk's S_{-1}

    u = u_ref[0, 0].astype(jnp.float32)                # (M,)

    def tile_step(i, state):
        rows = row_tile(i)
        r8, k8 = _load(r_ref, rows), _load(k_ref, rows)
        v8, w8 = _load(v_ref, rows), _load(w_ref, rows)
        ys = []
        for j in range(ROWS):
            kv = k8[j][:, None] * v8[j:j + 1]          # (M, M)
            ys.append(jnp.sum(r8[j][:, None] * (state + u[:, None] * kv),
                              axis=0))
            state = w8[j][:, None] * state + kv
        y_ref[0, 0, rows] = jnp.stack(ys).astype(y_ref.dtype)
        return state

    state = jax.lax.fori_loop(0, chunk // ROWS, tile_step, state_scr[...])
    state_scr[...] = state

    @pl.when(ic == n_chunks - 1)
    def _emit():
        s_out_ref[0, 0] = state_scr[...]


def _bwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, sinit_ref, dy_ref, ds_ref,
                dr_ref, dk_ref, dv_ref, dw_ref, du_ref, g_scr, hist_scr,
                *, chunk: int):
    """One reversed-order chunk of the WKV adjoint (see module docstring).

    hist_scr[t] holds the replayed pre-state S_{t-1}; g_scr carries the
    state adjoint G across (reversed) chunk iterations, seeded with the
    final-state cotangent at the last chunk."""
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():                                       # last chunk first
        g_scr[...] = ds_ref[0, 0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)                # (M,)

    def replay(i, state):
        rows = row_tile(i)
        k8, v8, w8 = _load(k_ref, rows), _load(v_ref, rows), _load(w_ref, rows)
        for j in range(ROWS):
            hist_scr[i * ROWS + j] = state
            state = w8[j][:, None] * state + k8[j][:, None] * v8[j:j + 1]
        return state

    jax.lax.fori_loop(0, chunk // ROWS, replay,
                      sinit_ref[0, 0, 0].astype(jnp.float32))

    def bstep(s, carry):
        g, du_acc = carry
        i = chunk // ROWS - 1 - s
        rows = row_tile(i)
        r8, k8 = _load(r_ref, rows), _load(k_ref, rows)
        v8, w8 = _load(v_ref, rows), _load(w_ref, rows)
        dy8 = _load(dy_ref, rows)
        dr, dk, dv, dw = ([None] * ROWS for _ in range(4))
        for j in reversed(range(ROWS)):
            r_t, k_t, v_t, w_t, dy_t = r8[j], k8[j], v8[j], w8[j], dy8[j]
            s_prev = hist_scr[i * ROWS + j]            # (M, M)
            vdy = jnp.sum(v_t * dy_t)                  # scalar <v_t, dy_t>
            dw[j] = jnp.sum(g * s_prev, axis=1)
            dk[j] = jnp.sum(g * v_t[None, :], axis=1) + u * r_t * vdy
            dv[j] = (jnp.sum(g * k_t[:, None], axis=0)
                     + jnp.sum(r_t * u * k_t) * dy_t)
            dr[j] = jnp.sum(s_prev * dy_t[None, :], axis=1) + u * k_t * vdy
            du_acc = du_acc + r_t * k_t * vdy
            g = w_t[:, None] * g + r_t[:, None] * dy_t[None, :]
        dr_ref[0, 0, rows] = jnp.stack(dr)
        dk_ref[0, 0, rows] = jnp.stack(dk)
        dv_ref[0, 0, rows] = jnp.stack(dv)
        dw_ref[0, 0, rows] = jnp.stack(dw)
        return g, du_acc

    g, du_acc = jax.lax.fori_loop(
        0, chunk // ROWS, bstep, (g_scr[...], jnp.zeros_like(u)))
    g_scr[...] = g

    @pl.when(ic == 0)
    def _first():
        du_ref[0, 0, 0] = du_acc

    @pl.when(ic > 0)
    def _rest():
        du_ref[0, 0, 0] += du_acc


# u enters as (H, 1, M) and the du partial leaves as (B, H, 1, M): a block's
# last two dims must equal the array's or be (8, 128)-aligned
def _fwd_call(r, k, v, w, u, c, interpret):
    B, H, S, M = r.shape
    n_chunks = S // c
    seq_spec = pl.BlockSpec((1, 1, c, M), lambda b, h, i: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_chunks=n_chunks, chunk=c),
        grid=(B, H, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, M), lambda b, h, i: (h, 0, 0))],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, M, M), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, M, M), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, M), r.dtype),
            jax.ShapeDtypeStruct((B, H, M, M), jnp.float32),
            jax.ShapeDtypeStruct((B, H, n_chunks, M, M), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((M, M), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, M))


def _bwd_call(r, k, v, w, u, s_init, dy, ds, c, interpret):
    B, H, S, M = r.shape
    n_chunks = S // c
    rev = n_chunks - 1                                 # reversed chunk walk
    f32 = jnp.float32
    seq_spec = pl.BlockSpec((1, 1, c, M), lambda b, h, i: (b, h, rev - i, 0))
    dr, dk, dv, dw, du_p = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=c),
        grid=(B, H, n_chunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, M), lambda b, h, i: (h, 0, 0)),
            pl.BlockSpec((1, 1, 1, M, M), lambda b, h, i: (b, h, rev - i, 0, 0)),
            seq_spec,
            pl.BlockSpec((1, 1, M, M), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, 1, M), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, M), f32),   # dr
            jax.ShapeDtypeStruct((B, H, S, M), f32),   # dk
            jax.ShapeDtypeStruct((B, H, S, M), f32),   # dv
            jax.ShapeDtypeStruct((B, H, S, M), f32),   # dw
            jax.ShapeDtypeStruct((B, H, 1, M), f32),   # du partial (per-B)
        ],
        scratch_shapes=[pltpu.VMEM((M, M), jnp.float32),
                        pltpu.VMEM((c, M, M), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, M), s_init, dy, ds)
    return dr, dk, dv, dw, du_p[:, :, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(r, k, v, w, u, c, interpret):
    y, s_final, _ = _fwd_call(r, k, v, w, u, c, interpret)
    return y, s_final


def _scan_fwd_rule(r, k, v, w, u, c, interpret):
    y, s_final, s_init = _fwd_call(r, k, v, w, u, c, interpret)
    return (y, s_final), (r, k, v, w, u, s_init)


def _scan_bwd_rule(c, interpret, res, cts):
    r, k, v, w, u, s_init = res
    dy, ds = cts
    dr, dk, dv, dw, du_p = _bwd_call(r, k, v, w, u, s_init, dy, ds, c,
                                     interpret)
    return (dr.astype(r.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dw.astype(w.dtype), jnp.sum(du_p, axis=0).astype(u.dtype))


_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_bhsm(r, k, v, w, u, *, chunk: int = 128,
                    interpret: bool = False):
    """r,k,v,w: (B, H, S, M); u: (H, M).
    Returns y: (B, H, S, M), final state (B, H, M, M) f32.
    Differentiable in every array input."""
    B, H, S, M = r.shape
    # the time loop moves whole ROWS-step tiles: round both up to ROWS
    c, S_p = pick_block(round_up(S, ROWS), round_up(chunk, ROWS))
    # w = 1, k = v = 0 on the pad: the state passes through untouched, so
    # the emitted final state is exact and padded y rows are zero.
    r = pad_axis(r, S_p, axis=2)
    k = pad_axis(k, S_p, axis=2)
    v = pad_axis(v, S_p, axis=2)
    w = pad_axis(w, S_p, axis=2, value=1.0)
    y, s_final = _scan(r, k, v, w, u, c, interpret)
    return y[:, :, :S], s_final
