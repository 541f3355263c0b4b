"""Mamba-1 selective scan — chunked Pallas TPU kernel, forward + custom VJP.

    h_t = exp(dt_t ⊗ A) ⊙ h_{t-1} + (dt_t ⊙ u_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ u_t

TPU adaptation: time is chunked; channels are blocked so each program
instance owns a (c_blk, N) state tile in VMEM scratch carried across chunk
iterations.  Grid (B, n_cblk, n_chunks), chunk axis innermost/sequential.
Inside a chunk a fori_loop moves aligned 8-row tiles of every stream and
unrolls the 8 timesteps of each (``blocking.row_tile``).  B_t/C_t (shared across channels) are re-read per channel block — they are
(chunk, N) tiles, tiny next to the (chunk, c_blk) channel streams.

Backward (``docs/kernels.md``): the forward additionally emits each chunk's
*initial* state h_init (B, n_chunks, c_blk·n_cblk, N); the backward walks
chunks in reverse (index maps close over ``n_chunks − 1 − i``), replays the
chunk forward from h_init into a (chunk, c_blk, N) VMEM history, then runs
the adjoint recurrence

    g_t      = G_t + ŷ_t ⊗ C_t            (G carried across chunks in VMEM)
    G_{t-1}  = g_t ⊙ decay_t

per step t descending, producing du/ddt in place and *partial* parameter
grads: dB/dC get a leading channel-block axis and dA/dD a leading batch
axis — Pallas output accumulation is only safe across consecutive
innermost-grid revisits, so cross-(block, batch) sums happen outside the
kernel.  Non-multiple lengths are padded (``repro.kernels.blocking``) with
zeros: dt = 0 makes a padded step the identity (decay = 1, no input), so
outputs, states and gradients of real positions are exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import ROWS, pad_axis, pick_block, round_up, row_tile

# channel block of the backward: its (chunk, c_blk, N) f32 replay history
# pads N to 128 lanes in VMEM, 8 MiB at chunk 128 (the scoped limit is 16)
BWD_C_BLK = 128


def _load(ref, rows):
    return ref[0, rows].astype(jnp.float32)            # (ROWS, ·)


def _fwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, hinit_ref,
                h_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    hinit_ref[0, 0] = h_scr[...]                       # this chunk's h_{-1}

    A = a_ref[...].astype(jnp.float32)                 # (c_blk, N)
    D = d_ref[0].astype(jnp.float32)                   # (c_blk,)

    def tile_step(i, h):
        rows = row_tile(i)
        u8, dt8 = _load(u_ref, rows), _load(dt_ref, rows)
        b8, c8 = _load(b_ref, rows), _load(c_ref, rows)
        ys = []
        for j in range(ROWS):
            u_t, dt_t = u8[j], dt8[j]                  # (c_blk,)
            decay = jnp.exp(dt_t[:, None] * A)         # (c_blk, N)
            h = h * decay + (dt_t * u_t)[:, None] * b8[j:j + 1]
            ys.append(jnp.sum(h * c8[j:j + 1], axis=1) + D * u_t)
        y_ref[0, rows] = jnp.stack(ys).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // ROWS, tile_step, h_scr[...])


def _bwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, hinit_ref, dy_ref,
                du_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                g_scr, hist_scr, *, chunk: int):
    """One reversed-order chunk of the adjoint scan (see module docstring).

    hist_scr[t] holds the replayed pre-state h_{t-1}; g_scr carries the
    state adjoint G across (reversed) chunk iterations."""
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():                                       # last chunk first
        g_scr[...] = jnp.zeros_like(g_scr)

    A = a_ref[...].astype(jnp.float32)                 # (c_blk, N)
    D = d_ref[0].astype(jnp.float32)                   # (c_blk,)

    def replay(i, h):
        rows = row_tile(i)
        u8, dt8, b8 = _load(u_ref, rows), _load(dt_ref, rows), _load(b_ref, rows)
        for j in range(ROWS):
            hist_scr[i * ROWS + j] = h
            dt_t = dt8[j]
            decay = jnp.exp(dt_t[:, None] * A)
            h = h * decay + (dt_t * u8[j])[:, None] * b8[j:j + 1]
        return h

    jax.lax.fori_loop(0, chunk // ROWS, replay,
                      hinit_ref[0, 0].astype(jnp.float32))

    def bstep(s, carry):
        g, da_acc, dd_acc = carry
        i = chunk // ROWS - 1 - s
        rows = row_tile(i)
        u8, dt8 = _load(u_ref, rows), _load(dt_ref, rows)
        b8, c8 = _load(b_ref, rows), _load(c_ref, rows)
        dy8 = _load(dy_ref, rows)
        du, ddt, db, dc = ([None] * ROWS for _ in range(4))
        for j in reversed(range(ROWS)):
            u_t, dt_t, dy_t = u8[j], dt8[j], dy8[j]    # (c_blk,)
            b_t, c_t = b8[j:j + 1], c8[j:j + 1]        # (1, N)
            h_prev = hist_scr[i * ROWS + j]            # (c_blk, N)
            decay = jnp.exp(dt_t[:, None] * A)
            x_t = dt_t * u_t
            h_t = h_prev * decay + x_t[:, None] * b_t

            gt = g + dy_t[:, None] * c_t               # full dL/dh_t
            dc[j] = jnp.sum(dy_t[:, None] * h_t, axis=0)
            db[j] = jnp.sum(gt * x_t[:, None], axis=0)
            gh = gt * h_prev * decay                   # d(decay) chain
            dx = jnp.sum(gt * b_t, axis=1)
            ddt[j] = dx * u_t + jnp.sum(gh * A, axis=1)
            du[j] = dx * dt_t + D * dy_t
            da_acc = da_acc + gh * dt_t[:, None]
            dd_acc = dd_acc + dy_t * u_t
            g = gt * decay
        du_ref[0, rows] = jnp.stack(du)
        ddt_ref[0, rows] = jnp.stack(ddt)
        db_ref[0, 0, rows] = jnp.stack(db)
        dc_ref[0, 0, rows] = jnp.stack(dc)
        return g, da_acc, dd_acc

    g, da_acc, dd_acc = jax.lax.fori_loop(
        0, chunk // ROWS, bstep,
        (g_scr[...], jnp.zeros_like(g_scr), jnp.zeros_like(D)))
    g_scr[...] = g

    @pl.when(ic == 0)
    def _first():
        da_ref[0] = da_acc
        dd_ref[0, 0] = dd_acc

    @pl.when(ic > 0)
    def _rest():
        da_ref[0] += da_acc
        dd_ref[0, 0] += dd_acc


def _fwd_call(u, dt, B_t, C_t, A, D, c, cb, interpret):
    B, S, di = u.shape
    N = A.shape[1]
    n_chunks, n_cblk = S // c, di // cb
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=c),
        grid=(B, n_cblk, n_chunks),
        in_specs=[
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, c, N), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, c, N), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((cb, N), lambda b, j, i: (j, 0)),
            pl.BlockSpec((1, cb), lambda b, j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, 1, cb, N), lambda b, j, i: (b, i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), u.dtype),
            jax.ShapeDtypeStruct((B, n_chunks, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((cb, N), jnp.float32)],
        interpret=interpret,
    )(u, dt, B_t, C_t, A, D.reshape(1, di))


def _bwd_call(u, dt, B_t, C_t, A, D, h_init, dy, c, cb, interpret):
    B, S, di = u.shape
    N = A.shape[1]
    n_chunks, n_cblk = S // c, di // cb
    rev = n_chunks - 1                                 # reversed chunk walk
    f32 = jnp.float32
    du, ddt, dB_p, dC_p, dA_p, dD_p = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=c),
        grid=(B, n_cblk, n_chunks),
        in_specs=[
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, rev - i, j)),
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, rev - i, j)),
            pl.BlockSpec((1, c, N), lambda b, j, i: (b, rev - i, 0)),
            pl.BlockSpec((1, c, N), lambda b, j, i: (b, rev - i, 0)),
            pl.BlockSpec((cb, N), lambda b, j, i: (j, 0)),
            pl.BlockSpec((1, cb), lambda b, j, i: (0, j)),
            pl.BlockSpec((1, 1, cb, N), lambda b, j, i: (b, rev - i, j, 0)),
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, rev - i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, rev - i, j)),
            pl.BlockSpec((1, c, cb), lambda b, j, i: (b, rev - i, j)),
            pl.BlockSpec((1, 1, c, N), lambda b, j, i: (j, b, rev - i, 0)),
            pl.BlockSpec((1, 1, c, N), lambda b, j, i: (j, b, rev - i, 0)),
            pl.BlockSpec((1, cb, N), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, 1, cb), lambda b, j, i: (b, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), f32),          # du
            jax.ShapeDtypeStruct((B, S, di), f32),          # ddt
            jax.ShapeDtypeStruct((n_cblk, B, S, N), f32),   # dB partial
            jax.ShapeDtypeStruct((n_cblk, B, S, N), f32),   # dC partial
            jax.ShapeDtypeStruct((B, di, N), f32),          # dA partial
            jax.ShapeDtypeStruct((B, 1, di), f32),          # dD partial
        ],
        scratch_shapes=[pltpu.VMEM((cb, N), jnp.float32),
                        pltpu.VMEM((c, cb, N), jnp.float32)],
        interpret=interpret,
    )(u, dt, B_t, C_t, A, D.reshape(1, di), h_init, dy)
    return du, ddt, dB_p, dC_p, dA_p, dD_p[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, dt, B_t, C_t, A, D, c, cb, interpret):
    y, _ = _fwd_call(u, dt, B_t, C_t, A, D, c, cb, interpret)
    return y


def _scan_fwd_rule(u, dt, B_t, C_t, A, D, c, cb, interpret):
    y, h_init = _fwd_call(u, dt, B_t, C_t, A, D, c, cb, interpret)
    return y, (u, dt, B_t, C_t, A, D, h_init)


def _scan_bwd_rule(c, cb, interpret, res, dy):
    u, dt, B_t, C_t, A, D, h_init = res
    cb_bwd = BWD_C_BLK if cb % BWD_C_BLK == 0 else cb
    du, ddt, dB_p, dC_p, dA_p, dD_p = _bwd_call(
        u, dt, B_t, C_t, A, D, h_init, dy, c, cb_bwd, interpret)
    return (du.astype(u.dtype), ddt.astype(dt.dtype),
            jnp.sum(dB_p, axis=0).astype(B_t.dtype),
            jnp.sum(dC_p, axis=0).astype(C_t.dtype),
            jnp.sum(dA_p, axis=0).astype(A.dtype),
            jnp.sum(dD_p, axis=0).astype(D.dtype))


_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)


@functools.partial(jax.jit, static_argnames=("chunk", "c_blk", "interpret"))
def mamba_scan_bsd(u, dt, B_t, C_t, A, D, *, chunk: int = 128,
                   c_blk: int = 512, interpret: bool = False):
    """u, dt: (B, S, di); B_t, C_t: (B, S, N); A: (di, N); D: (di,).
    Returns y: (B, S, di).  Differentiable in every array input."""
    B, S, di = u.shape
    # the time loop moves whole ROWS-step tiles: round both up to ROWS
    c, S_p = pick_block(round_up(S, ROWS), round_up(chunk, ROWS))
    cb, di_p = pick_block(di, c_blk)
    # dt = 0 on the pad makes every padded step an identity; padded
    # channels (A = D = 0) contribute nothing and are sliced off.
    u = pad_axis(pad_axis(u, S_p, axis=1), di_p, axis=2)
    dt = pad_axis(pad_axis(dt, S_p, axis=1), di_p, axis=2)
    B_t = pad_axis(B_t, S_p, axis=1)
    C_t = pad_axis(C_t, S_p, axis=1)
    A = pad_axis(A, di_p, axis=0)
    D = pad_axis(D, di_p, axis=0)
    y = _scan(u, dt, B_t, C_t, A, D, c, cb, interpret)
    return y[:, :S, :di]
