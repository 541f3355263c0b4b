"""Mamba-1 selective-state-space block (Jamba's SSM half).

Recurrence (per channel c, state dim n):
    h_t = exp(dt_t * A) ⊙ h_{t-1} + (dt_t * x_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ x_t

The XLA path scans over time (TPU target uses the Pallas chunked kernel in
``repro.kernels.mamba_scan``).  Decode carries (conv window, ssm state).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.common.types import ModelConfig


def dims(cfg: ModelConfig):
    di = cfg.ssm_expand * cfg.d_model
    dt_rank = max(1, math.ceil(cfg.d_model / 16))
    return di, dt_rank, cfg.ssm_d_state, cfg.ssm_d_conv


def init(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    di, R, N, K = dims(cfg)
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))
    return {
        "in_proj": (jax.random.normal(ks[0], (d, 2 * di)) * s).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (di, K)) * K ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": (jax.random.normal(ks[2], (di, R + 2 * N)) * di ** -0.5).astype(dtype),
        "dt_proj": (jax.random.normal(ks[3], (R, di)) * R ** -0.5).astype(dtype),
        "dt_bias": jnp.full((di,), -4.6, dtype),   # softplus^-1(0.01)
        "A_log": jnp.log(A).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": (jax.random.normal(ks[5], (di, d)) * di ** -0.5).astype(dtype),
    }


def _conv_step(conv_state, x_t, conv_w, conv_b):
    """conv_state: (B, K-1, di); x_t: (B, di) -> (y_t, new_state)."""
    window = jnp.concatenate([conv_state, x_t[:, None]], axis=1)   # (B,K,di)
    y = jnp.einsum("bkc,ck->bc", window, conv_w.astype(x_t.dtype)) + conv_b
    return y, window[:, 1:]


def causal_conv(x, conv_w, conv_b):
    """x: (B, S, di) depthwise causal conv along S."""
    B, S, di = x.shape
    K = conv_w.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    # stack K shifted views: y_t = sum_k w[:,k] * x_{t-K+1+k}
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for k in range(K):
        y = y + xp[:, k:k + S].astype(jnp.float32) * conv_w[:, k].astype(jnp.float32)
    return (y + conv_b.astype(jnp.float32)).astype(x.dtype)


def ssm_scan_xla(u, dt, B_t, C_t, A, D):
    """Sequential selective scan.

    u, dt: (B, S, di); B_t, C_t: (B, S, N); A: (di, N); D: (di,)
    Returns y: (B, S, di) and final state (B, di, N).
    """
    b, S, di = u.shape
    N = A.shape[1]

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t[..., None] * A[None])            # (B, di, N)
        h = h * decay + (dt_t * u_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bcn,bn->bc", h, c_t)
        return h, y

    h0 = jnp.zeros((b, di, N), jnp.float32)
    xs = (u.astype(jnp.float32).swapaxes(0, 1),
          dt.astype(jnp.float32).swapaxes(0, 1),
          B_t.astype(jnp.float32).swapaxes(0, 1),
          C_t.astype(jnp.float32).swapaxes(0, 1))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = ys.swapaxes(0, 1) + u.astype(jnp.float32) * D.astype(jnp.float32)[None, None]
    return y.astype(u.dtype), h_final


def ssm_scan_chunked(u, dt, B_t, C_t, A, D, *, chunk: int = 32, h0=None):
    """Chunked selective scan (same idea as the chunked WKV6).

    With T_t = Σ_{s≤t} dt_s (per channel), the recurrence solves to
        y_tc = Σ_n C_tn [ e^{A_cn T_tc} h0_cn
                          + Σ_{j≤t} e^{A_cn (T_tc − T_jc)} dt_jc u_jc B_jn ]
    Exponents are ≤ 0 (A < 0, T monotone), so the closed intra-chunk form is
    stable; the sequential dependency survives only across chunks — cutting
    the per-timestep HBM state round-trips by the chunk factor.

    u, dt: (B, S, di); B_t, C_t: (B, S, N); A: (di, N); D: (di,).
    Returns (y (B,S,di), final state (B,di,N))."""
    b, S, di = u.shape
    N = A.shape[1]
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c
    f32 = jnp.float32

    uc = u.astype(f32).reshape(b, n, c, di).transpose(1, 0, 2, 3)
    dtc = dt.astype(f32).reshape(b, n, c, di).transpose(1, 0, 2, 3)
    Bc = B_t.astype(f32).reshape(b, n, c, N).transpose(1, 0, 2, 3)
    Cc = C_t.astype(f32).reshape(b, n, c, N).transpose(1, 0, 2, 3)
    A32 = A.astype(f32)
    h_init = h0 if h0 is not None else jnp.zeros((b, di, N), f32)
    tri = jnp.tril(jnp.ones((c, c), bool))        # j <= t

    def chunk_step(h, inp):
        u_, dt_, b_, c_ = inp                      # (b,c,di) / (b,c,N)
        T = jnp.cumsum(dt_, axis=1)                # (b,c,di)
        # inter-chunk: y_inter_tc = sum_n C_tn e^{A_cn T_tc} h_cn
        decay_T = jnp.exp(T[..., None] * A32[None, None])    # (b,c,di,N)
        y = jnp.einsum("btn,btcn,bcn->btc", c_, decay_T, h)
        # intra-chunk: E_{tjcn} = e^{A_cn (T_t - T_j)}, j <= t
        dT = T[:, :, None, :] - T[:, None, :, :]             # (b,t,j,di)
        E = jnp.exp(dT[..., None] * A32[None, None, None])   # (b,t,j,di,N)
        E = jnp.where(tri[None, :, :, None, None], E, 0.0)
        w = (dt_ * u_)                                        # (b,j,di)
        y = y + jnp.einsum("btn,btjcn,bjc,bjn->btc", c_, E, w, b_)
        y = y + u_ * D.astype(f32)[None, None]
        # state hand-off
        Tc = T[:, -1:, :]                                     # (b,1,di)
        dTc = Tc[:, 0][:, None, :] - T                        # (b,c,di)
        Ec = jnp.exp(dTc[..., None] * A32[None, None])        # (b,c,di,N)
        h = h * jnp.exp(Tc[:, 0][..., None] * A32[None]) + \
            jnp.einsum("bjcn,bjc,bjn->bcn", Ec, w, b_)
        return h, y

    h_final, ys = jax.lax.scan(chunk_step, h_init, (uc, dtc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3).reshape(b, S, di)
    return y.astype(u.dtype), h_final


def ssm_scan_sharded(u, dt, B_t, C_t, A, D, shard_ctx, chunked=False):
    """shard_map-wrapped selective scan.

    Under plain SPMD, the scan's backward re-shards the (shared-across-
    channels) B_t/C_t cotangents EVERY timestep — millions of tiny
    all-reduces at 4k+ sequence length.  Inside shard_map each model shard
    scans its channel slice locally and the cotangent psum happens ONCE per
    layer (shard_map's transpose rule for replicated inputs)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import sanitize_spec

    mesh, b_axes, m_axes = shard_ctx
    chan = tuple(m_axes) or None
    spec_u = sanitize_spec(P(tuple(b_axes) or None, None, chan),
                           u.shape, mesh)
    spec_bc = sanitize_spec(P(tuple(b_axes) or None, None, None),
                            B_t.shape, mesh)
    spec_A = sanitize_spec(P(chan, None), A.shape, mesh)
    spec_D = sanitize_spec(P(chan), D.shape, mesh)
    spec_h = sanitize_spec(P(tuple(b_axes) or None, chan, None),
                           (u.shape[0], u.shape[2], A.shape[1]), mesh)

    inner = (ssm_scan_chunked if chunked else ssm_scan_xla)
    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(spec_u, spec_u, spec_bc, spec_bc,
                                 spec_A, spec_D),
                       out_specs=(spec_u, spec_h), check_vma=False)
    return fn(u, dt, B_t, C_t, A, D)


def init_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    di, R, N, K = dims(cfg)
    return {
        "conv": jnp.zeros((batch, K - 1, di), dtype),
        "ssm": jnp.zeros((batch, di, N), jnp.float32),
    }


def _project(params, x, cfg: ModelConfig):
    di, R, N, K = dims(cfg)
    xz = jnp.einsum("...d,de->...e", x, params["in_proj"].astype(x.dtype))
    return jnp.split(xz, [di], axis=-1)    # u, z


def _bcdt(params, u, cfg: ModelConfig):
    di, R, N, K = dims(cfg)
    proj = jnp.einsum("...c,ce->...e", u, params["x_proj"].astype(u.dtype))
    dt_low, B_t, C_t = jnp.split(proj, [R, R + N], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("...r,rc->...c", dt_low, params["dt_proj"].astype(u.dtype))
        + params["dt_bias"].astype(u.dtype))
    return dt, B_t, C_t


def apply(params, x, cfg: ModelConfig, *, cache=None, impl: str = "xla",
          shard_ctx=None):
    """x: (B, S, d) train/prefill, or (B, 1, d) decode with cache."""
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    D = params["D"]
    if cache is not None:
        x_t = x[:, 0]
        u, z = _project(params, x_t, cfg)
        u_c, conv_state = _conv_step(cache["conv"], u, params["conv_w"],
                                     params["conv_b"])
        u_c = jax.nn.silu(u_c)
        dt, B_t, C_t = _bcdt(params, u_c, cfg)
        decay = jnp.exp(dt.astype(jnp.float32)[..., None] * A[None])
        h = cache["ssm"] * decay + (dt * u_c).astype(jnp.float32)[..., None] \
            * B_t.astype(jnp.float32)[:, None, :]
        y = jnp.einsum("bcn,bn->bc", h, C_t.astype(jnp.float32))
        y = y + u_c.astype(jnp.float32) * D.astype(jnp.float32)[None]
        y = y.astype(x.dtype) * jax.nn.silu(z)
        out = jnp.einsum("bc,cd->bd", y, params["out_proj"].astype(x.dtype))
        return out[:, None], {"conv": conv_state, "ssm": h}

    u, z = _project(params, x, cfg)
    u = jax.nn.silu(causal_conv(u, params["conv_w"], params["conv_b"]))
    dt, B_t, C_t = _bcdt(params, u, cfg)
    if impl == "pallas":
        from repro.kernels import ops as kops
        y, _ = kops.mamba_scan(u, dt, B_t, C_t, A, D)
    elif shard_ctx is not None:
        y, _ = ssm_scan_sharded(u, dt, B_t, C_t, A, D, shard_ctx,
                                chunked=(impl == "chunked"))
    elif impl == "chunked":
        y, _ = ssm_scan_chunked(u, dt, B_t, C_t, A, D)
    else:
        y, _ = ssm_scan_xla(u, dt, B_t, C_t, A, D)
    y = y * jax.nn.silu(z)
    return jnp.einsum("bsc,cd->bsd", y, params["out_proj"].astype(x.dtype)), None
