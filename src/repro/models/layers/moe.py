"""Mixture-of-Experts FFN: top-k routing with capacity-based dispatch.

Two equivalent kinds of path:

  * ``dense``    — every expert processes every token, masked combine.
                   O(E/top_k) FLOP inflation; used as the correctness oracle.
  * ``capacity`` — GShard/Switch-style: tokens are scattered into a fixed
                   (E, C, d) buffer (C = ceil(T·k/E·capacity_factor)), expert
                   matmuls run as one batched einsum, results gathered back.
                   Active-FLOPs faithful.  One dispatch (``_dispatch``) over a
                   range of held experts serves every variant: all experts
                   (``apply_capacity``, chunked or not), a shard's experts
                   (expert parallelism) or every expert's ff slice (TP).

Both return a Switch-style load-balance auxiliary loss (needed by the
router to keep the capacity path's drop rate near zero).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.common.types import ModelConfig
from repro.models.layers.ffn import GATED, _act


def init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {
        "router": (jax.random.normal(kr, (d, E)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ku, (E, d, ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(kd, (E, ff, d)) * s_out).astype(dtype),
    }
    if cfg.activation in GATED:
        p["w_gate"] = (jax.random.normal(kg, (E, d, ff)) * s_in).astype(dtype)
    return p


def _route(params, x2d, cfg: ModelConfig):
    """x2d: (T, d) -> top-k weights/indices + load-balance loss."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)           # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * sum_e f_e * p_e
    E = cfg.n_experts
    f = jnp.mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1))
    p_mean = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(f * p_mean)
    return top_w, top_e, lb_loss


def _load_imbalance(top_e, E: int):
    """Expert-load imbalance: ``E · max_e(f_e) − 1`` over the routed
    assignment fractions f (0 = perfectly uniform, E − 1 = one expert takes
    everything).  Same f as the Switch lb loss, so the two agree on what
    "load" means."""
    f = jnp.mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1))
    return E * jnp.max(f) - 1.0


def _expert_ffn(params, xe, cfg: ModelConfig):
    """xe: (E, C, d) -> (E, C, d), batched over experts."""
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(xe.dtype))
    if cfg.activation in GATED:
        gate = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(xe.dtype))
        h = _act(cfg.activation, gate) * up
    else:
        h = _act(cfg.activation, up)
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(xe.dtype))


def _capacity(T: int, cfg: ModelConfig, capacity_factor: float) -> int:
    """Slots per expert for ``T`` tokens: ceil(T·k·capacity_factor / E)."""
    return int(max(1, -(-T * cfg.top_k * capacity_factor // cfg.n_experts)))


def _dispatch(x2d, top_w, top_e, lo, n_held: int, C: int, ffn):
    """Capacity dispatch of the routed assignments onto the held experts
    ``[lo, lo + n_held)``: each expert keeps its first ``C`` assignments in
    token order, ``ffn`` maps the (n_held, C, d) buffer, and the results
    are gathered back to (T, d) with their routing weights.  Returns
    ``(y, keep)``; ``keep`` is False for dropped assignments and for those
    to experts not held."""
    T, d = x2d.shape
    flat_e = top_e.reshape(-1) - lo                          # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), top_e.shape[1])
    # position of each (token, expert) pair within its expert's buffer
    # (one_hot of an expert not held is a zero row)
    oh = jax.nn.one_hot(flat_e, n_held, dtype=jnp.int32)     # (T*k, n_held)
    pos = jnp.cumsum(oh, axis=0) - 1                         # running count
    e = jnp.clip(flat_e, 0, n_held - 1)
    flat_pos = jnp.take_along_axis(pos, e[:, None], axis=1)[:, 0]
    keep = (e == flat_e) & (flat_pos < C)
    slot = jnp.where(keep, flat_pos, C - 1)                  # clip (weight=0)
    xe = jnp.zeros((n_held, C, d), x2d.dtype)
    xe = xe.at[e, slot].add(jnp.where(keep[:, None], x2d[flat_t], 0))
    ye = ffn(xe)
    w = jnp.where(keep, top_w.reshape(-1), 0.0).astype(x2d.dtype)
    y = jnp.zeros((T, d), x2d.dtype).at[flat_t].add(ye[e, slot] * w[:, None])
    return y, keep


def apply_dense(params, x, cfg: ModelConfig):
    """Oracle path: (B,S,d) -> (B,S,d), every expert sees every token."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    top_w, top_e, lb_loss = _route(params, x2d, cfg)
    y_all = _expert_ffn(params, jnp.broadcast_to(x2d[None], (cfg.n_experts, B * S, d)),
                        cfg)                                  # (E, T, d)
    combine = jnp.zeros((B * S, cfg.n_experts), jnp.float32)
    combine = combine.at[jnp.arange(B * S)[:, None], top_e].add(top_w)
    y = jnp.einsum("te,etd->td", combine.astype(x.dtype), y_all)
    stats = {"drop_rate": jnp.zeros((), jnp.float32),       # dense never drops
             "imbalance": _load_imbalance(top_e, cfg.n_experts)}
    return y.reshape(B, S, d), lb_loss, stats


def apply_capacity(params, x, cfg: ModelConfig, *, capacity_factor: float = 1.25,
                   constrain: Optional[Callable] = None):
    """Scatter/gather dispatch with fixed per-expert capacity.

    Returns ``(y, lb_loss, stats)``: stats {"drop_rate", "imbalance"} are
    the fraction of (token, expert) assignments silently zeroed by the
    capacity clip, and the routed-load skew (``_load_imbalance``), the two
    quantities the duration model needs to price MoE layers."""
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    top_w, top_e, lb_loss = _route(params, x2d, cfg)
    pin = constrain or (lambda a: a)
    y, keep = _dispatch(x2d, top_w, top_e, 0, cfg.n_experts,
                        _capacity(T, cfg, capacity_factor),
                        lambda xe: pin(_expert_ffn(params, pin(xe), cfg)))
    stats = {"drop_rate": 1.0 - jnp.mean(keep.astype(jnp.float32)),
             "imbalance": _load_imbalance(top_e, cfg.n_experts)}
    return y.reshape(B, S, d), lb_loss, stats


def apply_capacity_chunked(params, x, cfg: ModelConfig, *,
                           capacity_factor: float = 1.25, constrain=None,
                           chunk_tokens: int = 8192):
    """Token-chunked dispatch: bounds the (T·k, d) gather/scatter working set
    (which XLA otherwise materializes replicated) to one chunk; each chunk is
    checkpointed so backward recomputes instead of saving chunk residuals."""
    B, S, d = x.shape
    T = B * S
    c = min(chunk_tokens, T)
    while T % c:
        c -= 1
    n_chunks = T // c
    if n_chunks == 1:
        return apply_capacity(params, x, cfg,
                              capacity_factor=capacity_factor,
                              constrain=constrain)
    x2d = x.reshape(n_chunks, 1, c, d)
    zero = jnp.zeros((), jnp.float32)

    def chunk_fn(carry, xc):
        lb_c, drop_c, imb_c = carry
        y, lb, st = apply_capacity(params, xc, cfg,
                                   capacity_factor=capacity_factor,
                                   constrain=constrain)
        return (lb_c + lb, drop_c + st["drop_rate"],
                jnp.maximum(imb_c, st["imbalance"])), y

    body = jax.checkpoint(chunk_fn, prevent_cse=False)
    (lb, drop, imb), ys = jax.lax.scan(body, (zero, zero, zero), x2d)
    # mean drop over chunks; worst-chunk imbalance (that's the chunk whose
    # expert matmul is the straggler)
    return ys.reshape(B, S, d), lb / n_chunks, {"drop_rate": drop / n_chunks,
                                                "imbalance": imb}


def apply_ep_shard_map(params, x, cfg: ModelConfig, shard_ctx, *,
                       capacity_factor: float = 1.25):
    """Experts sharded over the model axes via shard_map (Megatron-style).

    Activations are replicated over the model axes; every shard routes the
    full local token set and dispatches it to the experts it holds, so the
    only collective is ONE psum per layer instead of the SPMD partitioner's
    per-dispatch gathers:

      * E divisible by the model axes — expert parallelism: a shard holds
        E/msize whole experts and psums the partial combine;
      * else, d_ff divisible — TP-sharded experts (mixtral 8e / granite 40e
        on a 16-wide axis): a shard holds every expert's d_ff/msize slice
        and psums the FFN's partial products.

    Returns ``(y, lb_loss, stats)`` with NaN stats (the per-shard dispatch
    is not measured), or None if neither layout applies."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import sanitize_spec

    mesh, b_axes, m_axes = shard_ctx
    E, d = cfg.n_experts, cfg.d_model
    msize = int(np.prod([mesh.shape[a] for a in m_axes], initial=1))
    if not m_axes or msize == 1:
        return None
    m = tuple(m_axes)
    maxis = m_axes[0] if len(m_axes) == 1 else m_axes
    ep = E % msize == 0
    if ep:
        n_held, w_in, w_out = E // msize, P(m, None, None), P(m, None, None)
    elif cfg.d_ff % msize == 0:
        n_held, w_in, w_out = E, P(None, None, m), P(None, m, None)
    else:
        return None
    x_spec = sanitize_spec(P(tuple(b_axes) or None, None, None), x.shape, mesh)
    in_specs = {"router": P(None, None), "w_up": w_in, "w_gate": w_in,
                "w_down": w_out}

    def local(p_l, x_l):
        Bl, Sl, _ = x_l.shape
        x2d = x_l.reshape(Bl * Sl, d)
        top_w, top_e, lb = _route(p_l, x2d, cfg)     # replicated over model
        lo = 0
        if ep:
            shard = jax.lax.axis_index(m_axes[0])
            for a in m_axes[1:]:
                shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
            lo = shard * n_held

        def ffn(xe):
            ye = _expert_ffn(p_l, xe, cfg)
            return ye if ep else jax.lax.psum(ye, maxis)   # TP: sum ff slices

        y, _ = _dispatch(x2d, top_w, top_e, lo, n_held,
                         _capacity(Bl * Sl, cfg, capacity_factor), ffn)
        if ep:
            y = jax.lax.psum(y, maxis)                 # combine expert shards
        # lb differs per batch shard: average so the scalar is replicated
        for a in b_axes:
            lb = jax.lax.pmean(lb, a)
        return y.reshape(Bl, Sl, d), lb

    sm = jax.shard_map(local, mesh=mesh,
                       in_specs=({k: in_specs[k] for k in params}, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    y, lb = sm(params, x)
    nan = jnp.full((), jnp.nan, jnp.float32)
    return y, lb, {"drop_rate": nan, "imbalance": nan}


def apply(params, x, cfg: ModelConfig, *, impl: str = "capacity",
          capacity_factor: float = 1.25, constrain=None,
          chunk_tokens: int = 0, shard_ctx=None):
    """Dispatch to a MoE path.  Every path returns ``(y, lb_loss, stats)``
    with stats {"drop_rate", "imbalance"}; the shard_map paths don't
    measure their (per-shard) dispatch — their stats are NaN, never a fake
    0.0 (the RuntimeMetrics convention)."""
    if impl == "dense":
        return apply_dense(params, x, cfg)
    if impl == "ep" and shard_ctx is not None:
        out = apply_ep_shard_map(params, x, cfg, shard_ctx,
                                 capacity_factor=capacity_factor)
        if out is not None:
            return out
        # neither experts nor d_ff divide the model axes: fall through
    if chunk_tokens:
        return apply_capacity_chunked(params, x, cfg,
                                      capacity_factor=capacity_factor,
                                      constrain=constrain,
                                      chunk_tokens=chunk_tokens)
    return apply_capacity(params, x, cfg, capacity_factor=capacity_factor,
                          constrain=constrain)
