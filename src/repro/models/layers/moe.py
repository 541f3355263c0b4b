"""Mixture-of-Experts FFN: top-k routing with capacity-based dispatch.

Two equivalent dispatch paths:

  * ``dense``    — every expert processes every token, masked combine.
                   O(E/top_k) FLOP inflation; used as the correctness oracle.
  * ``capacity`` — GShard/Switch-style: tokens are scattered into a fixed
                   (E, C, d) buffer (C = ceil(T·k/E·capacity_factor)), expert
                   matmuls run as one batched einsum, results gathered back.
                   Active-FLOPs faithful; the expert dim is sharded over the
                   tensor axes (expert parallelism) — XLA emits the
                   all-to-alls that GPU frameworks issue explicitly.

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


def apply_dense(params, x, cfg: ModelConfig, *, with_stats: bool = False):
    """Oracle path: (B,S,d) -> (B,S,d), every expert sees every token."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    top_w, top_e, lb_loss = _route(params, x2d, cfg)
    y_all = _expert_ffn(params, jnp.broadcast_to(x2d[None], (cfg.n_experts, B * S, d)),
                        cfg)                                  # (E, T, d)
    combine = jnp.zeros((B * S, cfg.n_experts), jnp.float32)
    combine = combine.at[jnp.arange(B * S)[:, None], top_e].add(top_w)
    y = jnp.einsum("te,etd->td", combine.astype(x.dtype), y_all)
    if with_stats:
        stats = {"drop_rate": jnp.zeros((), jnp.float32),   # dense never drops
                 "imbalance": _load_imbalance(top_e, cfg.n_experts)}
        return y.reshape(B, S, d), lb_loss, stats
    return y.reshape(B, S, d), lb_loss


def apply_capacity(params, x, cfg: ModelConfig, *, capacity_factor: float = 1.25,
                   constrain: Optional[Callable] = None,
                   with_stats: bool = False):
    """Scatter/gather dispatch with fixed per-expert capacity.

    With ``with_stats`` also returns {"drop_rate", "imbalance"} — the
    fraction of (token, expert) assignments silently zeroed by the capacity
    clip, and the routed-load skew (``_load_imbalance``), the two
    quantities the duration model needs to price MoE layers."""
    B, S, d = x.shape
    T, E, k = B * S, cfg.n_experts, cfg.top_k
    x2d = x.reshape(T, d)
    top_w, top_e, lb_loss = _route(params, x2d, cfg)

    C = int(max(1, -(-T * k * capacity_factor // E)))        # ceil
    flat_e = top_e.reshape(-1)                               # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    # position of each (token, expert) pair within its expert's buffer
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (T*k, E)
    pos = (jnp.cumsum(oh, axis=0) - 1)                       # running count
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < C
    flat_w = jnp.where(keep, flat_w, 0.0)
    slot = jnp.where(keep, flat_pos, C - 1)                  # clip (weight=0)

    xe = jnp.zeros((E, C, d), x.dtype)
    xe = xe.at[flat_e, slot].add(jnp.where(keep[:, None], x2d[flat_t], 0))
    if constrain is not None:
        xe = constrain(xe)
    ye = _expert_ffn(params, xe, cfg)                        # (E, C, d)
    if constrain is not None:
        ye = constrain(ye)
    y = jnp.zeros((T, d), x.dtype)
    y = y.at[flat_t].add(ye[flat_e, slot] * flat_w[:, None].astype(x.dtype))
    if with_stats:
        stats = {
            "drop_rate": 1.0 - jnp.sum(keep.astype(jnp.float32)) / (T * k),
            "imbalance": _load_imbalance(top_e, E),
        }
        return y.reshape(B, S, d), lb_loss, stats
    return y.reshape(B, S, d), lb_loss


def apply_capacity_chunked(params, x, cfg: ModelConfig, *,
                           capacity_factor: float = 1.25, constrain=None,
                           chunk_tokens: int = 8192, with_stats: bool = False):
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
                              constrain=constrain, with_stats=with_stats)
    x2d = x.reshape(n_chunks, 1, c, d)
    zero = jnp.zeros((), jnp.float32)

    def chunk_fn(carry, xc):
        lb_c, drop_c, imb_c = carry
        if with_stats:
            y, lb, st = apply_capacity(params, xc, cfg,
                                       capacity_factor=capacity_factor,
                                       constrain=constrain, with_stats=True)
            return (lb_c + lb, drop_c + st["drop_rate"],
                    jnp.maximum(imb_c, st["imbalance"])), y
        y, lb = apply_capacity(params, xc, cfg,
                               capacity_factor=capacity_factor,
                               constrain=constrain)
        return (lb_c + lb, drop_c, imb_c), y

    body = jax.checkpoint(chunk_fn, prevent_cse=False)
    (lb, drop, imb), ys = jax.lax.scan(body, (zero, zero, zero), x2d)
    y = ys.reshape(B, S, d)
    if with_stats:
        # mean drop over chunks; worst-chunk imbalance (that's the chunk
        # whose expert matmul is the straggler)
        return y, lb / n_chunks, {"drop_rate": drop / n_chunks,
                                  "imbalance": imb}
    return y, lb / n_chunks


def apply_ep_shard_map(params, x, cfg: ModelConfig, shard_ctx, *,
                       capacity_factor: float = 1.25):
    """True expert parallelism via shard_map (Megatron-style EP×TP).

    Requires n_experts % model-axis size == 0.  Activations are replicated
    over the model axes; every shard routes the full local token set, keeps
    only the assignments for its resident experts, computes them locally and
    psums the partial combine — ONE (tokens, d) all-reduce per layer instead
    of the SPMD partitioner's per-dispatch gather storm (measured 7 TB/step
    on jamba-52B; see EXPERIMENTS.md §Perf).  Returns None if inapplicable.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import sanitize_spec

    mesh, b_axes, m_axes = shard_ctx
    E = cfg.n_experts
    msize = int(np.prod([mesh.shape[a] for a in m_axes], initial=1))
    if not m_axes or msize == 1:
        return None
    if E % msize != 0:
        # experts don't divide the model axes (mixtral 8e / granite 40e on a
        # 16-wide axis): TP-sharded experts instead — every shard owns ALL
        # experts' ff-slices, dispatch is fully local, one psum combines.
        if cfg.d_ff % msize == 0:
            return _apply_tp_shard_map(params, x, cfg, shard_ctx,
                                       capacity_factor=capacity_factor)
        return None
    B, S, d = x.shape
    E_loc = E // msize
    maxis = m_axes[0] if len(m_axes) == 1 else m_axes

    x_spec = sanitize_spec(P(tuple(b_axes) or None, None, None), x.shape, mesh)
    w_e = P(tuple(m_axes), None, None)
    in_specs = {"router": P(None, None), "w_up": w_e, "w_down": w_e}
    if "w_gate" in params:
        in_specs["w_gate"] = w_e

    def local(p_l, x_l):
        Bl, Sl, _ = x_l.shape
        T = Bl * Sl
        x2d = x_l.reshape(T, d)
        top_w, top_e, lb = _route(p_l, x2d, cfg)     # replicated over model
        C = int(max(1, -(-T * cfg.top_k * capacity_factor // E)))
        shard = jax.lax.axis_index(m_axes[0])
        for a in m_axes[1:]:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        offset = shard * E_loc
        flat_e = top_e.reshape(-1) - offset          # local expert ids
        flat_w = top_w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), cfg.top_k)
        mine = (flat_e >= 0) & (flat_e < E_loc)
        e_clip = jnp.clip(flat_e, 0, E_loc - 1)
        oh = jax.nn.one_hot(jnp.where(mine, e_clip, E_loc), E_loc + 1,
                            dtype=jnp.int32)[:, :E_loc]
        pos = jnp.cumsum(oh, axis=0) - 1
        flat_pos = jnp.take_along_axis(pos, e_clip[:, None], axis=1)[:, 0]
        keep = mine & (flat_pos < C)
        slot = jnp.where(keep, flat_pos, C - 1)
        xe = jnp.zeros((E_loc, C, d), x_l.dtype)
        xe = xe.at[e_clip, slot].add(jnp.where(keep[:, None], x2d[flat_t], 0))
        ye = _expert_ffn(p_l, xe, cfg)
        w_eff = jnp.where(keep, flat_w, 0.0).astype(x_l.dtype)
        y = jnp.zeros((T, d), x_l.dtype)
        y = y.at[flat_t].add(ye[e_clip, slot] * w_eff[:, None])
        y = jax.lax.psum(y, maxis)                   # combine expert shards
        # lb differs per batch shard: average so the scalar is replicated
        for a in b_axes:
            lb = jax.lax.pmean(lb, a)
        return y.reshape(Bl, Sl, d), lb

    sm = jax.shard_map(local, mesh=mesh,
                       in_specs=({k: in_specs[k] for k in params}, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    # lb is computed identically on every shard (replicated routing)
    y, lb = sm(params, x)
    return y, lb


def _apply_tp_shard_map(params, x, cfg: ModelConfig, shard_ctx, *,
                        capacity_factor: float = 1.25):
    """TP-sharded experts with local dispatch (E ∤ model axes).

    Each model shard holds every expert's d_ff/msize slice; the scatter/
    gather dispatch runs on local (batch-sharded, model-replicated) tokens —
    no partitioner-inserted gathers — and the only collective is the psum
    that sums the ff partial products."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import sanitize_spec

    mesh, b_axes, m_axes = shard_ctx
    E, d = cfg.n_experts, cfg.d_model
    maxis = m_axes[0] if len(m_axes) == 1 else m_axes
    x_spec = sanitize_spec(P(tuple(b_axes) or None, None, None), x.shape, mesh)
    w_up_spec = P(None, None, tuple(m_axes))       # (E, d, ff/m)
    w_dn_spec = P(None, tuple(m_axes), None)       # (E, ff/m, d)
    in_specs = {"router": P(None, None), "w_up": w_up_spec,
                "w_down": w_dn_spec}
    if "w_gate" in params:
        in_specs["w_gate"] = w_up_spec

    def local(p_l, x_l):
        Bl, Sl, _ = x_l.shape
        T = Bl * Sl
        x2d = x_l.reshape(T, d)
        top_w, top_e, lb = _route(p_l, x2d, cfg)
        C = int(max(1, -(-T * cfg.top_k * capacity_factor // E)))
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), cfg.top_k)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=0) - 1
        flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = flat_pos < C
        slot = jnp.where(keep, flat_pos, C - 1)
        xe = jnp.zeros((E, C, d), x_l.dtype)
        xe = xe.at[flat_e, slot].add(jnp.where(keep[:, None], x2d[flat_t], 0))
        # expert FFN on the local ff slice; psum sums ff partials
        up = jnp.einsum("ecd,edf->ecf", xe, p_l["w_up"].astype(xe.dtype))
        if "w_gate" in p_l:
            gate = jnp.einsum("ecd,edf->ecf", xe,
                              p_l["w_gate"].astype(xe.dtype))
            h = _act(cfg.activation, gate) * up
        else:
            h = _act(cfg.activation, up)
        ye = jnp.einsum("ecf,efd->ecd", h, p_l["w_down"].astype(xe.dtype))
        ye = jax.lax.psum(ye, maxis)
        w_eff = jnp.where(keep, flat_w, 0.0).astype(x_l.dtype)
        y = jnp.zeros((T, d), x_l.dtype)
        y = y.at[flat_t].add(ye[flat_e, slot] * w_eff[:, None])
        for a in b_axes:
            lb = jax.lax.pmean(lb, a)
        return y.reshape(Bl, Sl, d), lb

    sm = jax.shard_map(local, mesh=mesh,
                       in_specs=({k: in_specs[k] for k in params}, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    return sm(params, x)


def apply(params, x, cfg: ModelConfig, *, impl: str = "capacity",
          capacity_factor: float = 1.25, constrain=None,
          chunk_tokens: int = 0, shard_ctx=None, with_stats: bool = False):
    """Dispatch to a MoE path; ``with_stats`` appends a
    {"drop_rate", "imbalance"} dict to the (y, lb) return.  The shard_map
    paths don't measure their (per-shard) dispatch — their stats are NaN,
    never a fake 0.0 (the RuntimeMetrics convention)."""
    if impl == "dense":
        return apply_dense(params, x, cfg, with_stats=with_stats)
    if impl == "ep" and shard_ctx is not None:
        out = apply_ep_shard_map(params, x, cfg, shard_ctx,
                                 capacity_factor=capacity_factor)
        if out is not None:
            if with_stats:
                nan = jnp.full((), jnp.nan, jnp.float32)
                return out[0], out[1], {"drop_rate": nan, "imbalance": nan}
            return out
        # experts don't divide the model axes: fall through
    if chunk_tokens:
        return apply_capacity_chunked(params, x, cfg,
                                      capacity_factor=capacity_factor,
                                      constrain=constrain,
                                      chunk_tokens=chunk_tokens,
                                      with_stats=with_stats)
    return apply_capacity(params, x, cfg, capacity_factor=capacity_factor,
                          constrain=constrain, with_stats=with_stats)
