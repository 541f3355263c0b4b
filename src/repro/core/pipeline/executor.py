"""shard_map pipeline executor: TPU-native pipeline parallelism.

Layers are sharded over a `stage` mesh axis; microbatch activations rotate
through stages with ``jax.lax.ppermute`` inside a ``lax.scan`` over
T = m + p − 1 ticks (the circular-pipeline idiom).  The steady-state bubble
structure matches 1F1B's (p−1)/(m+p−1); the discrete-event simulator
(`simulator.py`) models the full 1F1B order for schedule studies, while this
executor provides a *runnable, differentiable* pipeline on a real mesh —
the piece a GPU framework implements with P2P sends.

Homogeneous stages (equal layers per stage).  The DFLOP heterogeneous
encoder/LLM split is realized in SPMD mode via per-module sharding
(`repro.core.communicator`); the pipeline axis is exercised for the LLM
stack, with scheduler-balanced microbatches entering through stage 0.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def build_stage_fn(layer_apply: Callable, layers_per_stage: int) -> Callable:
    """stage_fn(stage_params, x) applying `layers_per_stage` stacked layers.

    `stage_params` leaves have leading dim layers_per_stage."""

    def stage_fn(stage_params, x):
        def body(h, lp):
            return layer_apply(lp, h), None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    return stage_fn


def pipeline_forward(mesh: Mesh, stage_fn: Callable, axis: str = "stage"):
    """Returns f(stacked_stage_params, microbatches) -> outputs.

    stacked_stage_params: leaves (p, layers_per_stage, ...), sharded dim0
    over `axis`.  microbatches: (m, mb, seq, d) replicated.  outputs:
    (m, mb, seq, d) replicated (psum-collected from the last stage).
    """
    p = mesh.shape[axis]

    def inner(params_local, mbs):
        # params_local leaves: (1, layers_per_stage, ...) -> drop stage dim
        params_local = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        m = mbs.shape[0]
        T = m + p - 1
        state = jnp.zeros_like(mbs[0])
        outputs = jnp.zeros_like(mbs)

        def tick(carry, t):
            state, outputs = carry
            inject = jnp.take(mbs, jnp.clip(t, 0, m - 1), axis=0)
            x = jnp.where(idx == 0, inject, state)
            y = stage_fn(params_local, x)
            nxt = jax.lax.ppermute(y, axis,
                                   [(i, (i + 1) % p) for i in range(p)])
            out_t = t - (p - 1)
            is_emit = (idx == p - 1) & (out_t >= 0)
            upd = jnp.where(is_emit, y, jnp.take(outputs,
                                                 jnp.clip(out_t, 0, m - 1),
                                                 axis=0))
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, upd, jnp.clip(out_t, 0, m - 1), 0)
            return (nxt, outputs), None

        (state, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                           jnp.arange(T))
        # collect from the last stage; other stages contribute zeros
        outputs = jnp.where(idx == p - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    in_specs = (P(axis), P())
    out_specs = P()
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def stack_stage_params(per_layer_params, p: int, *, from_p=None):
    """(n_layers, ...) stacked layer params -> (p, n_layers/p, ...).

    With ``from_p`` set (any integer, including 1) the leaves are already
    stage-stacked as (from_p, n_layers/from_p, ...) and are re-partitioned
    for the new stage count — the layout transition a physical plan
    hot-swap needs (`repro.launch.reshard`)."""

    def reshape(a):
        if from_p is not None:
            assert a.shape[0] == from_p, (
                f"leaf leading dim {a.shape[0]} != from_p={from_p}")
            a = a.reshape(from_p * a.shape[1], *a.shape[2:])
        n = a.shape[0]
        assert n % p == 0, f"{n} layers not divisible by {p} stages"
        return a.reshape(p, n // p, *a.shape[1:])

    return jax.tree.map(reshape, per_layer_params)


def unstack_stage_params(stacked_params):
    """(p, n_layers/p, ...) stage-stacked leaves -> flat (n_layers, ...)."""
    return jax.tree.map(lambda a: a.reshape(a.shape[0] * a.shape[1],
                                            *a.shape[2:]), stacked_params)
