"""Inter-model Communicator (paper §4, Fig. 6).

The paper's problem: the encoder's data-parallel groups and the LLM's
data-parallel groups differ in size (e.g. E_dp=4 vs L_dp=2), so activations
must be gathered from the encoder groups and re-scattered to the LLM groups
in the forward pass (reversed for gradients).

TPU-native realization: within one SPMD program, the "communicator" is a
resharding of the activation tensor from the encoder module's layout to the
LLM module's layout.  ``jax.lax.with_sharding_constraint`` marks the
boundary; the XLA SPMD partitioner emits the all-to-all / collective-permute
(and its transpose emits the reverse path for gradients automatically —
the backward of a reshard is the reverse reshard, exactly Fig. 6's gradient
path).

An explicit ``shard_map`` gather/scatter mirroring the paper's designated-
rank implementation is provided for validation on host-device meshes.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.partition import AxisAssignment, sanitize_spec


def make_communicator(mesh: Mesh, enc: AxisAssignment,
                      llm: AxisAssignment) -> Callable:
    """Returns f(x) resharding (B, T, D) activations from the encoder
    layout to the LLM layout (identity if the layouts coincide)."""

    def communicate(x):
        spec = P(tuple(llm.batch) if llm.batch else None, None, None)
        spec = sanitize_spec(spec, x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return communicate


# --------------------------------------------------------------------------- #
# Explicit gather/scatter (paper's designated-rank mechanism) for validation
# --------------------------------------------------------------------------- #
def explicit_gather_scatter(mesh: Mesh, axis: str):
    """shard_map gather→scatter along `axis`: every device gathers the full
    batch then keeps its new shard — semantically the Fig. 6 data movement
    (gather from E_dp groups, scatter to L_dp groups) when the two layouts
    shard the same logical batch differently."""

    def fn(x):
        def inner(xs):
            full = jax.lax.all_gather(xs, axis, axis=0, tiled=True)
            n = jax.lax.axis_size(axis)
            idx = jax.lax.axis_index(axis)
            shard = full.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(full, idx * shard, shard, 0)

        return jax.shard_map(inner, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis))(x)

    return fn
