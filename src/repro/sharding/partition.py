"""Logical-axis sharding: per-module axis assignments -> PartitionSpecs.

This is the TPU-native realization of DFLOP's "independent 3D parallelism
per module" (paper §4): instead of disjoint NCCL process groups, each module
(modality encoder vs. LLM) gets its own *axis assignment* — which mesh axes
shard the batch dimension and which shard tensor dimensions (heads / ffn /
experts / vocab).  The Data-aware 3D Parallelism Optimizer searches over
these assignments; the XLA SPMD partitioner emits the boundary collectives
that the paper's Inter-model Communicator performs explicitly.

Example (mesh ("data","model") = (16,16)):
    encoder: AxisAssignment(batch=("data","model"), tensor=())   # E_dp=256, E_tp=1
    llm:     AxisAssignment(batch=("data",), tensor=("model",))  # L_dp=16,  L_tp=16
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.pytree import tree_map_with_path_str


@dataclass(frozen=True)
class AxisAssignment:
    """Mesh-axis roles for one module."""

    batch: Tuple[str, ...] = ("data",)
    tensor: Tuple[str, ...] = ("model",)
    # Optional ZeRO axes: optimizer state (and, with fsdp=True, params) get an
    # extra sharding over these axes on their largest replicated dim.
    zero: Tuple[str, ...] = ()
    fsdp: bool = False
    # path regexes kept OUT of FSDP (resident, tensor-sharded only); vocab
    # tables are always excluded (see param_specs)
    fsdp_exclude: Tuple[str, ...] = ()

    def dp(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.batch], initial=1))

    def tp(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.tensor], initial=1))


@dataclass(frozen=True)
class ModuleAssignment:
    """Per-module assignments for an MLLM (encoder may differ from LLM)."""

    llm: AxisAssignment
    encoder: Optional[AxisAssignment] = None

    def for_module(self, module: str) -> AxisAssignment:
        if module == "encoder" and self.encoder is not None:
            return self.encoder
        return self.llm


# --------------------------------------------------------------------------- #
# Spec sanitation
# --------------------------------------------------------------------------- #
def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes], initial=1))


def sanitize_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Drop shardings that do not divide the dim (replicate instead)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        size = _axes_size(mesh, entry)
        if size > 1 and (i >= len(shape) or shape[i] % size != 0):
            # keep the LARGEST contiguous subsequence of the axes tuple that
            # still divides the dim (e.g. batch 16 over ("pod","data")=(2,16)
            # must keep ("data",)=16, not the ("pod",)=2 prefix)
            if isinstance(entry, tuple):
                best, best_size = None, 1
                n_ax = len(entry)
                for lo in range(n_ax):
                    for hi in range(lo + 1, n_ax + 1):
                        sub = entry[lo:hi]
                        ssize = _axes_size(mesh, sub)
                        if shape[i] % ssize == 0 and ssize > best_size:
                            best, best_size = sub, ssize
                out.append(best)
            else:
                out.append(None)
        else:
            out.append(entry)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


# --------------------------------------------------------------------------- #
# Parameter rules (path-pattern based, maxtext-style)
# --------------------------------------------------------------------------- #
# Each rule: (regex on param path, spec builder given (assignment, ndim)).
# Specs are written for the *unstacked* layer shape; scanned-layer stacking
# prepends a None (layer) dim, handled by `_with_layer_dims`.
def _t(a: AxisAssignment):
    return a.tensor if a.tensor else None


_RULES = [
    # embeddings / unembedding: shard vocab over tensor axes
    (r"(^|/)embed/w$", lambda a: P(_t(a), None)),
    (r"(^|/)unembed/w$", lambda a: P(None, _t(a))),
    (r"(^|/)pos_embed/w$", lambda a: P(None, None)),
    # attention
    (r"/attn/wq$", lambda a: P(None, _t(a), None)),
    (r"/attn/wk$", lambda a: P(None, _t(a), None)),
    (r"/attn/wv$", lambda a: P(None, _t(a), None)),
    (r"/attn/wo$", lambda a: P(_t(a), None, None)),
    # dense ffn
    (r"/ffn/w_gate$", lambda a: P(None, _t(a))),
    (r"/ffn/w_up$", lambda a: P(None, _t(a))),
    (r"/ffn/w_down$", lambda a: P(_t(a), None)),
    # MoE: expert dim over tensor axes when divisible (expert parallelism),
    # sanitize_spec falls back to ffn sharding via the trailing entries.
    (r"/moe/w_gate$", lambda a: P(_t(a), None, None)),
    (r"/moe/w_up$", lambda a: P(_t(a), None, None)),
    (r"/moe/w_down$", lambda a: P(_t(a), None, None)),
    (r"/moe/router$", lambda a: P(None, None)),
    # mamba
    (r"/mamba/in_proj$", lambda a: P(None, _t(a))),
    (r"/mamba/out_proj$", lambda a: P(_t(a), None)),
    (r"/mamba/conv_w$", lambda a: P(_t(a), None)),
    (r"/mamba/conv_b$", lambda a: P(_t(a))),
    (r"/mamba/x_proj$", lambda a: P(_t(a), None)),
    (r"/mamba/dt_proj$", lambda a: P(None, _t(a))),
    (r"/mamba/dt_bias$", lambda a: P(_t(a))),
    (r"/mamba/A_log$", lambda a: P(_t(a), None)),
    (r"/mamba/D$", lambda a: P(_t(a))),
    # rwkv6
    (r"/rwkv/wo$", lambda a: P(_t(a), None)),
    (r"/rwkv/w[rkvg]$", lambda a: P(None, _t(a))),
    (r"/rwkv/cm_wk$", lambda a: P(None, _t(a))),
    (r"/rwkv/cm_wv$", lambda a: P(_t(a), None)),
    (r"/rwkv/cm_wr$", lambda a: P(None, _t(a))),
    (r"/rwkv/time_first$", lambda a: P(_t(a), None)),
    (r"/rwkv/(decay_)?lora_[ab]$", lambda a: P(None, None)),
    (r"/rwkv/(mix_|decay_base)", lambda a: P(None)),
    # connector (MLLM projector)
    (r"/connector/w\d$", lambda a: P(None, None)),
    # norms / biases / scalars: replicated
    (r".*", lambda a: None),
]


def _spec_for_path(path: str, assignment: AxisAssignment) -> Optional[P]:
    for pat, builder in _RULES:
        if re.search(pat, path):
            return builder(assignment)
    return None


def _module_of(path: str) -> str:
    if path.startswith("encoder/") or "/encoder/" in path:
        return "encoder"
    return "llm"


def param_specs(params: Any, assignment: ModuleAssignment, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching `params` (handles scanned layer dims)."""

    def rule(path: str, leaf) -> P:
        a = assignment.for_module(_module_of(path))
        spec = _spec_for_path(path, a)
        if spec is None:
            spec = P()
        # MoE expert weights: expert-dim sharding when E divides the tensor
        # axes, else shard the FFN dim (granite 40e / mixtral 8e vs a
        # 16-wide model axis).
        m = re.search(r"/moe/(w_gate|w_up|w_down)$", path)
        if m and a.tensor:
            tsize = _axes_size(mesh, tuple(a.tensor))
            E = leaf.shape[-3]
            if E % tsize == 0:
                spec = P(tuple(a.tensor), None, None)
            elif m.group(1) == "w_down":       # (E, ff, d)
                spec = P(None, tuple(a.tensor), None)
            else:                              # (E, d, ff)
                spec = P(None, None, tuple(a.tensor))
        # scanned layers stack params with 1–2 leading dims (block, layer);
        # align the spec to the *trailing* dims of the leaf.
        ndim = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
        pad = ndim - len(spec)
        if pad > 0:
            spec = P(*([None] * pad), *spec)
        elif pad < 0:
            spec = P(*list(spec)[-ndim:] if ndim else [])
        spec = sanitize_spec(spec, leaf.shape, mesh)
        # FSDP-shard everything except the (un)embedding tables: their
        # gradient is a contraction over *all* tokens, and a ZeRO-sharded
        # weight forces SPMD to all-gather the (tokens, vocab) cotangent —
        # vocab-sharded-only weights psum a small partial dW instead.
        is_vocab_table = re.search(r"(^|/)(embed|unembed)/w$", path) is not None
        excluded = is_vocab_table or any(re.search(p, path)
                                         for p in a.fsdp_exclude)
        if a.fsdp and a.zero and not excluded:
            spec = _with_zero(spec, leaf.shape, mesh, a.zero)
        return spec

    return tree_map_with_path_str(rule, params)


def _with_zero(spec: P, shape: Sequence[int], mesh: Mesh, zero_axes: Tuple[str, ...]) -> P:
    """Add ZeRO axes to the largest dim that is unsharded and divisible."""
    if not zero_axes:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for e in entries:
        if e is None:
            continue
        used.update([e] if isinstance(e, str) else e)
    if used & set(zero_axes):
        return spec          # already ZeRO/FSDP-sharded on these axes
    zsize = _axes_size(mesh, tuple(zero_axes))
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if entries[i] is None and shape[i] % zsize == 0 and shape[i] >= zsize:
            entries[i] = tuple(zero_axes)
            return P(*entries)
    return spec


def opt_state_specs(params: Any, pspecs: Any, assignment: ModuleAssignment,
                    mesh: Mesh) -> Any:
    """Optimizer-moment specs: param specs + ZeRO sharding over `zero` axes."""

    def rule(path_leaf, spec_leaf):
        path, leaf = path_leaf
        a = assignment.for_module(_module_of(path))
        return _with_zero(spec_leaf, leaf.shape, mesh, a.zero)

    from repro.common.pytree import tree_paths

    flat_params = tree_paths(params)
    flat_specs = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_params) == len(flat_specs)
    out_flat = [rule(pl, sl) for pl, sl in zip(flat_params, flat_specs)]
    treedef = jax.tree_util.tree_structure(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree_util.tree_unflatten(treedef, out_flat)


# --------------------------------------------------------------------------- #
# Activation specs
# --------------------------------------------------------------------------- #
def tokens_spec(a: AxisAssignment, extra_dims: int = 1) -> P:
    """(batch, seq, ...) tokens: batch sharded over the module's batch axes."""
    return P(tuple(a.batch) if a.batch else None, *([None] * extra_dims))


def activation_spec(a: AxisAssignment, ndim: int = 3) -> P:
    """(batch, seq, d_model): d replicated; heads shard inside attention."""
    return P(tuple(a.batch) if a.batch else None, *([None] * (ndim - 1)))
