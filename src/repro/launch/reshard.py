"""Physical plan hot-swap: re-lay-out parameters on device for a
re-planned θ*.

`RuntimeController.maybe_swap()` changes the *logical* bucket structure
the Online Scheduler balances against; this module supplies the *physical*
half — without it, device arrays stay sharded for the stale plan and the
swapped θ* is a fiction.  Three pieces:

  * ``plan_mesh(plan)`` — the ``(data, stage, model)`` mesh a
    `ParallelismPlan`'s LLM parallelism implies, built via
    `launch.mesh.make_mesh` over a prefix of the local devices.
  * ``reshard_params(params, old_plan, new_plan)`` — re-stack
    stage-stacked leaves for the new PP degree (generalized
    `executor.stack_stage_params`), then `jax.device_put` onto the new
    mesh's `NamedSharding`s with buffer donation, so the old and new
    layouts are never resident together.  Returns the new params plus a
    `ReshardReport` (bytes moved, elapsed seconds, old/new plan tuples).
  * ``ParamSwapper`` — the controller-facing hook: owns get/set callbacks
    into the training loop's live param pytree, estimates transition cost
    (measured history first, bytes/bandwidth model otherwise) so
    `maybe_swap()` can gate a swap on amortized reshard cost, and performs
    the re-layout at the global-batch boundary.

Layout reconfiguration is *not* free (DistTrain, arXiv:2408.04275): the
swap decision must weigh measured/estimated reshard time against the
predicted per-batch makespan advantage over a horizon — the gate lives in
`repro.runtime.controller`, the cost model here.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.optimizer.space import ParallelismPlan
from repro.core.pipeline.executor import stack_stage_params
from repro.launch.mesh import make_mesh

# Axis convention for plan-implied meshes.  `pipeline_forward` shards
# stage-stacked leaves over "stage"; "data"/"model" replicate them.
PLAN_AXES = ("data", "stage", "model")

# Default cost-model constants for `estimate_reshard_s`: aggregate
# device-to-device bandwidth (ICI-ish for a v5e slice; the measured-report
# path replaces this as soon as one real swap has happened) and a fixed
# dispatch/compile latency floor per transition.
DEFAULT_BANDWIDTH_BYTES_PER_S = 1e11
DEFAULT_LATENCY_S = 5e-3


@dataclass(frozen=True)
class ReshardReport:
    """What one physical swap actually did (trace/metrics payload)."""

    old_plan: tuple                # ParallelismPlan.as_tuple() before
    new_plan: tuple                # ... and after
    bytes_moved: int               # bytes placed onto a new layout
    bytes_total: int               # total param bytes considered
    elapsed_s: float               # wall time incl. blocking on transfers
    n_leaves: int
    restacked: bool                # stage leaves re-partitioned for new PP


def plan_mesh(plan: ParallelismPlan, *, devices=None) -> Mesh:
    """Mesh implied by ``plan.llm``: shape (dp, pp, tp), axes PLAN_AXES.

    Uses the first ``dp·pp·tp`` of ``devices`` (default: all local
    devices); raises ``ValueError`` when the plan needs more devices than
    exist — `ParamSwapper.compatible` turns that into a gated swap."""
    mp = plan.llm
    n = mp.dp * mp.pp * mp.tp
    devices = list(jax.devices() if devices is None else devices)
    if n > len(devices):
        raise ValueError(
            f"plan {plan.as_tuple()} needs {n} devices, have {len(devices)}")
    return make_mesh((mp.dp, mp.pp, mp.tp), PLAN_AXES,
                     devices=devices[:n])


def clamped_plan_mesh(plan: ParallelismPlan, *, devices=None) -> Mesh:
    """`plan_mesh` clamped onto however many local devices exist.

    Single-host examples/benchmarks emulate a pod-scale transition with
    the devices they have: each axis is cut to fit (tp first, then pp,
    then dp), preserving the plan's axis *structure* while the device
    count shrinks.  Production launches use `plan_mesh` unclamped."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    tp = min(plan.llm.tp, n)
    pp = min(plan.llm.pp, max(n // tp, 1))
    dp = min(plan.llm.dp, max(n // (tp * pp), 1))
    return make_mesh((dp, pp, tp), PLAN_AXES,
                     devices=devices[:dp * pp * tp])


def param_bytes(params) -> int:
    """Total bytes across a param pytree.

    >>> import numpy as np
    >>> param_bytes({"w": np.zeros((4, 8), np.float32),
    ...              "b": np.zeros(8, np.float32)})
    160
    """
    return int(sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)))


def estimate_reshard_s(n_bytes: int, *,
                       bandwidth_bytes_per_s: float = DEFAULT_BANDWIDTH_BYTES_PER_S,
                       latency_s: float = DEFAULT_LATENCY_S) -> float:
    """Transfer-time estimate for moving ``n_bytes`` to a new layout.

    >>> estimate_reshard_s(2 * 10**9, bandwidth_bytes_per_s=1e11,
    ...                    latency_s=0.0)
    0.02
    """
    return n_bytes / bandwidth_bytes_per_s + latency_s


def _stage_stacked(params, pp: int) -> bool:
    leaves = jax.tree_util.tree_leaves(params)
    return bool(leaves) and all(
        leaf.ndim >= 2 and leaf.shape[0] == pp for leaf in leaves)


def _restackable(params, old_pp: int, new_pp: int) -> bool:
    return all((leaf.shape[0] * leaf.shape[1]) % new_pp == 0
               for leaf in jax.tree_util.tree_leaves(params)) \
        if _stage_stacked(params, old_pp) else False


def _any_deleted(params) -> bool:
    return any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in jax.tree_util.tree_leaves(params))


def reshard_params(params, old_plan: ParallelismPlan,
                   new_plan: ParallelismPlan, *,
                   new_mesh: Optional[Mesh] = None,
                   stage_stacked: Optional[bool] = None,
                   donate: bool = True,
                   mesh_factory: Callable[..., Mesh] = plan_mesh):
    """Re-lay-out ``params`` from ``old_plan``'s layout to ``new_plan``'s.

    Stage-stacked pipeline params (leaves ``(old_pp, L/old_pp, ...)``) are
    re-partitioned to ``(new_pp, L/new_pp, ...)`` and sharded over the new
    mesh's "stage" axis; generic pytrees are replicated onto the new mesh.
    A *schedule-only* transition (same LLM parallelism, different schedule
    family in the widened θ tuple) implies an identical mesh: the
    re-layout degenerates to a no-op placement (``bytes_moved == 0``)
    while the report still records the full old/new plan identities.
    Donation hands the old buffers to the transfer so peak memory stays at
    one copy (double-residency during a swap is exactly the failure mode a
    memory-feasible plan can't afford).

    Returns ``(new_params, ReshardReport)``.
    """
    t0 = time.monotonic()
    old_pp, new_pp = old_plan.llm.pp, new_plan.llm.pp
    if stage_stacked is None:
        # Every leaf shaped (old_pp, layers, ...) reads as stage-stacked —
        # including old_pp == 1, where a (1, L, ...) pytree must still be
        # re-partitioned for a larger new PP.  The heuristic is ambiguous
        # for generic pytrees whose leaves all happen to lead with old_pp;
        # pass stage_stacked explicitly (ParamSwapper always does) when
        # the layout is known.
        stage_stacked = _stage_stacked(params, old_pp)

    restacked = False
    if stage_stacked and old_pp != new_pp:
        if not _restackable(params, old_pp, new_pp):
            raise ValueError(
                f"cannot re-stack stage params from pp={old_pp} to "
                f"pp={new_pp}: layer count not divisible")
        params = stack_stage_params(params, new_pp, from_p=old_pp)
        restacked = True

    if new_mesh is None:
        new_mesh = mesh_factory(new_plan)

    # Stage leaves shard over "stage" only when their leading dim divides
    # the mesh's actual stage-axis size — a clamped emulation mesh can be
    # narrower than the plan's PP (e.g. pp=7 on 4 local devices), where
    # the correct layout is replication, not a device_put failure.
    spec = P()
    if stage_stacked:
        # leading dim is new_pp here: a pp change either restacked or raised
        stage_size = dict(new_mesh.shape).get("stage", 1)
        if new_pp % stage_size == 0:
            spec = P("stage")
    sharding = NamedSharding(new_mesh, spec)

    leaves = jax.tree_util.tree_leaves(params)
    total = int(sum(leaf.nbytes for leaf in leaves))
    moved = int(sum(
        leaf.nbytes for leaf in leaves
        if restacked or not (isinstance(leaf, jax.Array)
                             and getattr(leaf, "sharding", None) == sharding)))

    target = jax.tree_util.tree_map(lambda _: sharding, params)
    new_params = jax.block_until_ready(
        jax.device_put(params, target, donate=donate))

    report = ReshardReport(
        old_plan=old_plan.as_tuple(), new_plan=new_plan.as_tuple(),
        bytes_moved=moved, bytes_total=total,
        elapsed_s=time.monotonic() - t0, n_leaves=len(leaves),
        restacked=restacked)
    return new_params, report


class ParamSwapper:
    """Controller hook performing the physical half of a plan hot-swap.

    The training loop owns the live params; the swapper reaches them
    through ``get_params``/``set_params`` callbacks so a swap at the
    global-batch boundary mutates the loop's pytree in place:

        state = {"params": params}
        swapper = ParamSwapper(lambda: state["params"],
                               lambda p: state.update(params=p))
        ctl = engine.runtime(gbs, param_swapper=swapper)

    ``stage_stacked=True`` declares pipeline-stacked leaves (re-partitioned
    across PP transitions; with ``strict=True`` an impossible re-stack
    makes `compatible()` False, which gates the *whole* swap — the logical
    and physical plans never diverge).  ``strict=False`` (emulation mode,
    used by single-host benchmarks) falls back to a plain re-placement
    when the layer count doesn't divide the new PP.
    """

    def __init__(self, get_params: Callable[[], object],
                 set_params: Callable[[object], None], *,
                 stage_stacked: bool = False,
                 strict: bool = True,
                 donate: bool = True,
                 mesh_factory: Callable[..., Mesh] = plan_mesh,
                 bandwidth_bytes_per_s: float = DEFAULT_BANDWIDTH_BYTES_PER_S,
                 latency_s: float = DEFAULT_LATENCY_S):
        self._get = get_params
        self._set = set_params
        self.stage_stacked = stage_stacked
        self.strict = strict
        self.donate = donate
        self.mesh_factory = mesh_factory
        self.bandwidth_bytes_per_s = bandwidth_bytes_per_s
        self.latency_s = latency_s
        self.reports: List[ReshardReport] = []
        # True once a failed donated transfer has consumed the live
        # buffers: the stale layout is gone too, recovery is impossible,
        # and the controller must fail fast instead of training on a
        # deleted pytree.  Pass donate=False for a fully recoverable swap
        # at the price of transient double-residency (docs/resharding.md).
        self.damaged = False

    # ------------------------------------------------------------------ #
    def compatible(self, old_plan: ParallelismPlan,
                   new_plan: ParallelismPlan) -> bool:
        """Can this transition be realized physically?  A False return
        gates the logical swap too (controller policy)."""
        try:
            self.mesh_factory(new_plan)
        except ValueError:
            return False
        if (self.strict and self.stage_stacked
                and old_plan.llm.pp != new_plan.llm.pp):
            return _restackable(self._get(), old_plan.llm.pp,
                                new_plan.llm.pp)
        return True

    def estimate_cost_s(self, old_plan: ParallelismPlan,
                        new_plan: ParallelismPlan) -> float:
        """Predicted reshard wall time for the amortization gate.

        Always sized to the bytes of the transition being priced: once any
        swap has moved real bytes, the configured bandwidth is replaced by
        the *measured* one (Σbytes/Σelapsed over history) — a raw mean of
        past elapsed times would misprice as soon as transitions of
        different magnitudes mix."""
        n_bytes = param_bytes(self._get())
        informative = [(r.bytes_moved, r.elapsed_s) for r in self.reports
                       if r.bytes_moved > 0 and r.elapsed_s > 0]
        bandwidth = self.bandwidth_bytes_per_s
        if informative:
            bandwidth = (sum(b for b, _ in informative)
                         / sum(t for _, t in informative))
        return estimate_reshard_s(n_bytes, bandwidth_bytes_per_s=bandwidth,
                                  latency_s=self.latency_s)

    # ------------------------------------------------------------------ #
    def swap(self, old_plan: ParallelismPlan,
             new_plan: ParallelismPlan) -> ReshardReport:
        params = self._get()
        stacked = self.stage_stacked
        if (stacked and not self.strict
                and not _restackable(params, old_plan.llm.pp,
                                     new_plan.llm.pp)):
            stacked = False          # emulation fallback: re-place only
        try:
            new_params, report = reshard_params(
                params, old_plan, new_plan, stage_stacked=stacked,
                donate=self.donate, mesh_factory=self.mesh_factory)
        except Exception:
            if self.donate and _any_deleted(params):
                self.damaged = True
            raise
        self._set(new_params)
        self.reports.append(report)
        return report

    def refresh(self, plan: ParallelismPlan) -> ReshardReport:
        """Re-place the *same* logical plan onto whatever mesh
        ``mesh_factory`` currently resolves — the elastic-recovery
        primitive: after a host loss, a fleet-backed factory
        (`FleetManager.plan_mesh`) now maps the plan onto the surviving
        devices, so ``refresh`` migrates live params off the dead host
        without a plan change (and without a checkpoint)."""
        return self.swap(plan, plan)

    __call__ = swap
