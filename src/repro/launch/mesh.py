"""Production meshes (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run's ``main()`` sets XLA_FLAGS for 512 host devices before
JAX initialises its backends; tests and benchmarks see the default device
count.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with explicit Auto axes.  `devices` restricts the
    mesh to a subset of the local devices (a re-planned θ* rarely uses all
    of them)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def host_groups(devices, per_host: int):
    """Partition a flat device list into contiguous emulated "hosts" of
    ``per_host`` devices each (the roster `repro.launch.fleet.FleetManager`
    owns).  Raises on a ragged split — every host must field the same
    device count or per-host data shards stop being comparable."""
    devices = list(devices)
    if per_host < 1 or len(devices) % per_host:
        raise ValueError(
            f"{len(devices)} devices do not split into hosts of {per_host}")
    return [devices[i:i + per_host]
            for i in range(0, len(devices), per_host)]


def serve_device_pools(n_prefill: int, n_decode: int, devices=None):
    """Assign the serving engine's worker pools to devices (DistTrain-style
    prefill/decode disaggregation).  With enough devices the pools are
    disjoint — the KV handoff is then a genuine device-to-device transfer
    (on an emulated fleet via ``--xla_force_host_platform_device_count``).
    Fewer devices wrap round-robin, degrading gracefully to same-device
    copies on a single-chip host."""
    devs = list(devices if devices is not None else jax.devices())
    if n_prefill < 1 or n_decode < 1:
        raise ValueError("both pools need at least one worker")
    total = n_prefill + n_decode
    if len(devs) >= total:
        return devs[:n_prefill], devs[n_prefill:total]
    pre = [devs[i % len(devs)] for i in range(n_prefill)]
    dec = [devs[(n_prefill + i) % len(devs)] for i in range(n_decode)]
    return pre, dec


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over forced host devices (tests / examples)."""
    return make_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """Mesh axes that shard the batch by default: pod (if present) + data."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def model_axes(mesh) -> tuple:
    return ("model",) if "model" in mesh.shape else ()
