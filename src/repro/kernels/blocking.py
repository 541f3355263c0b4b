"""Shared grid-blocking policy for the Pallas kernels and XLA's chunked
attention.

Every kernel tiles a sequence (or channel) axis into ``block``-sized grid
steps.  The old per-kernel ``_pick`` helper chose the largest *divisor* of
the length ≤ the target — which silently degenerates to block size 1 for
prime lengths (a catastrophic grid blowup: a 127-token packed sequence ran
127 × 127 grid steps instead of 1).  The shared policy here instead pads the
axis up to the next block multiple and lets masking neutralize the tail:

  * attention — padded positions carry segment id ``PAD_SEGMENT`` (−1),
    which can never equal a real segment id (callers use ids ≥ 0), so the
    existing segment mask hides the tail for free; padded query rows are
    sliced off.
  * scans — padded steps are identities (mamba: dt = 0 ⇒ decay = 1, no
    input; rwkv: w = 1, k = v = 0 ⇒ state passes through), so the final
    state and all real-position outputs are untouched.

Gradients need no special handling: padding/slicing happen *outside* the
``custom_vjp`` boundary with plain ``jnp.pad``/slice, whose
transposes drop the tail cotangents automatically.

>>> pick_block(128, 64)      # divisible: exact tiling, no padding
(64, 128)
>>> pick_block(127, 64)      # prime: pad one step instead of 127 steps
(64, 128)
>>> pick_block(96, 128)      # short axis: single block, no padding
(96, 96)
>>> pick_block(257, 64)      # minimal grid: ceil(257/64) = 5 steps
(64, 320)
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl

# Reserved segment id for padded positions: real segment ids are ≥ 0
# (0 = packing tail, 1..n = instances), so −1 never matches under the
# ``seg_q == seg_k`` mask.
PAD_SEGMENT = -1

# Mosaic loads and stores VMEM in (8, 128) tiles and cannot prove a
# per-timestep dynamic row index aligned, so the scans' time loops move
# ROWS timesteps per iteration: one aligned (ROWS, ·) tile load or store
# per stream, with the ROWS steps unrolled over the tile's rows.
ROWS = 8


def round_up(n: int, m: int) -> int:
    """
    >>> round_up(67, 8)
    72
    """
    return -(-int(n) // m) * m


def row_tile(i):
    """Aligned ``ROWS``-row slice for time-loop iteration ``i``."""
    return pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)


def pick_block(s: int, target: int) -> tuple:
    """Block size and padded length for an axis of length ``s``.

    Returns ``(block, padded)`` with ``block = min(s, target)`` and
    ``padded`` the next multiple of ``block`` ≥ ``s`` — the minimal grid:
    ``padded // block == ceil(s / block)``, never more than one partial
    step of overhead regardless of divisibility.
    """
    b = min(int(s), max(1, int(target)))
    padded = -(-int(s) // b) * b
    return b, padded


def pad_axis(x, padded: int, axis: int, value=0):
    """Pad ``x`` along ``axis`` up to length ``padded`` with ``value``.

    No-op (returns ``x`` unchanged) when the axis already has that length,
    so jit'd callers trace identical programs for divisible shapes.
    """
    n = x.shape[axis]
    if n == padded:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, padded - n)
    return jnp.pad(x, widths, constant_values=value)


def pad_segments(seg, block: int):
    """``(block, padded length, ids padded with PAD_SEGMENT)`` of an
    attention call's ``(B, S)`` segment ids tiled by ``block``."""
    b, padded = pick_block(seg.shape[1], block)
    return b, padded, pad_axis(seg, padded, axis=1, value=PAD_SEGMENT)
