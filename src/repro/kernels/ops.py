"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend ``interpret=False`` compiles the Mosaic kernel.  On CPU the
kernels execute with ``interpret=True`` — the kernel body runs op-by-op in
Python, validating the exact TPU program against the ``ref.py`` oracles.
Any other backend can do neither and is refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan import mamba_scan_bsd
from repro.kernels.packed_flash_attention import packed_flash_attention_bkgsd
from repro.kernels.rwkv6_scan import rwkv6_scan_bhsm


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"the Pallas TPU kernels cannot run on a {backend!r} backend")
    return backend == "cpu"


def packed_flash_attention(q, k, v, *, segment_ids=None, causal=True,
                           window=0, block_q=512, block_k=512):
    """q: (B, S, H, D); k, v: (B, S, KH, D); segment_ids: (B, S) int32.
    Returns (B, S, H, D) — layout-matched to the model's attention layer."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    if segment_ids is None:
        segment_ids = jnp.zeros((B, S), jnp.int32)
    # GQA convention: head h attends through kv head h // G — the
    # (B, S, KH, G, D) reshape groups G consecutive query heads per kv head.
    qt = q.reshape(B, S, KH, G, D).transpose(0, 2, 3, 1, 4)  # (B,KH,G,S,D)
    kt = k.transpose(0, 2, 1, 3)                             # (B,KH,S,D)
    vt = v.transpose(0, 2, 1, 3)
    out = packed_flash_attention_bkgsd(
        qt, kt, vt, segment_ids, segment_ids, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret())
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)


def rwkv6_scan(r, k, v, w, u, *, chunk=128):
    """r,k,v,w: (B, S, H, M); u: (H, M). Returns (y (B,S,H,M), state)."""
    rt, kt, vt, wt = (t.transpose(0, 2, 1, 3) for t in (r, k, v, w))
    y, s = rwkv6_scan_bhsm(rt, kt, vt, wt, u, chunk=chunk,
                           interpret=_interpret())
    return y.transpose(0, 2, 1, 3), s


def mamba_scan(u, dt, B_t, C_t, A, D, *, chunk=128, c_blk=512):
    """u, dt: (B,S,di); B_t, C_t: (B,S,N); A: (di,N); D: (di,).
    Returns (y (B,S,di), None) — state hand-off via the XLA path."""
    y = mamba_scan_bsd(u, dt, B_t, C_t, A, D, chunk=chunk, c_blk=c_blk,
                       interpret=_interpret())
    return y, None
