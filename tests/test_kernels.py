"""Pallas kernel validation: shape/dtype sweeps vs. pure-jnp oracles.

Kernels execute in interpret mode on CPU (the exact TPU program, run
op-by-op) and must match ``repro.kernels.ref`` to float tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _mk_qkv(key, B, S, H, KH, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), dtype=jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _segments(key, B, S, n_seg):
    """Contiguous segments 1..n_seg (0 = padding tail)."""
    lens = jax.random.randint(key, (B, n_seg), 1, max(2, S // n_seg + 1))
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cur = 0
        for i, L in enumerate(np.asarray(lens)[b]):
            L = int(L)
            seg[b, cur:cur + L] = i + 1
            cur += L
            if cur >= S:
                break
    return jnp.asarray(seg)


ATTN_CASES = [
    # (B, S, H, KH, D, causal, window, dtype)
    (1, 128, 4, 4, 64, True, 0, jnp.float32),
    (2, 256, 8, 2, 64, True, 0, jnp.float32),       # GQA
    (2, 128, 4, 1, 64, True, 0, jnp.float32),       # MQA
    (1, 256, 4, 4, 128, True, 64, jnp.float32),     # sliding window
    (2, 128, 4, 2, 64, False, 0, jnp.float32),      # bidirectional (encoder)
    (1, 128, 4, 2, 64, True, 0, jnp.bfloat16),
    (1, 96, 2, 2, 32, True, 0, jnp.float32),        # non-pow2 seq
]


@pytest.mark.parametrize("B,S,H,KH,D,causal,window,dtype", ATTN_CASES)
def test_packed_flash_attention(B, S, H, KH, D, causal, window, dtype):
    key = jax.random.PRNGKey(42)
    q, k, v = _mk_qkv(key, B, S, H, KH, D, dtype)
    seg = _segments(jax.random.PRNGKey(7), B, S, n_seg=3)
    got = ops.packed_flash_attention(q, k, v, segment_ids=seg, causal=causal,
                                     window=window, block_q=64, block_k=64)
    want = ref.packed_attention_ref(q, k, v, causal=causal, window=window,
                                    seg_q=seg, seg_k=seg)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_packed_flash_attention_respects_boundaries():
    """Tokens must not attend across packing boundaries: identical segment
    content -> identical outputs regardless of what is packed after it."""
    B, S, H, D = 1, 128, 2, 32
    key = jax.random.PRNGKey(0)
    q, k, v = _mk_qkv(key, B, S, H, H, D, jnp.float32)
    seg = jnp.asarray(np.r_[np.ones(64), np.full(64, 2)].astype(np.int32))[None]
    out = ops.packed_flash_attention(q, k, v, segment_ids=seg,
                                     block_q=32, block_k=32)
    # replace the second segment with garbage; first segment output unchanged
    q2 = q.at[:, 64:].set(123.0)
    k2 = k.at[:, 64:].set(-7.0)
    v2 = v.at[:, 64:].set(0.5)
    out2 = ops.packed_flash_attention(q2, k2, v2, segment_ids=seg,
                                      block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out[:, :64]),
                               np.asarray(out2[:, :64]), rtol=1e-5, atol=1e-5)


RWKV_CASES = [
    (1, 64, 2, 32, 32, jnp.float32),
    (2, 128, 4, 64, 32, jnp.float32),
    (1, 96, 2, 64, 48, jnp.float32),                 # non-pow2 seq/chunk
    (1, 64, 2, 32, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,M,chunk,dtype", RWKV_CASES)
def test_rwkv6_scan(B, S, H, M, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = jax.random.normal(ks[0], (B, S, H, M)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, M)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, M)).astype(dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, M))).astype(dtype)
    u = (jax.random.normal(ks[4], (H, M)) * 0.1).astype(dtype)
    y, s = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    y_ref, s_ref = ref.rwkv6_scan_ref(r, k, v, w, u)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=tol, atol=tol)


MAMBA_CASES = [
    (1, 64, 64, 8, 32, 32, jnp.float32),
    (2, 128, 128, 16, 64, 64, jnp.float32),
    (1, 96, 64, 16, 48, 32, jnp.float32),
    (1, 64, 128, 16, 32, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,di,N,chunk,c_blk,dtype", MAMBA_CASES)
def test_mamba_scan(B, S, di, N, chunk, c_blk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    u = jax.random.normal(ks[0], (B, S, di)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 1).astype(dtype)
    B_t = jax.random.normal(ks[2], (B, S, N)).astype(dtype)
    C_t = jax.random.normal(ks[3], (B, S, N)).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (di, N)) * 0.3)
    D = jax.random.normal(ks[5], (di,))
    y, _ = ops.mamba_scan(u, dt, B_t, C_t, A, D, chunk=chunk, c_blk=c_blk)
    y_ref, _ = ref.mamba_scan_ref(u, dt, B_t, C_t, A, D)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=tol, atol=tol)


# --------------------------------------------------------------------------- #
# Backwards: the fused Pallas custom-vjp kernels vs jax.grad of the oracles
# --------------------------------------------------------------------------- #
from repro.kernels import blocking                               # noqa: E402
from repro.kernels import packed_flash_attention as pfa          # noqa: E402


def _loss_through(fn):
    """Scalar loss with non-trivial cotangents at every output position."""
    def go(*args):
        y = fn(*args)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))
    return go


ATTN_GRAD_CASES = [
    # (B, S, H, KH, D, causal, window, dtype)
    (1, 64, 4, 2, 32, True, 0, jnp.float32),      # GQA
    (2, 64, 2, 2, 32, False, 0, jnp.float32),     # bidirectional
    (1, 96, 2, 1, 32, True, 48, jnp.float32),     # MQA, window spans 32-blocks
    (1, 127, 2, 2, 32, True, 0, jnp.float32),     # prime length (pad path)
    (1, 64, 2, 2, 32, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,KH,D,causal,window,dtype", ATTN_GRAD_CASES)
def test_attention_grad_matches_oracle(B, S, H, KH, D, causal, window, dtype):
    q, k, v = _mk_qkv(jax.random.PRNGKey(11), B, S, H, KH, D, dtype)
    seg = _segments(jax.random.PRNGKey(13), B, S, n_seg=2)

    def f_pallas(q, k, v):
        return ops.packed_flash_attention(q, k, v, segment_ids=seg,
                                          causal=causal, window=window,
                                          block_q=32, block_k=32)

    def f_ref(q, k, v):
        return ref.packed_attention_ref(q, k, v, causal=causal, window=window,
                                        seg_q=seg, seg_k=seg)

    got = jax.grad(_loss_through(f_pallas), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss_through(f_ref), argnums=(0, 1, 2))(q, k, v)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_attention_bwd_fully_masked_query_tile():
    """A query tile whose segment id matches no key exercises the l > 0
    guard: exact-zero outputs and exact-zero dq for those rows, finite
    gradients everywhere, and agreement with the oracle."""
    B, KH, G, S, D = 1, 2, 1, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, KH, G, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KH, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KH, S, D), jnp.float32)
    # first 32 queries live in a segment no key belongs to -> with
    # block_q=32 the whole first query tile is fully masked
    seg_q = jnp.asarray(np.r_[np.full(32, 7), np.ones(32)].astype(np.int32))[None]
    seg_k = jnp.ones((B, S), jnp.int32)

    def f(q, k, v):
        return pfa.packed_flash_attention_bkgsd(
            q, k, v, seg_q, seg_k, causal=True, window=0,
            block_q=32, block_k=32, interpret=True)

    y = f(q, k, v)
    np.testing.assert_array_equal(np.asarray(y[:, :, :, :32]), 0.0)
    dq, dk, dv = jax.grad(_loss_through(f), argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.all(np.isfinite(np.asarray(g)))
    np.testing.assert_array_equal(np.asarray(dq[:, :, :, :32]), 0.0)

    # the oracle agrees on the surviving rows' gradients
    def f_ref(q, k, v):
        qf = q.transpose(0, 3, 1, 2, 4).reshape(B, S, KH * G, D)
        kf = k.transpose(0, 2, 1, 3)
        vf = v.transpose(0, 2, 1, 3)
        return ref.packed_attention_ref(qf, kf, vf, causal=True,
                                        seg_q=seg_q, seg_k=seg_k)

    dq_ref, dk_ref, dv_ref = jax.grad(
        _loss_through(f_ref), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                               rtol=5e-4, atol=5e-4)


def _layout(name, B, S):
    """Segment ids of the row layouts the tile skip meets, for 32-wide
    blocks (0 = padding, blocking.PAD_SEGMENT = a tail no real id
    matches)."""
    seg = np.zeros((B, S), np.int32)
    if name == "real_first":          # encoder rows: real patches, then pad
        seg[:, :S // 2] = 1
    elif name == "packed":            # several instances a row
        # row 0: blocks of one instance each, then of two (cross tiles
        # empty); row 1: a pure block beside a mixed one (partial tiles)
        seg[:, :32], seg[:, 32:40], seg[:, 40:] = 1, 2, 3
        seg[1:, 32:40] = 1
    elif name == "pad_tail":          # a causal row ending in a reserved tail
        seg[:, :S * 5 // 8] = 1
        seg[:, S * 5 // 8:] = blocking.PAD_SEGMENT
    return jnp.asarray(seg)


LAYOUT_CASES = [
    # (layout, B, H, KH, causal, dtype): 64 positions, D 32, blocks of 32;
    # each the shape of an ATTN_GRAD_CASES entry, whose compiles it shares
    ("real_first", 2, 2, 2, False, jnp.float32),
    ("real_first", 1, 2, 2, True, jnp.bfloat16),
    ("packed", 2, 2, 2, False, jnp.float32),
    ("packed", 1, 2, 2, True, jnp.bfloat16),
    ("pad_tail", 1, 4, 2, True, jnp.float32),
    ("pad_tail", 1, 2, 2, True, jnp.bfloat16),
]


@pytest.mark.parametrize("layout,B,H,KH,causal,dtype", LAYOUT_CASES)
def test_tile_skip_matches_float32_oracle(layout, B, H, KH, causal, dtype):
    """Layouts whose empty tiles the kernels skip: forward and gradients
    of inputs in ``dtype`` (bfloat16 operands reach the MXU as they are)
    against the float32 oracle on the same values."""
    S = 64
    q, k, v = _mk_qkv(jax.random.PRNGKey(21), B, S, H, KH, 32, dtype)
    seg = _layout(layout, B, S)
    skipped, total = ops.attention_skip(q, k, seg, causal=causal,
                                        block_q=32, block_k=32)
    assert 0 < float(skipped) < float(total)

    def f_pallas(q, k, v):
        return ops.packed_flash_attention(q, k, v, segment_ids=seg,
                                          causal=causal, block_q=32,
                                          block_k=32)

    def f_ref(q, k, v):
        return ref.packed_attention_ref(q, k, v, causal=causal, seg_q=seg,
                                        seg_k=seg)

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    fwd_tol, grad_tol = (2e-2, 5e-2) if dtype == jnp.bfloat16 else (2e-5, 5e-4)
    np.testing.assert_allclose(np.asarray(f_pallas(q, k, v), np.float32),
                               np.asarray(f_ref(*f32)), rtol=fwd_tol,
                               atol=fwd_tol)
    got = jax.grad(_loss_through(f_pallas), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss_through(f_ref), argnums=(0, 1, 2))(*f32)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                                   rtol=grad_tol, atol=grad_tol,
                                   err_msg=name)


@pytest.mark.parametrize("s,g,block", [
    (8192, 1, 1024), (1024, 1, 1024),   # InternViT: a 1024-tile fits
    (1792, 2, 512), (1280, 2, 512),     # InternLM2, GQA 2: 2·1024² does not
    (96, 1, 96), (300, 8, 256),         # short axis clamped; G 8
])
def test_attention_block_follows_the_shape(s, g, block):
    """The tile edge is the largest MXU multiple whose folded score tile
    fits the budget, clamped to the axis; the grid pads under one tile."""
    assert ops.attention_block(s, g) == block
    assert g * block * block <= ops.ATTN_TILE_ELEMS
    assert blocking.pick_block(s, block)[1] - s < block


def test_attention_skip_counts_the_kernels_grid():
    """2048 positions in blocks of 512, the first block segment 1 and the
    rest segment 0, non-causal: the 6 cross tiles of 16 are skipped; with
    causal the 6 tiles above the diagonal go too, and the tiles left are
    the 4 on it and the 3 below it in segment 0."""
    seg = jnp.asarray(np.r_[np.ones(512), np.zeros(1536)].astype(np.int32))
    seg = jnp.broadcast_to(seg, (2, 2048))
    q = jnp.zeros((2, 2048, 4, 8))
    k = jnp.zeros((2, 2048, 2, 8))
    full = 2 * 2048 * 2048 * 4
    for causal, live in ((False, 10), (True, 4 + 3)):
        skipped, total = ops.attention_skip(q, k, seg, causal=causal,
                                            block_q=512, block_k=512)
        assert float(total) == full
        assert float(skipped) / float(total) == (16 - live) / 16


def test_gqa_kv_head_mapping():
    """ops.py regression: query head h must read kv head h // G.  With
    uniform attention and per-kv-head constant values, head h's output is
    exactly its kv head's constant."""
    B, S, KH, G, D = 1, 32, 2, 2, 16
    H = KH * G
    q = jnp.zeros((B, S, H, D))
    k = jnp.zeros((B, S, KH, D))
    v = jnp.broadcast_to(
        jnp.arange(1, KH + 1, dtype=jnp.float32)[None, None, :, None],
        (B, S, KH, D))
    out = ops.packed_flash_attention(q, k, v, block_q=16, block_k=16)
    want = jnp.repeat(jnp.arange(1, KH + 1, dtype=jnp.float32), G)
    np.testing.assert_allclose(
        np.asarray(out),
        np.broadcast_to(np.asarray(want)[None, None, :, None], out.shape),
        rtol=1e-6, atol=1e-6)
    # and on random inputs the full H != KH path matches the oracle
    q, k, v = _mk_qkv(jax.random.PRNGKey(2), 2, 64, 8, 2, 32, jnp.float32)
    got = ops.packed_flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.packed_attention_ref(q, k, v)),
        rtol=2e-5, atol=2e-5)


def test_pick_block_prime_lengths_no_extra_grid_steps():
    """The shared pad helper must never add a whole extra block: the grid
    runs exactly ceil(s / b) steps even for prime lengths."""
    for s in (1, 63, 64, 96, 127, 257, 509):
        for tgt in (32, 64, 128, 512):
            b, padded = blocking.pick_block(s, tgt)
            assert 1 <= b <= max(1, tgt) and padded >= s
            assert padded % b == 0
            assert padded // b == -(-s // b), (s, tgt, b, padded)


MAMBA_GRAD_CASES = [
    (1, 64, 32, 8, 32, 32),
    (2, 67, 24, 8, 32, 16),        # prime seq, non-multiple channels
    (1, 32, 17, 4, 16, 8),
]


@pytest.mark.parametrize("B,S,di,N,chunk,c_blk", MAMBA_GRAD_CASES)
def test_mamba_grad_matches_oracle(B, S, di, N, chunk, c_blk):
    ks = jax.random.split(jax.random.PRNGKey(21), 6)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 1)
    B_t = jax.random.normal(ks[2], (B, S, N))
    C_t = jax.random.normal(ks[3], (B, S, N))
    A = -jnp.exp(jax.random.normal(ks[4], (di, N)) * 0.3)
    D = jax.random.normal(ks[5], (di,))

    def f_pallas(u, dt, B_t, C_t, A, D):
        y, _ = ops.mamba_scan(u, dt, B_t, C_t, A, D, chunk=chunk, c_blk=c_blk)
        return y

    def f_ref(u, dt, B_t, C_t, A, D):
        y, _ = ref.mamba_scan_ref(u, dt, B_t, C_t, A, D)
        return y

    args = (u, dt, B_t, C_t, A, D)
    got = jax.grad(_loss_through(f_pallas), argnums=tuple(range(6)))(*args)
    want = jax.grad(_loss_through(f_ref), argnums=tuple(range(6)))(*args)
    for g, w, name in zip(got, want, ("du", "ddt", "dB", "dC", "dA", "dD")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-3, atol=1e-3, err_msg=name)


@pytest.mark.parametrize("B,S,H,M,chunk", [(1, 64, 2, 32, 32),
                                           (2, 61, 2, 16, 16)])
def test_rwkv6_grad_matches_oracle(B, S, H, M, chunk):
    """Gradients through y AND the final state (the s_final cotangent
    seeds the adjoint state at the last chunk)."""
    ks = jax.random.split(jax.random.PRNGKey(23), 5)
    r = jax.random.normal(ks[0], (B, S, H, M))
    k = jax.random.normal(ks[1], (B, S, H, M))
    v = jax.random.normal(ks[2], (B, S, H, M))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, M)))
    u = jax.random.normal(ks[4], (H, M)) * 0.1

    def loss(fn):
        def go(r, k, v, w, u):
            y, s = fn(r, k, v, w, u)
            return (jnp.sum(jnp.sin(y.astype(jnp.float32)))
                    + jnp.sum(jnp.cos(s.astype(jnp.float32))))
        return go

    args = (r, k, v, w, u)
    got = jax.grad(loss(lambda *a: ops.rwkv6_scan(*a, chunk=chunk)),
                   argnums=tuple(range(5)))(*args)
    want = jax.grad(loss(ref.rwkv6_scan_ref), argnums=tuple(range(5)))(*args)
    for g, wv, name in zip(got, want, ("dr", "dk", "dv", "dw", "du")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                   rtol=1e-3, atol=1e-3, err_msg=name)


def test_model_grad_through_pallas_impls():
    """End-to-end: jax.grad through a hybrid model with
    attn_impl/ssm_impl = "pallas" matches the reference impls."""
    from repro.common.types import ModelConfig
    from repro.models import model as model_lib
    from repro.models.model import FwdCtx

    cfg = ModelConfig(name="t", family="hybrid", n_layers=3, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                      layer_pattern=("attention", "mamba", "rwkv6"),
                      rwkv_head_dim=32, ssm_d_state=8,
                      dtype="float32", param_dtype="float32")
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 1, 64)

    def loss(params, ctx):
        out, _, _ = model_lib.forward(params, cfg, tokens=toks, ctx=ctx)
        return jnp.mean(jnp.sin(out.astype(jnp.float32)))

    ctx_p = FwdCtx(mode="train", attn_impl="pallas", ssm_impl="pallas")
    ctx_r = FwdCtx(mode="train", attn_impl="naive", ssm_impl="xla")
    g_p = jax.grad(loss)(params, ctx_p)
    g_r = jax.grad(loss)(params, ctx_r)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(g_p)
    flat_r, _ = jax.tree_util.tree_flatten_with_path(g_r)
    for (path, a), (_, b) in zip(flat_p, flat_r):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-3, atol=2e-3, err_msg=jax.tree_util.keystr(path))


def test_kernels_refuse_backends_they_cannot_run_on(monkeypatch):
    """Mosaic on a TPU, the interpreter on the CPU, nothing elsewhere: a
    GPU backend must not silently fall back to interpret mode."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        ops._interpret()
