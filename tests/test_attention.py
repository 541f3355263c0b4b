"""XLA flash attention (custom VJP) vs naive oracle: fwd + gradients;
which attention a forward runs, and the share of the grid it skips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import MLLMConfig, ModalityStub, ModelConfig
from repro.models import mllm as mllm_lib
from repro.models import model as model_lib
from repro.models.layers.attention import (BLOCK, attend_naive,
                                           flash_attention_xla)
from repro.models.model import FwdCtx, resolve_attn_impl
from repro.train.optim import AdamWConfig, adamw_init
from repro.train.step import make_train_step

CASES = [
    # B, S, H, KH, D, causal, window, segs
    (2, 128, 4, 2, 32, True, 0, True),
    (1, 96, 4, 1, 32, True, 24, True),
    (2, 64, 4, 4, 32, False, 0, False),
    (1, 128, 8, 8, 64, True, 0, False),
    (1, 97, 4, 2, 32, True, 24, True),      # prime: 4 tiles, padded
    (2, 100, 4, 4, 32, False, 0, False),    # only the padding masks the tail
]


def _inputs(B, S, H, KH, D, segs):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KH, D))
    v = jax.random.normal(ks[2], (B, S, KH, D))
    seg = None
    if segs:
        seg = jnp.concatenate([jnp.ones((B, S // 2), jnp.int32),
                               jnp.full((B, S - S // 2), 2, jnp.int32)], 1)
    return q, k, v, seg


@pytest.mark.parametrize("B,S,H,KH,D,causal,window,segs", CASES)
def test_flash_forward_matches_naive(B, S, H, KH, D, causal, window, segs):
    q, k, v, seg = _inputs(B, S, H, KH, D, segs)
    out_n = attend_naive(q, k, v, causal=causal, window=window,
                         seg_q=seg, seg_k=seg)
    out_f = flash_attention_xla(q, k, v, causal=causal, window=window,
                                seg_q=seg, seg_k=seg, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out_n), np.asarray(out_f),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,KH,D,causal,window,segs", CASES)
def test_flash_custom_vjp_matches_naive_grads(B, S, H, KH, D, causal,
                                              window, segs):
    q, k, v, seg = _inputs(B, S, H, KH, D, segs)

    def loss(fn):
        def inner(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(jnp.square(o) + o)
        return inner

    fn_n = lambda q, k, v: attend_naive(q, k, v, causal=causal, window=window,
                                        seg_q=seg, seg_k=seg)
    fn_f = lambda q, k, v: flash_attention_xla(
        q, k, v, causal=causal, window=window, seg_q=seg, seg_k=seg,
        block_q=32, block_k=32)
    gn = jax.grad(loss(fn_n), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(fn_f), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gn, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_flash_no_quadratic_residuals():
    """The custom VJP must not save S^2 probabilities: check the jaxpr of
    the VJP for any (S, S)-sized residual."""
    S = 256
    q, k, v, _ = _inputs(1, S, 2, 2, 16, False)

    def f(q, k, v):
        return jnp.sum(flash_attention_xla(q, k, v, block_q=32, block_k=32))

    # residuals = closure of the VJP function
    _, vjp = jax.vjp(f, q, k, v)
    leaves = jax.tree_util.tree_leaves(vjp)
    for leaf in leaves:
        if hasattr(leaf, "shape"):
            assert not (leaf.ndim >= 2 and leaf.shape[-1] == S
                        and leaf.shape[-2] == S), \
                f"quadratic residual {leaf.shape}"


def _scan_lengths(jaxpr):
    """Lengths of every ``lax.scan`` in ``jaxpr`` and the jaxprs it calls."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _scan_lengths(sub)
    return out


def test_flash_default_blocks_pad_to_whole_tiles():
    """At a length that is not a multiple of ``BLOCK`` the default tiles
    pad to ceil(S / BLOCK) grid steps, forward and backward, where a
    divisor search would fall to tiles of 1 at a prime length."""
    S = 1031
    q, k, v, _ = _inputs(1, S, 2, 1, 8, False)
    f = lambda q, k, v: jnp.sum(flash_attention_xla(q, k, v))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)
    lengths = _scan_lengths(jaxpr.jaxpr)
    assert lengths and set(lengths) == {-(-S // BLOCK)}, lengths


# --------------------------------------------------------------------------- #
# decode_pos contract: scalar or (B,), anything else is an error
# --------------------------------------------------------------------------- #
from repro.models.layers.attention import cache_write, check_decode_pos  # noqa: E402


def test_check_decode_pos_scalar_broadcasts():
    pos = check_decode_pos(3, 4)
    np.testing.assert_array_equal(np.asarray(pos), np.full(4, 3))
    vec = check_decode_pos(jnp.arange(4), 4)
    np.testing.assert_array_equal(np.asarray(vec), np.arange(4))


@pytest.mark.parametrize("bad", [jnp.zeros((4, 1), jnp.int32),
                                 jnp.zeros((3,), jnp.int32),
                                 jnp.zeros((1, 4), jnp.int32)])
def test_check_decode_pos_rejects_wrong_shape(bad):
    with pytest.raises(ValueError, match="decode_pos"):
        check_decode_pos(bad, 4)


def test_cache_write_rejects_malformed_pos():
    """A (B, 1) position used to broadcast silently and write KV rows at
    the wrong ring slots; now it raises."""
    B, C, Kh, D = 2, 8, 2, 16
    cache = {"k": jnp.zeros((B, C, Kh, D)), "v": jnp.zeros((B, C, Kh, D)),
             "kpos": jnp.full((B, C), -1, jnp.int32)}
    k_new = jnp.ones((B, 1, Kh, D))
    with pytest.raises(ValueError, match="decode_pos"):
        cache_write(cache, k_new, k_new, jnp.zeros((B, 1), jnp.int32))
    # the two legal forms still work
    out = cache_write(cache, k_new, k_new, 5)
    np.testing.assert_array_equal(np.asarray(out["kpos"][:, 5]), [5, 5])
    out = cache_write(cache, k_new, k_new, jnp.asarray([0, 3]))
    np.testing.assert_array_equal(np.asarray(out["kpos"][0, 0]), 0)
    np.testing.assert_array_equal(np.asarray(out["kpos"][1, 3]), 3)


# --------------------------------------------------------------------------- #
# Which attention runs, and the share of its grid the Pallas kernel skips
# --------------------------------------------------------------------------- #
SHARD_CTX = ("mesh", ("data",), ("model",))


@pytest.mark.parametrize("backend,mode,sharded,impl,want", [
    ("tpu", "train", False, None, "pallas"),
    ("cpu", "train", False, None, "chunked"),
    ("gpu", "train", False, None, "chunked"),
    ("tpu", "prefill", False, None, "chunked"),
    ("tpu", "decode", False, None, "chunked"),
    ("tpu", "train", True, None, "chunked"),
    ("cpu", "train", False, "pallas", "pallas"),
    ("tpu", "train", False, "naive", "naive"),
    ("tpu", "train", False, "chunked", "chunked"),
    ("tpu", "prefill", True, "pallas", "pallas"),
])
def test_resolve_attn_impl(monkeypatch, backend, mode, sharded, impl, want):
    """Unset, the Pallas kernel only for a one-device train step on a TPU;
    a set ``attn_impl`` is always honoured."""
    monkeypatch.setattr(model_lib.jax, "default_backend", lambda: backend)
    ctx = FwdCtx(mode=mode, attn_impl=impl,
                 shard_ctx=SHARD_CTX if sharded else None)
    assert resolve_attn_impl(ctx) == want


def _lm(causal=True):
    return ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                       causal=causal)


@pytest.mark.parametrize("impl,want", [("pallas", 0.5), (None, 0.0)])
def test_train_step_reports_attn_skip_share(impl, want):
    """2048 positions, the first 1024 segment 1 and the rest 0, causal, in
    the kernel's 1024-tiles: the tile above the diagonal and the tile
    across segments are skipped, half the grid in every layer and
    microbatch.  The XLA path (what an unset ``attn_impl`` runs on the CPU)
    skips nothing."""
    cfg = _lm()
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    S = 2048
    seg = jnp.asarray(np.r_[np.ones(S // 2), np.zeros(S // 2)], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 1, S), 1, 64)
    batch = {"tokens": toks, "labels": toks,
             "segment_ids": jnp.broadcast_to(seg, (2, 1, S))}
    step = make_train_step(cfg, AdamWConfig(),
                           ctx=FwdCtx(mode="train", attn_impl=impl),
                           donate=False)
    _, _, metrics = step(params, adamw_init(params), batch, 1e-3)
    assert float(metrics["attn_skip_share"]) == want
    assert np.isfinite(float(metrics["loss"]))


def test_forward_train_sums_encoder_and_llm_skips():
    """An MLLM row of one real image (1024 patches) and 1024 padded ones:
    the encoder skips its 2 cross tiles of 4 in each layer, the LLM's
    single tile is live; the forward's ``attn_skip`` adds both."""
    enc = ModelConfig(name="e", family="vlm-enc", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=0,
                      causal=False, use_rope=False, input_embed_dim=16,
                      has_lm_head=False, activation="gelu")
    llm = ModelConfig(name="l", family="vlm-llm", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64)
    mcfg = MLLMConfig(name="m", encoder=enc, llm=llm,
                      stub=ModalityStub("vision", 1024, 16),
                      tokens_per_item_out=64)
    params = mllm_lib.init(jax.random.PRNGKey(0), mcfg)
    T, X = 2048, 64
    batch = {"media_embeds": jnp.ones((1, T, 16)),
             "media_mask": jnp.asarray(np.r_[np.ones(1024), np.zeros(1024)],
                                       jnp.int32)[None],
             "text_tokens": jnp.ones((1, X), jnp.int32),
             "text_mask": jnp.ones((1, X), jnp.int32)}
    _, aux = mllm_lib.forward_train(
        params, mcfg, batch, ctx=FwdCtx(mode="train", attn_impl="pallas"))
    enc_tile, llm_tile = 1024 * 1024 * 2, (64 + X) ** 2 * 4
    np.testing.assert_array_equal(
        np.asarray(aux["attn_skip"]),
        [2 * 2 * enc_tile, 2 * 4 * enc_tile + 1 * llm_tile])
