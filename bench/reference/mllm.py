"""Plain float32 reference of the multimodal training step the benchmark times.

Written from the configuration file alone, in ``jax.numpy``, with no import
of the program under test.  It mirrors what the program computes, departures
from the published models included (see PERF.md, "Cells"):

* the modality frontend is a stub: rows carry precomputed patch / frame
  embeddings, projected by ``in_proj``;
* the encoder attends over the whole row as one segment (padding as its own
  segment), where InternViT and Whisper encode each tile or clip alone;
* the connector output of a whole row is average-pooled to
  ``tokens_per_item_out`` tokens, whatever the number of media items;
* every norm is an RMSNorm, GELU is the tanh approximation.

The step: per microbatch, cross-entropy averaged over the microbatch's valid
labels; gradients averaged over microbatches; clipped by global norm; AdamW
with decoupled weight decay on every leaf of two or more dimensions.

``Arith`` sets the precision of every matrix product: ``"highest"`` is the
reference, ``"fp8"`` rounds both operands of each forward product to
float8 e4m3 with a per-tensor scale (the control, one step below the
bfloat16 the configuration states).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
Q_BLOCK = 512            # query rows per attention block (memory only)


# --------------------------------------------------------------------------- #
# precision of the matrix products
# --------------------------------------------------------------------------- #
class Arith:
    def __init__(self, mode: str = "highest"):
        if mode not in ("highest", "fp8"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def dot(self, eq: str, a, b):
        if self.mode == "fp8":
            a, b = _fake_fp8(a), _fake_fp8(b)
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def _fake_fp8(x):
    """x rounded to float8 e4m3 with a per-tensor scale; the gradient
    passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


# --------------------------------------------------------------------------- #
# weights, made from the seed by the benchmark (never by the program)
# --------------------------------------------------------------------------- #
def _stack_shapes(c: dict, n_layers: int) -> dict:
    d, h, kh = c["d_model"], c["n_heads"], c["n_kv_heads"]
    hd = c["head_dim"] or d // h
    ff = c["d_ff"]
    L = n_layers
    ffn = {"w_up": ((L, d, ff), d ** -0.5), "w_down": ((L, ff, d), ff ** -0.5)}
    if c["activation"] in ("swiglu", "geglu"):
        ffn["w_gate"] = ((L, d, ff), d ** -0.5)
    return {
        "ln1": {"scale": ((L, d), None)},
        "attn": {"wq": ((L, d, h, hd), d ** -0.5),
                 "wk": ((L, d, kh, hd), d ** -0.5),
                 "wv": ((L, d, kh, hd), d ** -0.5),
                 "wo": ((L, h, hd, d), (h * hd) ** -0.5)},
        "ln2": {"scale": ((L, d), None)},
        "ffn": ffn,
    }


def param_shapes(m: dict) -> dict:
    """(shape, init std) of every leaf; std None means ones."""
    enc, llm = m["encoder"], m["llm"]
    de, dl = enc["d_model"], llm["d_model"]
    ch = m["connector_hidden"]
    encoder = {"in_proj": {"w": ((enc["input_embed_dim"], de),
                                 enc["input_embed_dim"] ** -0.5)},
               "blocks": {"pos0": _stack_shapes(enc, enc["n_layers"])},
               "final_norm": {"scale": ((de,), None)}}
    if ch:
        connector = {"w1": ((de, ch), de ** -0.5), "w2": ((ch, dl), ch ** -0.5)}
    else:
        connector = {"w1": ((de, dl), de ** -0.5)}
    V = llm["vocab_size"]
    lm = {"embed": {"w": ((V, dl), 0.02)},
          "blocks": {"pos0": _stack_shapes(llm, llm["n_layers"])},
          "final_norm": {"scale": ((dl,), None)},
          "unembed": {"w": ((dl, V), dl ** -0.5)}}
    return {"encoder": encoder, "connector": connector, "llm": lm}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(key, m: dict):
    """Every weight from one key, float32; call under ``jax.jit``."""
    spec = param_shapes(m)
    flat, tree = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
    out = []
    for i, (shape, std) in enumerate(flat):
        if std is None:
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32) * std)
    return jax.tree_util.tree_unflatten(tree, out)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, :, None] * freqs          # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(ar: Arith, p, x, c, seg, pos):
    """x: (B,S,d); seg: (B,S) int; exact softmax over all keys, computed
    one block of queries at a time so that it fits."""
    B, S, _ = x.shape
    h, kh = c["n_heads"], c["n_kv_heads"]
    q = ar.dot("bsd,dhk->bshk", x, p["wq"])
    k = ar.dot("bsd,dhk->bshk", x, p["wk"])
    v = ar.dot("bsd,dhk->bshk", x, p["wv"])
    if c["use_rope"]:
        q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, h // kh, axis=2)        # query head j reads kv j // G
    v = jnp.repeat(v, h // kh, axis=2)
    D = q.shape[-1]
    bq = next(b for b in range(min(S, Q_BLOCK), 0, -1) if S % b == 0)
    nq = S // bq
    qb = q.reshape(B, nq, bq, h, D).transpose(1, 0, 2, 3, 4)
    sq = seg.reshape(B, nq, bq).transpose(1, 0, 2)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        q_i, s_i, i = args
        s = ar.dot("bqhd,bkhd->bhqk", q_i, k) * D ** -0.5
        mask = s_i[:, :, None] == seg[:, None, :]                  # (B,q,k)
        if c["causal"]:
            qpos = i * bq + jnp.arange(bq)
            mask = mask & (kpos[None, None, :] <= qpos[None, :, None])
        s = jnp.where(mask[:, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        pr = jnp.where(mask[:, None], pr, 0.0)
        return ar.dot("bhqk,bkhd->bqhd", pr, v)

    out = jax.lax.map(block, (qb, sq, jnp.arange(nq)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, h, D)
    return ar.dot("bshk,hkd->bsd", out, p["wo"])


def _ffn(ar: Arith, p, x, c):
    up = ar.dot("bsd,df->bsf", x, p["w_up"])
    if c["activation"] == "swiglu":
        hid = _silu(ar.dot("bsd,df->bsf", x, p["w_gate"])) * up
    elif c["activation"] == "gelu":
        hid = _gelu(up)
    else:
        raise ValueError(f"activation {c['activation']!r} not in the reference")
    return ar.dot("bsf,fd->bsd", hid, p["w_down"])


def _stack(ar: Arith, blocks, x, c, seg, pos):
    eps = c["norm_eps"]

    @jax.checkpoint
    def layer(x, lp):
        x = x + _attention(ar, lp["attn"], _rms(x, lp["ln1"]["scale"], eps),
                           c, seg, pos)
        x = x + _ffn(ar, lp["ffn"], _rms(x, lp["ln2"]["scale"], eps), c)
        return x, None

    x, _ = jax.lax.scan(layer, x, blocks["pos0"])
    return x


def next_token_labels(tokens, mask) -> np.ndarray:
    """The label of each text position: the next token where both positions
    hold text, -1 elsewhere.  The reference makes its labels so, from the
    fed tokens and mask, and takes none from the program."""
    tokens, mask = np.asarray(tokens), np.asarray(mask) > 0
    nxt = np.zeros_like(tokens)
    nxt[..., :-1] = tokens[..., 1:]
    both = np.zeros_like(mask)
    both[..., :-1] = mask[..., :-1] & mask[..., 1:]
    return np.where(both, nxt, -1).astype(np.int32)


def row_nll(ar: Arith, params, m: dict, row: dict):
    """(sum of next-token NLL over valid labels, their count) for a batch of
    rows; ``row`` holds media_embeds, media_mask, text_tokens, text_mask and
    the labels made by ``next_token_labels``."""
    enc, llm = m["encoder"], m["llm"]
    pe, pl = params["encoder"], params["llm"]
    media = row["media_embeds"].astype(jnp.float32)
    B, Tm, _ = media.shape
    h = ar.dot("bse,ed->bsd", media, pe["in_proj"]["w"])
    h = _stack(ar, pe["blocks"], h, enc, row["media_mask"].astype(jnp.int32),
               jnp.broadcast_to(jnp.arange(Tm)[None], (B, Tm)))
    h = _rms(h, pe["final_norm"]["scale"], enc["norm_eps"])
    pc = params["connector"]
    if "w2" in pc:
        h = ar.dot("bsh,hd->bsd", _gelu(ar.dot("bsd,dh->bsh", h, pc["w1"])),
                   pc["w2"])
    else:
        h = ar.dot("bsd,dh->bsh", h, pc["w1"])
    tpo = m["tokens_per_item_out"]
    if tpo:
        f = max(1, Tm // tpo)
        if f > 1:
            n = Tm // f
            h = h[:, : n * f].reshape(B, n, f, h.shape[-1]).mean(axis=2)
    Tt = row["text_tokens"].shape[1]
    x = jnp.concatenate([h, pl["embed"]["w"][row["text_tokens"]]], axis=1)
    To = h.shape[1]
    seg = jnp.concatenate([jnp.ones((B, To), jnp.int32),
                           (row["text_mask"] > 0).astype(jnp.int32)], axis=1)
    pos = jnp.broadcast_to(jnp.arange(To + Tt)[None], (B, To + Tt))
    x = _stack(ar, pl["blocks"], x, llm, seg, pos)
    x = _rms(x, pl["final_norm"]["scale"], llm["norm_eps"])[:, To:]
    logits = ar.dot("bsd,dv->bsv", x, pl["unembed"]["w"])
    labels = row["labels"]
    valid = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    nll = jnp.where(valid, lse - gold, 0.0)
    return nll.sum(), valid.sum().astype(jnp.float32)


# --------------------------------------------------------------------------- #
# the training step, one row at a time
# --------------------------------------------------------------------------- #
def _vg(ar, m, params, row):
    """((NLL sum, label count), gradient of the NLL sum) for one row."""
    return jax.value_and_grad(lambda p: row_nll(ar, p, m, row),
                              has_aux=True)(params)


class Reference:
    """Runs the configuration's training step in plain jax.numpy."""

    def __init__(self, m: dict, opt: dict, precision: str = "highest"):
        self.m, self.opt, self.ar = m, opt, Arith(precision)
        ar = self.ar

        def acc(total, params, row, w):
            (s, _), g = _vg(ar, m, params, row)
            return jax.tree.map(lambda t, x: t + w * x, total, g), s

        self._acc = jax.jit(acc, donate_argnums=0)
        self._update = jax.jit(partial(_adamw, opt), donate_argnums=(0, 2))
        self._norms = jax.jit(leaf_norms)
        self._zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        self._change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))

    def grad(self, params, batch):
        """Mean loss and mean gradient of one step's batch: a list of
        microbatches, each a list of single-row dicts (media_embeds,
        media_mask, text_tokens, text_mask).  Rows are summed into
        one accumulator, weighted by their microbatch's label count."""
        total, loss = self._zeros(params), 0.0
        for mb in batch:
            mb = [{**r, "labels": next_token_labels(r["text_tokens"],
                                                    r["text_mask"])}
                  for r in mb]
            n = max(sum(int((r["labels"] >= 0).sum()) for r in mb), 1)
            w = 1.0 / (n * len(batch))
            for row in mb:
                total, s = self._acc(total, params, row, jnp.float32(w))
                loss += float(s) * w
        return loss, total

    def run(self, key, batches, init_fn) -> dict:
        """Steps from the weights ``init_fn(key)`` through ``batches``.
        Returns each step's loss, the per-leaf norms of the first step's
        clipped gradient and of the weights' change after the last step."""
        params = init_fn(key)
        state = None
        losses, g1 = [], None
        for batch in batches:
            loss, grads = self.grad(params, batch)
            losses.append(loss)
            if g1 is None:
                g1 = np.asarray(self._norms(grads))
                g1 = g1 * _clip_scale(g1, self.opt["grad_clip"])
                state = {"m": self._zeros(params), "v": self._zeros(params),
                         "step": jnp.zeros((), jnp.int32)}
            params, state = self._update(params, grads, state)
            del grads
        del state
        dp = np.asarray(self._change(params, init_fn(key)))
        return {"losses": losses, "grad_norms": g1, "update_norms": dp}


def _clip_scale(norms: np.ndarray, clip: float) -> float:
    if not clip:
        return 1.0
    g = float(np.sqrt(np.sum(np.square(norms.astype(np.float64)))))
    return min(1.0, clip / (g + 1e-9))


def _adamw(opt, params, grads, state):
    step = state["step"] + 1
    if opt["grad_clip"]:
        g = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(grads)))
        grads = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, opt["grad_clip"] / (g + 1e-9)),
            grads)
    t = step.astype(jnp.float32)
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        if p.ndim >= 2 and opt["weight_decay"]:
            delta = delta + opt["weight_decay"] * p
        return p - opt["lr"] * delta, m, v

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "step": step}


def leaf_norms(tree):
    """Float32 norm of every leaf, in tree-flatten order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_names(tree) -> list[str]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]
