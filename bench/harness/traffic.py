"""Training traffic: items drawn from a mixture of modality profiles.

One generator serves every training mix; a mix is a data file under
``bench/traffic/`` (see ``mixed.json``).  The draw is the program's own
(``MixedDataset.sample`` in ``src/repro/data/synthetic.py``, copied so that
the yardstick cannot move): a profile by the mixture's weights, then a media
count and a text length, uniform over the profile's ranges.

So that every seed does the same work, every seed trains one pool:
``POOL_STEPS`` global batches of items drawn from ``POOL_SEED``, more
batches than any window trains.  The pool is split into batches that each
hold, as near as can be, the same trained tokens and the same media items
(the host's ``materialize`` draws an embedding for every media position),
so that whichever batches a window reaches, it does the same work a step.
``--seed`` orders the batches of each pass over the pool and the items of
each batch.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# 1024 items: more than any cell's window trains, so that a window samples
# the mixture and not one small draw of it (PERF.md, "Cells").
POOL_SEED = 1207
POOL_STEPS = 256
# Media items weigh this much beside trained tokens when the batches are
# balanced: enough to even out the host's work a step where media embeddings
# dominate it, not so much that the trained tokens stop being even.
MEDIA_WEIGHT = 0.3


@dataclass(frozen=True)
class Item:
    n_media: int
    text_len: int
    kind: str
    item_id: int


def sample(spec: dict, n: int, rng: np.random.Generator) -> list[tuple]:
    """``n`` items ``(n_media, text_len, kind)``, drawn as the program's
    ``MixedDataset.sample`` draws them from the same generator."""
    names = sorted(spec["mixture"])
    probs = np.array([spec["mixture"][k] for k in names], np.float64)
    probs = probs / probs.sum()
    items = []
    for k in rng.choice(len(names), size=n, p=probs):
        prof = spec["profiles"][names[k]]
        lo, hi = prof["media"]
        media = int(rng.integers(lo, hi + 1)) if hi else 0
        lo, hi = prof["text"]
        items.append((media, int(rng.integers(lo, hi + 1)), names[k]))
    return items


class Traffic:
    def __init__(self, spec: dict, seed: int, media_tokens: int = 1):
        """``media_tokens``: LLM tokens a media item trains (the connector's
        output per item), to weigh items when the batches are balanced."""
        self.spec = spec
        self.seed = int(seed)
        self.items_per_step = int(spec["items_per_step"])
        self.pool = sample(spec, POOL_STEPS * self.items_per_step,
                           np.random.default_rng(POOL_SEED))
        self.batches = self._balance(media_tokens)

    def tokens(self, media: int, text: int, media_tokens: int) -> int:
        return (min(media, self.spec["media_cap"]) * media_tokens
                + min(text, self.spec["text_cap"]))

    def _balance(self, media_tokens: int) -> list[list[int]]:
        """Pool indices in batches of ``items_per_step``, each as near as
        can be to the mean load.  An item's load is its trained tokens and,
        at ``MEDIA_WEIGHT``, its media items, each in units of its pool
        mean.  The largest item goes first, into the batch with room whose
        load it adds to least; then the batch farthest from the mean swaps
        an item for the one elsewhere that brings both nearest, until no
        swap does."""
        k = self.items_per_step
        n = len(self.pool) // k
        w = np.array([(self.tokens(m, t, media_tokens),
                       min(m, self.spec["media_cap"]))
                      for m, t, _ in self.pool], np.float64)
        w = w / np.maximum(w.mean(axis=0), 1e-12) * [1.0, MEDIA_WEIGHT]
        load, size = np.zeros((n, 2)), np.zeros(n, np.int64)
        idx = np.zeros((n, k), np.int64)
        for i in sorted(range(len(w)), key=lambda i: (-w[i].sum(), i)):
            cost = load @ w[i]
            cost[size == k] = np.inf
            b = int(np.argmin(cost))
            idx[b, size[b]] = i
            load[b] += w[i]
            size[b] += 1
        mean = load.mean(axis=0)
        for _ in range(len(w)):
            dev = ((load - mean) ** 2).sum(axis=1)
            a = int(np.argmax(dev))
            d = w[idx][:, None] - w[idx[a]][None, :, None]   # (n, k, k, 2)
            gain = (dev[a] + dev[:, None, None]
                    - ((load[a] + d - mean) ** 2).sum(-1)
                    - ((load[:, None, None] - d - mean) ** 2).sum(-1))
            gain[a] = -np.inf
            b, i, j = np.unravel_index(int(np.argmax(gain)), gain.shape)
            if gain[b, i, j] <= 1e-12:
                break
            idx[a, i], idx[b, j] = idx[b, j], idx[a, i]
            load[a], load[b] = w[idx[a]].sum(axis=0), w[idx[b]].sum(axis=0)
        return idx.tolist()

    def step_items(self, step: int) -> list[Item]:
        """The items of global step ``step`` (0, 1, ...)."""
        n = len(self.batches)
        epoch, pos = divmod(step, n)
        order = np.random.default_rng(
            np.random.SeedSequence([self.seed, epoch])).permutation(n)
        batch = self.batches[order[pos]]
        inner = np.random.default_rng(
            np.random.SeedSequence([self.seed, epoch, pos])).permutation(
                len(batch))
        first = step * self.items_per_step
        return [Item(*self.pool[batch[j]], first + r)
                for r, j in enumerate(inner)]
