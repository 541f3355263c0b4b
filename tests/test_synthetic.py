"""`MixedDataset.materialize`: the stub batch's distribution, its masks and
labels, and its independence from the number of threads that draw it."""
import sys

import numpy as np
import pytest

from repro.data import synthetic
from repro.data.items import DataItem
from repro.data.synthetic import MixedDataset

TPM = 300                    # media rows an item
EMBED = 128
VOCAB = 97
MAX_MEDIA, MAX_TEXT = 6144, 64
# 20 + 8 + 3 items of media: 6000 + 2400 + 900 rows of 128, 1.19 M samples,
# over _INLINE_SAMPLES, so the pool draws them
ITEMS = [DataItem(20, 40), DataItem(8, 64), DataItem(3, 90), DataItem(0, 5)]


def _materialize(items=ITEMS, seed=11, **kw):
    ds = MixedDataset("mixed", seed=0, tokens_per_media_item=TPM)
    return ds.materialize(items, embed_dim=EMBED, vocab_size=VOCAB,
                          max_media=MAX_MEDIA, max_text=MAX_TEXT, seed=seed,
                          **kw)


@pytest.fixture
def workers(monkeypatch):
    """Set the pool's size for one test: a fresh pool of ``n`` threads,
    shut down after it."""
    made = []

    def use(n):
        monkeypatch.setattr(synthetic, "_WORKERS", n)
        monkeypatch.setattr(synthetic, "_pool", None)
        made.append(synthetic._executor())

    yield use
    for pool in made:
        pool.shutdown(wait=True)


@pytest.mark.parametrize("n", [1, 2, 5, 16])
def test_batch_is_the_same_on_any_worker_count(workers, monkeypatch, n):
    """Bit for bit the batch the calling thread draws alone, with any
    number of workers (16 is more than most hosts' cores), with threads
    switched as often as the interpreter allows."""
    monkeypatch.setattr(synthetic, "_INLINE_SAMPLES", sys.maxsize)
    want = _materialize()
    monkeypatch.undo()
    workers(n)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        got = _materialize()
    finally:
        sys.setswitchinterval(interval)
    assert got.keys() == want.keys()
    for key in want:
        assert got[key].dtype == want[key].dtype, key
        assert np.array_equal(got[key], want[key]), key


def test_seeds_give_different_media():
    a, b = _materialize(seed=11), _materialize(seed=12)
    real = a["media_mask"] > 0
    assert np.array_equal(real, b["media_mask"] > 0)
    assert np.mean(a["media_embeds"][real] == b["media_embeds"][real]) < 1e-4
    assert not np.array_equal(a["text_tokens"], b["text_tokens"])


def test_media_is_float32_normal_and_padding_is_zero():
    """Real slots: N(0, 0.02^2) over 1.19 M float32 samples.  The sample
    mean's standard error is 1.8e-5 and the std's relative one 6.5e-4:
    the tolerances are over 5 of each.  Padding: exact zeros."""
    batch = _materialize()
    media, mask = batch["media_embeds"], batch["media_mask"] > 0
    assert media.dtype == np.float32
    real = media[mask]
    assert real.size == 9300 * EMBED
    assert abs(real.mean()) < 1e-4
    assert real.std() == pytest.approx(0.02, rel=5e-3)
    # a normal puts 68.27% of its mass within one std (binomial error 4e-4)
    assert np.mean(np.abs(real) < 0.02) == pytest.approx(0.6827, abs=3e-3)
    assert not media[~mask].any()
    for i, it in enumerate(ITEMS):
        assert mask[i].sum() == min(it.n_media_items * TPM, MAX_MEDIA)
        assert mask[i, :mask[i].sum()].all()


def test_tokens_in_vocab_and_labels_shift_by_one():
    batch = _materialize()
    text, tmask, labels = (batch[k] for k in
                           ("text_tokens", "text_mask", "labels"))
    for i, it in enumerate(ITEMS):
        t = min(it.text_len, MAX_TEXT)
        assert tmask[i].sum() == t and tmask[i, :t].all()
        assert ((text[i, :t] >= 1) & (text[i, :t] < VOCAB)).all()
        assert not text[i, t:].any()
        assert np.array_equal(labels[i, :t - 1], text[i, 1:t])
        assert (labels[i, t - 1:] == -1).all()


def test_row_without_text_has_empty_span_and_no_labels():
    items = [DataItem(2, 0), DataItem(1, 6)]
    batch = _materialize(items)
    assert not batch["text_mask"][0].any()
    assert not batch["text_tokens"][0].any()
    assert (batch["labels"][0] == -1).all()
    assert batch["media_mask"][0].sum() == 2 * TPM
    assert batch["media_embeds"][0, :2 * TPM].any()
    assert batch["text_mask"][1].sum() == 6
