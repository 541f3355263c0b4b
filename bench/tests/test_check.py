"""The check that decides ``correct``: sound runs pass, broken ones fail.

A tiny cell, defined by data files only, runs on the CPU through the whole
harness (set-up, driven steps, window, reference, check) with the chip check
skipped.  Each fault is planted underneath, in the program the harness
drives; the control puts the reference, computed with float8 products, in
the program's place.  The tiny cell's limits sit between its sound
readings and its control's, as the cells' limits do on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import train_1chip
from bench.tests import tiny

ref = train_1chip.reference_of(tiny.CONFIG)

SEED = 2 ** 33 + 17


def _run(tmp_path, seed=SEED):
    name = tiny.write_checkout(tmp_path)
    return bench_run.execute(tmp_path, name, seed=seed, seconds=0.5,
                             trace=False, require_chip=False)


def _wrap_step(monkeypatch, wrap):
    import repro.train.step as step_mod
    real = step_mod.make_train_step

    def broken(*a, **k):
        return wrap(real(*a, **k))

    monkeypatch.setattr(step_mod, "make_train_step", broken)


def _unchanged(f):
    def g(params, opt, batch, lr):
        return (params, opt) + (f(params, opt, batch, lr)[2],)
    return g


def _half_batch(f):
    def g(params, opt, batch, lr):
        rows = jax.tree.leaves(batch)[0].shape[1]
        return f(params, opt, jax.tree.map(lambda x: x[:, : rows // 2], batch),
                 lr)
    return g


def _answer_altered(f):
    """One leaf of the new weights moved twice as far."""
    def g(params, opt, batch, lr):
        new, o, met = f(params, opt, batch, lr)
        leaves, tree = jax.tree.flatten(new)
        old = jax.tree.leaves(params)
        leaves[0] = old[0] + 2.0 * (leaves[0] - old[0])
        return jax.tree.unflatten(tree, leaves), o, met
    return g


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("seed", [SEED, 12345])
def test_sound_run_is_correct(tmp_path, seed):
    line = _run(tmp_path, seed)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert list(line["checks"]) == ["loss_gap", "grad_gap", "update_gap"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(tmp_path, monkeypatch, fault):
    _wrap_step(monkeypatch, FAULTS[fault])
    line = _run(tmp_path)
    assert not line["correct"], line["checks"]


def test_control_is_caught(tmp_path, monkeypatch):
    """The reference in float8 in the program's place fails the check."""
    real = train_1chip.TrainRun.drive

    def control(self, log):
        real(self, log)
        return {**self.reference("fp8"), "failed": 0}

    monkeypatch.setattr(train_1chip.TrainRun, "drive", control)
    line = _run(tmp_path)
    assert not line["correct"], line["checks"]


def test_wrong_labels_are_caught(tmp_path, monkeypatch):
    """Labels that the program's ``materialize`` makes wrong (each text
    position labelled with its own token, not the next) fail the run."""
    from repro.data.synthetic import MixedDataset
    real = MixedDataset.materialize

    def unshifted(self, items, **kw):
        out = real(self, items, **kw)
        out["labels"] = np.where(out["text_mask"] > 0, out["text_tokens"], -1)
        return out

    monkeypatch.setattr(MixedDataset, "materialize", unshifted)
    line = _run(tmp_path)
    assert not line["correct"] and line["failed"] > 0


def test_reference_makes_its_own_labels():
    tokens = np.array([[5, 6, 7, 0, 0]])
    mask = np.array([[1, 1, 1, 0, 0]])
    assert ref.next_token_labels(tokens, mask).tolist() == [[6, 7, -1, -1, -1]]


def test_worst_leaf_floor_is_the_median():
    from bench.harness.check import worst_leaf
    want = np.array([1.0, 1.0, 1.0, 1e-6])
    got = np.array([1.0, 1.0, 1.0, 1e-3])     # tiny leaf off by 1000x
    gap, leaf = worst_leaf(got, want)
    assert gap == pytest.approx(1e-3 - 1e-6) and leaf == 3


def test_fp8_rounds_to_eight_bits():
    x = jnp.linspace(-3.0, 3.0, 1001)
    q = ref._fake_fp8(x)
    assert len(np.unique(np.asarray(q))) <= 256
    assert float(jnp.max(jnp.abs(q - x))) <= 3.0 * 2 ** -4
