"""Faults planted under the timed path make ``correct`` come out false.

Each test skips the harness's look for a chip, drives the rest of a run at
a CPU test's size on a copy of the benchmark's tree, and breaks the program
underneath: the training step leaves its state unchanged, each step's loss
is the mean over half of its minibatch (the program's own step, rewritten),
the evaluator answers only half of its batch of rows (the other half copies
it), every answer is altered where it is produced (each row gets another
row's), or the stacked islands' results are not exchanged (every island
gets island 0's).  A sound run on the same tree comes out correct."""

import inspect
import json
import textwrap

import jax
import numpy as np
import pytest

from bench import harness
from tiny import ISLANDS, add_cell, tree

CELLS = ["seeds.search", "seeds.wave1024", ISLANDS["name"]]


def _run(tmp_path, name, seed=2**31 + 3, seconds=0.1, reference_rows=None):
    root = tree(tmp_path)
    if name == ISLANDS["name"]:
        add_cell(root, ISLANDS, "seeds.search")
    if reference_rows:
        for f in (root / "bench" / "traffic").glob("*.json"):
            f.write_text(json.dumps({**json.loads(f.read_text()),
                                     "reference_rows": reference_rows}))
    cell = harness.load_cell(name, root)
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    return harness.run(cell, seed, seconds, False, dev, 0.0)


def _population(monkeypatch, transform):
    from repro.core import trainer

    make = trainer.make_population_evaluator

    def broken(*a, **k):
        ev = make(*a, **k)

        def evaluate(*rows):
            return transform(np.asarray(ev(*rows)))

        def dispatch(*rows):
            resolve = ev.dispatch(*rows)
            return lambda: transform(np.asarray(resolve()))

        evaluate.__dict__.update(ev.__dict__)
        evaluate.dispatch = dispatch
        return evaluate

    monkeypatch.setattr(trainer, "make_population_evaluator", broken)


def _islands(monkeypatch, transform):
    from repro.core import trainer

    make = trainer.make_island_evaluator

    def broken(*a, **k):
        ev = make(*a, **k)

        def evaluate(batches):
            return transform(ev(batches))

        evaluate.__dict__.update(ev.__dict__)
        return evaluate

    monkeypatch.setattr(trainer, "make_island_evaluator", broken)


def _half(acc):
    h = (len(acc) + 1) // 2
    return np.concatenate([acc[:h], acc[: len(acc) - h]])


def _other_row(acc):
    return np.roll(acc, 1) if len(acc) > 1 else 1.0 - acc


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tmp_path, name):
    assert _run(tmp_path, name)["correct"] is True


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged(tmp_path, monkeypatch, name):
    from repro.core import trainer

    make = trainer._make_train_one

    def frozen(*a, **k):
        train_one = make(*a, **k)
        return lambda mask, wb, ab, bs, ep, lr, seed, *x: train_one(
            mask, wb, ab, bs, ep, lr * 0.0, seed, *x)

    monkeypatch.setattr(trainer, "_make_train_one", frozen)
    assert _run(tmp_path, name)["correct"] is False


@pytest.mark.parametrize("name", CELLS)
def test_step_uses_half_its_minibatch(tmp_path, monkeypatch, name):
    from repro.core import trainer

    # the program's own row program, with its minibatch weights cut to the
    # first half of the row's batch: the loss is the mean over the rest
    old = "w = (jnp.arange(cfg.max_batch) < bs)"
    src = textwrap.dedent(inspect.getsource(trainer._make_train_one))
    assert src.count(old) == 1
    ns = dict(vars(trainer))
    exec(compile(src.replace(old, "w = (jnp.arange(cfg.max_batch) < (bs + 1) // 2)"),
                 trainer.__file__, "exec"), ns)
    monkeypatch.setattr(trainer, "_make_train_one", ns["_make_train_one"])
    # a tiny search trains a dozen low-lr rows: compare every pool seed's
    assert _run(tmp_path, name, seconds=8.0, reference_rows=64)["correct"] is False


@pytest.mark.parametrize("name", CELLS)
def test_half_the_rows_answered(tmp_path, monkeypatch, name):
    _population(monkeypatch, _half)
    _islands(monkeypatch, lambda outs: [_half(np.asarray(o)) for o in outs])
    assert _run(tmp_path, name)["correct"] is False


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered(tmp_path, monkeypatch, name):
    _population(monkeypatch, _other_row)
    _islands(monkeypatch, lambda outs: [_other_row(np.asarray(o)) for o in outs])
    assert _run(tmp_path, name)["correct"] is False


def test_exchange_between_chips_left_out(tmp_path, monkeypatch):
    def island0(outs):
        first = np.asarray(outs[0])
        return [np.resize(first, len(o)) if len(first) else np.asarray(o) for o in outs]

    _islands(monkeypatch, island0)
    assert _run(tmp_path, ISLANDS["name"])["correct"] is False
