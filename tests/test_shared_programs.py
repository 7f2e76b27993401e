"""One QAT program per shape and configuration (``core.trainer``).

The data split and the trainer's base key are arguments of the row
program, so an evaluator built for another search seed finds the program
its predecessor traced, and answers as a freshly traced one would.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import chromosome, qat, spans, trainer
from repro.data import uci_synth

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (11, 12)
KINDS = ("population", "islands")

X, Y, SPEC = uci_synth.load("seeds")
MLP = qat.MLPConfig((SPEC.n_features, SPEC.hidden, SPEC.n_classes), adc_bits=4)


def _cfg(seed, **kw):
    return trainer.EvalConfig(**{"max_steps": 10, "step_scale": 0.05, "seed": seed, **kw})


def _split(seed):
    return uci_synth.stratified_split(X, Y, 0.7, seed)


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    masks = rng.uniform(size=(n, chromosome.n_mask_bits(SPEC.n_features, 4))) < 0.6
    cats = np.stack([rng.integers(0, c, n) for c in chromosome.cat_cardinalities(("adc",), 2)], 1)
    dec = chromosome.decode_batch(masks, cats, SPEC.n_features, 4)
    return (dec["masks"], dec["weight_bits"], dec["act_bits"], dec["batch_size"],
            dec["epochs"], dec["lr"], rng.integers(0, 2**31 - 1, n).astype(np.int32))


def _build(kind, seed, **kw):
    if kind == "population":
        return trainer.make_population_evaluator(*_split(seed), MLP, _cfg(seed, **kw))
    return trainer.make_island_evaluator(*_split(seed), MLP, _cfg(seed, **kw), num_islands=2)


def _accs(kind, ev, rows):
    """Accuracies of ``rows``; the island evaluator gets them as two
    islands of three and the rest."""
    if kind == "population":
        return np.asarray(ev(*rows))
    return np.concatenate(ev([tuple(r[:3] for r in rows), tuple(r[3:] for r in rows)]))


def _builds_and_reuses(log):
    return log.counters["trainer.program_builds"], log.counters["trainer.program_reuses"]


@pytest.mark.parametrize("kind", KINDS)
def test_an_evaluator_for_another_seed_reuses_the_program(kind):
    rows = _rows(6)
    logs = []
    for seed in SEEDS:
        with spans.recording() as log:
            _accs(kind, _build(kind, seed), rows)
        logs.append(log)
    assert _builds_and_reuses(logs[0]) == (1, 0)
    assert _builds_and_reuses(logs[1]) == (0, 1)


@pytest.mark.parametrize("kind", KINDS)
def test_a_reused_program_answers_as_a_freshly_traced_one(kind):
    rows = _rows(6)
    shared = {seed: _accs(kind, _build(kind, seed), rows) for seed in SEEDS}
    for seed in SEEDS:
        trainer.clear_programs()
        with spans.recording() as log:
            fresh = _accs(kind, _build(kind, seed), rows)
        assert _builds_and_reuses(log) == (1, 0)
        np.testing.assert_array_equal(shared[seed], fresh)
    # the split and the trainer seed reach the shared program
    assert not np.array_equal(shared[SEEDS[0]], shared[SEEDS[1]])


def test_island_and_population_evaluators_agree_on_shared_programs():
    rows = _rows(6, seed=1)
    for seed in SEEDS:
        pop = _accs("population", _build("population", seed), rows)
        np.testing.assert_array_equal(_accs("islands", _build("islands", seed), rows), pop)


def test_split_as_arguments_matches_the_split_closed_over():
    """The program with the split as constants, as an evaluator's own
    closure once traced it, gives the same bits."""
    seed, rows = SEEDS[0], _rows(6, seed=2)
    X_tr, y_tr, X_te, y_te = _split(seed)
    data = (jax.numpy.asarray(X_tr, jax.numpy.float32), jax.numpy.asarray(y_tr, jax.numpy.int32),
            jax.numpy.asarray(X_te, jax.numpy.float32), jax.numpy.asarray(y_te, jax.numpy.int32))
    train_one = trainer._make_train_one(MLP, _cfg(seed))
    closed = jax.jit(jax.vmap(lambda *row: train_one(*row, (*data, jax.random.PRNGKey(seed)))))
    np.testing.assert_array_equal(_accs("population", _build("population", seed), rows),
                                  np.asarray(closed(*rows)))


@pytest.mark.parametrize("seed", [0, 11, 2**31 - 1, 2**31 + 5, 2**32 + 7, -7])
def test_the_base_key_argument_is_the_traced_one(seed):
    key = trainer._split_args(*_split(0), seed)[-1]
    np.testing.assert_array_equal(np.asarray(key),
                                  np.asarray(jax.jit(lambda: jax.random.PRNGKey(seed))()))


def test_a_program_traced_at_default_precision_is_traced_again_at_highest():
    rows = _rows(4)
    ev = _build("population", SEEDS[0])

    def text():
        return ev.program.lower(*(ev.shard_fn(a) for a in rows)).as_text()

    with spans.recording() as default:
        ev(*rows)
    with jax.default_matmul_precision("highest"):
        with spans.recording() as highest:
            _build("population", SEEDS[1])(*rows)
        with spans.recording() as again:
            _build("population", SEEDS[0])(*rows)
        assert "HIGHEST" in text()
    assert "HIGHEST" not in text()
    assert _builds_and_reuses(default) == (1, 0)
    assert _builds_and_reuses(highest) == (1, 1)
    assert _builds_and_reuses(again) == (0, 1)


def test_rebuild_onto_the_same_mesh_finds_the_program():
    rows = _rows(4)
    ev = _build("population", SEEDS[0])
    acc = np.asarray(ev(*rows))
    with spans.recording() as log:
        again = np.asarray(ev.rebuild()(*rows))
    assert _builds_and_reuses(log) == (0, 1)
    np.testing.assert_array_equal(again, acc)


def test_rebuild_onto_another_mesh_traces_anew():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = textwrap.dedent("""
        import json, sys
        import numpy as np
        sys.path.insert(0, "tests")
        import test_shared_programs as t
        from repro.core import spans

        rows = t._rows(4)
        out = []
        ev = t._build("population", t.SEEDS[0])
        for n in (None, 1, 2):
            with spans.recording() as log:
                e = ev if n is None else ev.rebuild(n)
                acc = np.asarray(e(*rows))
            out.append([len(e.mesh.devices.ravel()), *t._builds_and_reuses(log), acc.tolist()])
        print(json.dumps(out))
    """)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    (n0, *first, acc0), (n1, *one, acc1), (n2, *two, acc2) = out
    assert (n0, n1, n2) == (2, 1, 2)
    assert first == [1, 0]
    assert one == [1, 0]   # a one-device mesh: another program, traced
    assert two == [0, 1]   # back on the first mesh: found
    assert acc0 == acc1 == acc2


def test_the_cache_keeps_its_bound_and_drops_the_least_recently_used():
    split = _split(SEEDS[0])
    for steps in range(1, trainer._PROGRAMS_MAX + 2):
        trainer.make_population_evaluator(*split, MLP, _cfg(SEEDS[0], max_steps=steps))
    assert len(trainer._programs) == trainer._PROGRAMS_MAX
    with spans.recording() as log:
        trainer.make_population_evaluator(*split, MLP, _cfg(SEEDS[1], max_steps=2))
        trainer.make_population_evaluator(*split, MLP, _cfg(SEEDS[1], max_steps=1))
    # max_steps 2 was still held; 1, the oldest, had gone and is built again
    assert log.counters["trainer.program_reuses"] == 1
    assert len(trainer._programs) == trainer._PROGRAMS_MAX
