"""Dispatched island schedule: bit-for-bit identity with the sequential one.

The fast tests (``ci`` marker) drive :class:`core.nsga2.NSGA2` /
:class:`core.nsga2.IslandNSGA2` with cheap analytic objectives and a
hand-rolled deferred ``dispatch_evaluate`` — no QAT training anywhere in
the marked subset.  The unmarked integration tests (tier-1 only) run the
real codesign search with ``async_pipeline=True`` against the synchronous
reference through the actual QAT trainer, and exercise the population
evaluator's ``.dispatch`` hook directly.
"""

import numpy as np
import pytest

from repro.core import nsga2


def _bitcount_eval(masks, cats):
    """Toy trade-off: obj0 = ones in first half, obj1 = zeros in second."""
    h = masks.shape[1] // 2
    return np.stack([masks[:, :h].mean(1), 1.0 - masks[:, h:].mean(1)], axis=1)


def _deferred_dispatch(log=None):
    """A dispatch_evaluate that defers evaluation into resolve().

    Mimics the JAX async-dispatch contract without a device: nothing is
    computed at dispatch time (so a resolve-before-dispatch ordering bug
    would surface as a stale/missing result), and ``log`` records the
    interleaving of dispatch and resolve events for the pipelining test.
    """

    def dispatch_evaluate(masks, cats):
        m, c = masks.copy(), cats.copy()
        if log is not None:
            log.append(("dispatch", m.shape[0]))

        def resolve():
            if log is not None:
                log.append(("resolve", m.shape[0]))
            return _bitcount_eval(m, c)

        return resolve

    return dispatch_evaluate


def _assert_same_search(out_a, out_b, ga_a, ga_b):
    np.testing.assert_array_equal(out_a["masks"], out_b["masks"])
    np.testing.assert_array_equal(out_a["cats"], out_b["cats"])
    np.testing.assert_array_equal(out_a["objs"], out_b["objs"])
    assert ga_a.n_evaluations == ga_b.n_evaluations
    assert ga_a.n_memo_hits == ga_b.n_memo_hits
    # memo: same keys, same insertion order, same objective vectors
    assert list(ga_a.memo) == list(ga_b.memo)
    for k in ga_a.memo:
        np.testing.assert_array_equal(ga_a.memo[k], ga_b.memo[k])
    assert [r["n_evals"] for r in out_a["history"]] == [
        r["n_evals"] for r in out_b["history"]
    ]


# ---------------------------------------------------------------------------
# one engine's dispatch half
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_dispatch_pool_defers_commit_until_resolve():
    """Memo writes and counters must move at resolve time, not dispatch."""
    cfg = nsga2.NSGA2Config(pop_size=6, n_generations=1, seed=0)
    ga = nsga2.NSGA2(16, (), _bitcount_eval, cfg)
    masks, cats = ga.setup_begin()
    resolve = ga.dispatch_pool(masks, cats, _deferred_dispatch())
    assert ga.n_evaluations == 0 and not ga.memo, "commit leaked into dispatch"
    allo = resolve()
    assert allo.shape == (masks.shape[0], 2)
    assert ga.n_evaluations == len(ga.memo) > 0


# ---------------------------------------------------------------------------
# island engine: async pipelined driver == sequential reference
# ---------------------------------------------------------------------------

def _island_pair(async_pipeline, dispatch_evaluate=None, **kw):
    cfg = nsga2.NSGA2Config(pop_size=kw.pop("pop_size", 8),
                            n_generations=kw.pop("n_generations", 6),
                            seed=kw.pop("seed", 2))
    icfg = nsga2.IslandConfig(
        num_islands=kw.pop("num_islands", 3), migration_interval=2,
        migration_size=2, async_pipeline=async_pipeline, **kw,
    )
    return nsga2.IslandNSGA2(
        20, (), _bitcount_eval, cfg, icfg, dispatch_evaluate=dispatch_evaluate
    )


@pytest.mark.ci
def test_async_driver_bit_for_bit_matches_sequential():
    """The acceptance invariant: async pipelined == sequential, bit for bit.

    Merged front (genomes AND objectives), evaluation/memo-hit counters,
    per-generation history, per-island histories, migrations, and the
    shared memo — contents and insertion order — must all be identical.
    """
    seq = _island_pair(async_pipeline=False)
    asy = _island_pair(async_pipeline=True, dispatch_evaluate=_deferred_dispatch())
    out_seq, out_asy = seq.run(), asy.run()
    _assert_same_search(out_seq, out_asy, seq, asy)
    for h_seq, h_asy in zip(out_seq["island_history"], out_asy["island_history"]):
        assert [r["n_evals"] for r in h_seq] == [r["n_evals"] for r in h_asy]
        assert [r["memo_hits"] for r in h_seq] == [r["memo_hits"] for r in h_asy]
    assert out_seq["migrations"] == out_asy["migrations"]


@pytest.mark.ci
def test_async_driver_eager_fallback_matches_sequential():
    """With no dispatch_evaluate the driver still runs, results unchanged."""
    seq = _island_pair(async_pipeline=False)
    asy = _island_pair(async_pipeline=True)  # eager fallback closure
    _assert_same_search(seq.run(), asy.run(), seq, asy)


@pytest.mark.ci
def test_async_driver_pipelines_dispatches_ahead_of_resolves():
    """All K dispatches of a wave must happen before the wave's resolves.

    This is the pipelining itself: island i+1's variation/planning (which
    precedes its dispatch) runs while island i's batch is notionally in
    flight.  Also pins cross-island dedupe: a wave's dispatched rows are
    exactly the engine-counted evaluations (claimed-set ownership, no
    genome dispatched twice).
    """
    log = []
    asy = _island_pair(
        async_pipeline=True, dispatch_evaluate=_deferred_dispatch(log),
        num_islands=3, n_generations=4,
    )
    asy.run()
    kinds = [k for k, _ in log]
    # group events into waves: each wave is a run of dispatches followed
    # by its run of resolves, one per island that had unseen rows
    i = 0
    waves = 0
    while i < len(kinds):
        n_d = 0
        while i < len(kinds) and kinds[i] == "dispatch":
            n_d += 1
            i += 1
        assert n_d >= 1, f"resolve before any dispatch at event {i}: {kinds}"
        n_r = 0
        while i < len(kinds) and kinds[i] == "resolve":
            n_r += 1
            i += 1
        assert n_r == n_d, "a wave's resolves must match its dispatches"
        waves += 1
    assert waves >= 2  # setup wave + at least one generation dispatched
    assert sum(n for k, n in log if k == "dispatch") == asy.n_evaluations


@pytest.mark.ci
def test_async_pipeline_requires_memoize():
    with pytest.raises(ValueError, match="memoize"):
        nsga2.IslandNSGA2(
            16, (), _bitcount_eval,
            nsga2.NSGA2Config(pop_size=4, memoize=False),
            nsga2.IslandConfig(num_islands=2, async_pipeline=True),
        )


@pytest.mark.ci
def test_async_pipeline_excludes_stacked():
    with pytest.raises(ValueError, match="mutually exclusive"):
        nsga2.IslandConfig(num_islands=2, stacked=True, async_pipeline=True)


# ---------------------------------------------------------------------------
# codesign integration (QAT training — tier-1 only, not in the ci subset)
# ---------------------------------------------------------------------------

def test_trainer_dispatch_matches_blocking_evaluate():
    """evaluate.dispatch: launch now, block in resolve, same accuracies."""
    from repro.core import qat, trainer
    from repro.data import uci_synth

    X, y, spec = uci_synth.load("seeds")
    Xtr, ytr, Xte, yte = uci_synth.stratified_split(X, y)
    cfg = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes))
    ev = trainer.make_population_evaluator(
        Xtr, ytr, Xte, yte, cfg, trainer.EvalConfig(max_steps=30, step_scale=0.1)
    )
    P = 3
    args = (
        np.ones((P, spec.n_features, 16), bool),
        np.full(P, 8.0, np.float32),
        np.full(P, 4.0, np.float32),
        np.full(P, 32, np.int32),
        np.full(P, 10, np.int32),
        np.full(P, 0.05, np.float32),
        np.arange(P, dtype=np.int32),
    )
    resolve = ev.dispatch(*args)
    acc_async = resolve()
    assert isinstance(acc_async, np.ndarray) and acc_async.shape == (P,)
    np.testing.assert_array_equal(acc_async, np.asarray(ev(*args)))


def test_codesign_async_pipeline_bit_for_bit_single_population():
    """Through the real QAT trainer: async == sync for num_islands=1."""
    from repro.core import codesign

    base = dict(
        dataset="seeds", pop_size=4, n_generations=2, step_scale=0.1,
        max_steps=30,
    )
    sync = codesign.run_codesign(codesign.CodesignConfig(**base))
    asyn = codesign.run_codesign(
        codesign.CodesignConfig(async_pipeline=True, **base)
    )
    np.testing.assert_array_equal(sync.front_masks, asyn.front_masks)
    np.testing.assert_array_equal(sync.front_cats, asyn.front_cats)
    np.testing.assert_array_equal(sync.front_acc, asyn.front_acc)
    np.testing.assert_array_equal(sync.front_area, asyn.front_area)
    assert sync.n_evaluations == asyn.n_evaluations
    assert sync.n_memo_hits == asyn.n_memo_hits


def test_codesign_async_pipeline_bit_for_bit_islands():
    """Through the real QAT trainer: async pipelined == sequential islands.

    The whole-system version of the analytic identity test above — the
    per-island batches launched via ``evaluate_acc.dispatch`` and
    resolved at commit time must reproduce the blocking per-island path
    exactly, including training accuracies, memo counters, and the
    per-generation history.
    """
    from repro.core import codesign

    base = dict(
        dataset="seeds", pop_size=4, n_generations=2, step_scale=0.1,
        max_steps=30, num_islands=2, migration_interval=1, migration_size=1,
    )
    seq = codesign.run_codesign(codesign.CodesignConfig(**base))
    asy = codesign.run_codesign(
        codesign.CodesignConfig(async_pipeline=True, **base)
    )
    np.testing.assert_array_equal(seq.front_masks, asy.front_masks)
    np.testing.assert_array_equal(seq.front_cats, asy.front_cats)
    np.testing.assert_array_equal(seq.front_acc, asy.front_acc)
    np.testing.assert_array_equal(seq.front_area, asy.front_area)
    assert seq.n_evaluations == asy.n_evaluations
    assert seq.n_memo_hits == asy.n_memo_hits
    assert [h["n_evals"] for h in seq.history] == [
        h["n_evals"] for h in asy.history
    ]
