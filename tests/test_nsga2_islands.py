"""Island-model NSGA-II: migration mechanics, shared memo, equivalences.

The fast tests (``ci`` marker) drive :class:`core.nsga2.IslandNSGA2` with
cheap analytic objectives — no QAT training loops anywhere in the marked
subset.  The one codesign integration test (unmarked, tier-1 only) runs a
two-island search on the smoke dataset end to end.
"""

import numpy as np
import pytest

from repro.core import nsga2


def _bitcount_eval(masks, cats):
    """Toy trade-off: obj0 = ones in first half, obj1 = zeros in second."""
    h = masks.shape[1] // 2
    return np.stack([masks[:, :h].mean(1), 1.0 - masks[:, h:].mean(1)], axis=1)


def _plant(island, masks, objs, dominator_row=0):
    """Overwrite an island's live population with a known state."""
    P = masks.shape[0]
    island.pop = nsga2.Genome(masks.copy(), np.zeros((P, 0), np.int64))
    island.objs = objs.astype(np.float64).copy()
    rank = np.ones(P, np.int64)
    rank[dominator_row] = 0
    island.rank = rank
    island.crowd = np.zeros(P)


def _unique_rows(rng, n, bits, tag):
    """n distinct genome rows, disjoint across tags (top bits encode tag)."""
    rows = np.zeros((n, bits), bool)
    for j in range(n):
        rows[j, j % (bits - 4)] = True
        rows[j, bits - 4 :] = [(tag >> b) & 1 for b in range(4)]
    return rows


# ---------------------------------------------------------------------------
# migration mechanics
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_ring_topology_delivers_migrants_to_correct_neighbor():
    """Island i's Pareto champion must land on island (i+1) % K only."""
    K, P, bits = 3, 5, 16
    drv = nsga2.IslandNSGA2(
        bits, (), _bitcount_eval,
        nsga2.NSGA2Config(pop_size=P, n_generations=2, seed=0),
        # migration_size=3 but each planted front has ONE member: the wave
        # log must record what was actually shipped, not the request
        nsga2.IslandConfig(num_islands=K, migration_interval=1, migration_size=3),
    )
    rng = np.random.default_rng(0)
    champions = []
    for i, isl in enumerate(drv.islands):
        masks = _unique_rows(rng, P, bits, tag=i + 1)
        objs = np.full((P, 2), 2.0)
        objs[0] = [0.0, 0.0]  # row 0 dominates: the emigrant
        _plant(isl, masks, objs)
        champions.append(nsga2.genome_keys(masks[:1], np.zeros((1, 0), np.int64))[0])

    drv._migrate(gen=0)

    for i in range(K):
        dst_keys = set(
            nsga2.genome_keys(drv.islands[(i + 1) % K].pop.masks,
                              drv.islands[(i + 1) % K].pop.cats)
        )
        far_keys = set(
            nsga2.genome_keys(drv.islands[(i + 2) % K].pop.masks,
                              drv.islands[(i + 2) % K].pop.cats)
        )
        assert champions[i] in dst_keys, f"island {i} champion missed its neighbor"
        assert champions[i] not in far_keys, f"island {i} champion over-travelled"
    assert drv.migrations[0]["accepted"] == [1] * K
    assert drv.migrations[0]["sent"] == [1] * K


@pytest.mark.ci
def test_migrants_dedupe_against_genome_keys():
    P, bits = 5, 16
    isl = nsga2.NSGA2(bits, (), _bitcount_eval,
                      nsga2.NSGA2Config(pop_size=P, seed=0))
    rng = np.random.default_rng(1)
    masks = _unique_rows(rng, P, bits, tag=1)
    objs = np.linspace(0.1, 0.9, P)[:, None] * np.ones((P, 2))
    _plant(isl, masks, objs)
    cats0 = np.zeros((2, 0), np.int64)

    # resident genomes bounce: nothing inserted, population untouched
    before = isl.pop.masks.copy()
    n = isl.immigrate(masks[:2].copy(), cats0, objs[:2].copy())
    assert n == 0
    np.testing.assert_array_equal(isl.pop.masks, before)

    # a genuinely new genome duplicated within one batch lands exactly once
    new = _unique_rows(rng, 1, bits, tag=7)
    batch = np.concatenate([new, new])
    n = isl.immigrate(batch, cats0, np.full((2, 2), 0.05))
    assert n == 1
    keys = nsga2.genome_keys(isl.pop.masks, isl.pop.cats)
    new_key = nsga2.genome_keys(new, np.zeros((1, 0), np.int64))[0]
    assert keys.count(new_key) == 1


@pytest.mark.ci
def test_immigrants_replace_worst_not_best():
    P, bits = 5, 16
    isl = nsga2.NSGA2(bits, (), _bitcount_eval,
                      nsga2.NSGA2Config(pop_size=P, seed=0))
    rng = np.random.default_rng(2)
    masks = _unique_rows(rng, P, bits, tag=3)
    # strictly ordered chain: row 0 best ... row P-1 worst
    objs = np.arange(P, dtype=np.float64)[:, None] * np.ones((P, 2))
    _plant(isl, masks, objs)
    isl.rank = np.arange(P, dtype=np.int64)  # chain fronts
    best_key = nsga2.genome_keys(masks[:1], np.zeros((1, 0), np.int64))[0]
    worst_key = nsga2.genome_keys(masks[P - 1 :], np.zeros((1, 0), np.int64))[0]

    mig = _unique_rows(rng, 1, bits, tag=9)
    assert isl.immigrate(mig, np.zeros((1, 0), np.int64), np.full((1, 2), 0.5)) == 1
    keys = set(nsga2.genome_keys(isl.pop.masks, isl.pop.cats))
    assert best_key in keys and worst_key not in keys


@pytest.mark.ci
def test_shared_memo_trains_migrated_genomes_zero_rows_on_arrival():
    rows_seen = []

    def counting_eval(masks, cats):
        rows_seen.append(masks.shape[0])
        return _bitcount_eval(masks, cats)

    drv = nsga2.IslandNSGA2(
        16, (), counting_eval,
        nsga2.NSGA2Config(pop_size=8, n_generations=4, seed=1),
        nsga2.IslandConfig(num_islands=2, migration_interval=1, migration_size=2),
    )
    # one global evaluation memo: every island aliases the same dict
    assert drv.islands[0].memo is drv.memo
    assert drv.islands[1].memo is drv.memo
    drv.run()
    assert drv.migrations, "migration must have happened"

    # any genome resident on island 0 — migrants included — is already in
    # the shared memo: re-submitting it to island 1 trains zero rows
    m, c = drv.islands[0].pop.masks[:4], drv.islands[0].pop.cats[:4]
    evals_before = drv.islands[1].n_evaluations
    hits_before = drv.islands[1].n_memo_hits
    drv.islands[1]._evaluate(m, c)
    assert drv.islands[1].n_evaluations == evals_before
    assert drv.islands[1].n_memo_hits == hits_before + 4


@pytest.mark.ci
def test_immigrate_clamps_oversized_migrant_batch():
    """A migrant batch larger than the island replaces at most pop_size rows.

    Regression: the victim slice was ``(pop_size,)`` but assigned from the
    full ``kept`` batch, so any immigrate() with more unique migrants than
    residents crashed with a broadcast shape error.
    """
    P, bits = 3, 16
    isl = nsga2.NSGA2(bits, (), _bitcount_eval,
                      nsga2.NSGA2Config(pop_size=P, seed=0))
    rng = np.random.default_rng(4)
    _plant(isl, _unique_rows(rng, P, bits, tag=1),
           np.linspace(0.2, 0.8, P)[:, None] * np.ones((P, 2)))

    migrants = _unique_rows(rng, P + 2, bits, tag=6)  # 5 migrants, 3 seats
    objs = np.linspace(0.01, 0.05, P + 2)[:, None] * np.ones((P + 2, 2))
    landed = isl.immigrate(migrants, np.zeros((P + 2, 0), np.int64), objs)
    assert landed == P
    assert isl.pop.masks.shape == (P, bits)
    # first-come priority: the clamped batch keeps its leading rows
    keys = set(nsga2.genome_keys(isl.pop.masks, isl.pop.cats))
    kept_keys = nsga2.genome_keys(migrants[:P], np.zeros((P, 0), np.int64))
    assert all(k in keys for k in kept_keys)


# ---------------------------------------------------------------------------
# engine equivalences + merged result
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_single_island_reproduces_single_population_bit_for_bit():
    cfg = nsga2.NSGA2Config(pop_size=14, n_generations=8, seed=5)
    single = nsga2.NSGA2(24, (), _bitcount_eval, cfg).run()
    one = nsga2.IslandNSGA2(
        24, (), _bitcount_eval, cfg, nsga2.IslandConfig(num_islands=1)
    ).run()
    np.testing.assert_array_equal(single["masks"], one["masks"])
    np.testing.assert_array_equal(single["cats"], one["cats"])
    np.testing.assert_array_equal(single["objs"], one["objs"])
    assert single["n_evaluations"] == one["n_evaluations"]
    assert one["migrations"] == []
    assert [h["n_evals"] for h in single["history"]] == [
        h["n_evals"] for h in one["history"]
    ]


@pytest.mark.ci
def test_merged_front_is_nondominated_and_deduplicated():
    drv = nsga2.IslandNSGA2(
        20, (), _bitcount_eval,
        nsga2.NSGA2Config(pop_size=8, n_generations=6, seed=2),
        nsga2.IslandConfig(num_islands=3, migration_interval=2, migration_size=2),
    )
    out = drv.run()
    objs = out["objs"]
    for i in range(objs.shape[0]):
        for j in range(objs.shape[0]):
            if i != j:
                assert not (
                    np.all(objs[i] <= objs[j]) and np.any(objs[i] < objs[j])
                ), "merged front contains a dominated point"
    keys = nsga2.genome_keys(out["masks"], out["cats"])
    assert len(keys) == len(set(keys)), "merged front contains duplicate genomes"
    # aggregated history sums island telemetry generation-wise
    assert len(out["history"]) == 6
    assert len(out["island_history"]) == 3
    for gen, rec in enumerate(out["history"]):
        assert rec["n_evals"] == sum(
            h[gen]["n_evals"] for h in out["island_history"]
        )


@pytest.mark.ci
def test_topology_none_runs_independent_islands():
    drv = nsga2.IslandNSGA2(
        16, (), _bitcount_eval,
        nsga2.NSGA2Config(pop_size=6, n_generations=4, seed=3),
        nsga2.IslandConfig(num_islands=2, migration_interval=1, topology="none"),
    )
    out = drv.run()
    assert out["migrations"] == []
    assert out["objs"].shape[0] >= 1


@pytest.mark.ci
def test_island_config_validation():
    with pytest.raises(ValueError):
        nsga2.IslandConfig(topology="torus")
    with pytest.raises(ValueError):
        nsga2.IslandConfig(num_islands=0)
    with pytest.raises(ValueError):
        nsga2.IslandConfig(migration_interval=0)


# ---------------------------------------------------------------------------
# stacked (K, P) lock-step driver
# ---------------------------------------------------------------------------

def _island_pair(stacked, evaluate=_bitcount_eval, stacked_evaluate=None, **kw):
    cfg = nsga2.NSGA2Config(pop_size=kw.pop("pop_size", 8),
                            n_generations=kw.pop("n_generations", 6),
                            seed=kw.pop("seed", 2))
    icfg = nsga2.IslandConfig(
        num_islands=kw.pop("num_islands", 3), migration_interval=2,
        migration_size=2, stacked=stacked, **kw,
    )
    return nsga2.IslandNSGA2(
        20, (), evaluate, cfg, icfg, stacked_evaluate=stacked_evaluate
    )


@pytest.mark.ci
def test_stacked_driver_bit_for_bit_matches_sequential():
    """The acceptance invariant: stacked == sequential, bit for bit.

    Merged front (genomes AND objectives), evaluation/memo-hit counters,
    per-generation history, per-island histories, and the shared memo —
    contents and insertion order — must all be identical.
    """
    seq = _island_pair(stacked=False)
    stk = _island_pair(stacked=True)
    out_seq, out_stk = seq.run(), stk.run()

    np.testing.assert_array_equal(out_seq["masks"], out_stk["masks"])
    np.testing.assert_array_equal(out_seq["cats"], out_stk["cats"])
    np.testing.assert_array_equal(out_seq["objs"], out_stk["objs"])
    assert out_seq["n_evaluations"] == out_stk["n_evaluations"]
    assert out_seq["n_memo_hits"] == out_stk["n_memo_hits"]
    # memo: same keys, same insertion order, same objective vectors
    assert list(seq.memo) == list(stk.memo)
    for k in seq.memo:
        np.testing.assert_array_equal(seq.memo[k], stk.memo[k])
    # telemetry: counters match generation-wise, per island and aggregated
    for h_seq, h_stk in zip(out_seq["island_history"], out_stk["island_history"]):
        assert [r["n_evals"] for r in h_seq] == [r["n_evals"] for r in h_stk]
        assert [r["memo_hits"] for r in h_seq] == [r["memo_hits"] for r in h_stk]
    assert [r["n_evals"] for r in out_seq["history"]] == [
        r["n_evals"] for r in out_stk["history"]
    ]
    assert out_seq["migrations"] == out_stk["migrations"]


@pytest.mark.ci
def test_stacked_driver_submits_one_cross_island_batch_per_generation():
    """ONE stacked submission per generation, deduped across islands.

    Every call must carry exactly K batches; no genome key may appear in
    two islands' batches of the same wave (the lower-indexed island owns
    it), nor may a key the memo already holds be re-submitted.
    """
    calls = []
    drv = None  # assigned below; the closure reads the live memo

    def recording(batches):
        keys = [
            nsga2.genome_keys(m, c) if m.shape[0] else [] for m, c in batches
        ]
        calls.append(keys)
        flat = [k for ks in keys for k in ks]
        assert len(flat) == len(set(flat)), "duplicate genome across islands"
        assert not any(k in drv.memo for k in flat), "memo entry re-submitted"
        return [
            _bitcount_eval(m, c) if m.shape[0] else None for m, c in batches
        ]

    K, gens = 3, 5
    drv = _island_pair(
        stacked=True, stacked_evaluate=recording,
        num_islands=K, n_generations=gens,
    )
    drv.run()
    # setup wave + one wave per generation, K batches each — generations
    # where every pool is a memo hit submit nothing and are not counted
    assert 1 <= len(calls) <= gens + 1
    assert all(len(keys) == K for keys in calls)
    submitted = sum(len(k) for keys in calls for k in keys)
    assert submitted == drv.n_evaluations


@pytest.mark.ci
def test_stacked_requires_memoize():
    with pytest.raises(ValueError, match="memoize"):
        nsga2.IslandNSGA2(
            16, (), _bitcount_eval,
            nsga2.NSGA2Config(pop_size=4, memoize=False),
            nsga2.IslandConfig(num_islands=2, stacked=True),
        )


# ---------------------------------------------------------------------------
# hypervolume helper
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_hypervolume_known_values():
    # single point: one rectangle
    assert nsga2.hypervolume_2d(np.array([[0.5, 0.5]]), (1.0, 1.0)) == pytest.approx(0.25)
    # staircase front: union of rectangles, dominated overlap not re-counted
    front = np.array([[0.2, 0.8], [0.5, 0.5], [0.8, 0.2]])
    expect = 0.8 * 0.2 + 0.5 * 0.3 + 0.2 * 0.3
    assert nsga2.hypervolume_2d(front, (1.0, 1.0)) == pytest.approx(expect)
    # points at or beyond the reference contribute nothing
    assert nsga2.hypervolume_2d(np.array([[1.0, 0.1], [2.0, 0.0]]), (1.0, 1.0)) == 0.0
    # a dominated point changes nothing
    with_dom = np.concatenate([front, [[0.6, 0.6]]])
    assert nsga2.hypervolume_2d(with_dom, (1.0, 1.0)) == pytest.approx(expect)


@pytest.mark.ci
def test_hypervolume_monotone_in_front_quality():
    better = nsga2.hypervolume_2d(np.array([[0.1, 0.1]]), (1.0, 1.0))
    worse = nsga2.hypervolume_2d(np.array([[0.4, 0.4]]), (1.0, 1.0))
    assert better > worse


# ---------------------------------------------------------------------------
# codesign integration (QAT training — tier-1 only, not in the ci subset)
# ---------------------------------------------------------------------------

def test_codesign_islands_smoke():
    from repro.core import codesign

    cfg = codesign.CodesignConfig(
        dataset="seeds", pop_size=4, n_generations=2, step_scale=0.1,
        max_steps=30, num_islands=2, migration_interval=1, migration_size=1,
    )
    res = codesign.run_codesign(cfg)
    assert res.front_acc.size >= 1
    assert res.island_history is not None and len(res.island_history) == 2
    assert res.migrations is not None and len(res.migrations) >= 1
    assert res.n_evaluations > 0
    # merged front is a real front: conventional area never exceeded
    assert (res.front_area <= res.conv_area + 1e-9).all()


def test_codesign_stacked_islands_bit_for_bit():
    """Through the real QAT trainer: stacked == sequential, bit for bit.

    This is the whole-system version of the analytic identity test above —
    ``trainer.make_island_evaluator`` (one (K, B) SPMD program per
    generation) must reproduce the per-island
    ``trainer.make_population_evaluator`` path exactly, including the
    training accuracies the objectives are built from.
    """
    from repro.core import codesign

    base = dict(
        dataset="seeds", pop_size=4, n_generations=2, step_scale=0.1,
        max_steps=30, num_islands=2, migration_interval=1, migration_size=1,
    )
    seq = codesign.run_codesign(codesign.CodesignConfig(**base))
    stk = codesign.run_codesign(
        codesign.CodesignConfig(stacked_islands=True, **base)
    )
    np.testing.assert_array_equal(seq.front_masks, stk.front_masks)
    np.testing.assert_array_equal(seq.front_cats, stk.front_cats)
    np.testing.assert_array_equal(seq.front_acc, stk.front_acc)
    np.testing.assert_array_equal(seq.front_area, stk.front_area)
    assert seq.n_evaluations == stk.n_evaluations
    assert seq.n_memo_hits == stk.n_memo_hits
    assert [h["n_evals"] for h in seq.history] == [
        h["n_evals"] for h in stk.history
    ]
