"""The PR-9 evaluation pipeline: stage units + per-driver bit-for-bit.

Two load-bearing properties.  First, the stage primitives
(``core.evalpipe``) enforce the screen honesty contract: a screen may
only split the planned rows, never invent/drop/defer-a-must-train, and
commit writes in plan order whatever order the screen chose.  Second,
the regression the tentpole promised: with screening disabled — or with
a screen that defers nothing — every driver (blocking engine,
sequential/stacked/dispatched island schedules, eval-service) is
bit-for-bit the PR-8 search: same fronts, same memo insertion order,
same ``n_evaluations``/``n_memo_hits`` counters, and checkpoint
round-trips that include the deferred side table.
"""

import numpy as np
import pytest

from repro.core import eval_service, evalpipe, nsga2

N_BITS = 12
CATS = (3, 2)


def _objective(masks, cats):
    masks = np.asarray(masks, bool)
    bits = masks.sum(axis=1).astype(np.float64)
    cat0 = np.asarray(cats, np.int64)[:, 0].astype(np.float64)
    return np.stack([bits + cat0, masks.shape[1] - bits], axis=1)


def _dispatch(masks, cats):
    objs = _objective(masks, cats)
    return lambda: objs


def _stacked(batches):
    return [_objective(m, c) if np.shape(m)[0] else None for m, c in batches]


def _ga(seed=0, pop=8, gens=5, **kw):
    kw.setdefault("memoize", True)
    return nsga2.NSGA2Config(pop_size=pop, n_generations=gens, seed=seed, **kw)


def _passthrough_screen(ctx):
    """A screen that defers nothing — must be identical to screen=None."""
    return evalpipe.ScreenDecision(train=dict(ctx.unseen))


def _stub_screen(ctx):
    """Stateless deterministic deferring screen (no surrogate model).

    Defers every planned genome whose first key byte is even — except
    must_train keys and final generations, per the honesty contract.
    The predicted objective is a recognisable constant.
    """
    if ctx.final:
        return evalpipe.ScreenDecision(train=dict(ctx.unseen))
    train, deferred = {}, {}
    for k, i in ctx.unseen.items():
        if k in ctx.must_train or k[0] % 2:
            train[k] = i
        else:
            deferred[k] = np.array([99.0, 99.0])
    return evalpipe.ScreenDecision(train=train, deferred=deferred)


# ---------------------------------------------------------------------------
# stage primitive units
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_plan_rows_dedupes_table_claims_and_repeats():
    table = {b"a": np.zeros(2)}
    keys = [b"a", b"b", b"c", b"b", b"d"]
    assert evalpipe.plan_rows(table, keys) == {b"b": 1, b"c": 2, b"d": 4}
    assert evalpipe.plan_rows(table, keys, claimed={b"c"}) == {b"b": 1, b"d": 4}


@pytest.mark.ci
def test_gather_rows_prefers_table_over_fallback():
    table = {b"a": np.array([1.0, 1.0])}
    fb = {b"a": np.array([9.0, 9.0]), b"b": np.array([2.0, 2.0])}
    out = evalpipe.gather_rows([b"a", b"b"], table, fb)
    np.testing.assert_array_equal(out, [[1.0, 1.0], [2.0, 2.0]])
    with pytest.raises(KeyError):
        evalpipe.gather_rows([b"a", b"b"], table)  # no fallback: b missing


@pytest.mark.ci
def test_commit_rows_writes_in_plan_order_and_purges_deferred():
    table = {}
    deferred = {b"y": np.array([9.0])}
    evalpipe.commit_rows(
        table, {b"x": 0, b"y": 2}, np.array([[1.0], [2.0]]), deferred
    )
    assert list(table) == [b"x", b"y"]
    assert deferred == {}  # the exact result supersedes the prediction
    evalpipe.commit_rows(table, {}, None)  # empty plan is a no-op
    assert list(table) == [b"x", b"y"]


@pytest.mark.ci
def test_resolve_decision_enforces_partition():
    ctx = evalpipe.ScreenContext(
        masks=np.zeros((3, 2), bool), cats=np.zeros((3, 0), np.int64),
        keys=[b"a", b"b", b"c"], unseen={b"a": 0, b"b": 1, b"c": 2},
        memo={}, must_train=frozenset([b"a"]),
    )
    ok = evalpipe.ScreenDecision(
        train={b"c": 2, b"a": 0}, deferred={b"b": np.zeros(2)}
    )
    resolved = evalpipe.resolve_decision(ctx, ok)
    assert list(resolved.train) == [b"a", b"c"]  # re-ordered to pool order
    with pytest.raises(ValueError, match="outside the plan"):
        evalpipe.resolve_decision(
            ctx, evalpipe.ScreenDecision(train={b"a": 0, b"b": 1, b"c": 2, b"z": 9})
        )
    with pytest.raises(ValueError, match="both trains and defers"):
        evalpipe.resolve_decision(
            ctx,
            evalpipe.ScreenDecision(
                train={b"a": 0, b"b": 1, b"c": 2}, deferred={b"b": np.zeros(2)}
            ),
        )
    with pytest.raises(ValueError, match="drops"):
        evalpipe.resolve_decision(
            ctx, evalpipe.ScreenDecision(train={b"a": 0, b"b": 1})
        )
    with pytest.raises(ValueError, match="must_train"):
        evalpipe.resolve_decision(
            ctx,
            evalpipe.ScreenDecision(
                train={b"b": 1, b"c": 2}, deferred={b"a": np.zeros(2)}
            ),
        )


@pytest.mark.ci
def test_pool_plan_first_seen_and_take():
    plan = evalpipe.PoolPlan(
        keys=[b"a", b"b", b"c"], train={b"a": 0, b"c": 2}, deferred={b"b": 1}
    )
    assert plan.first_seen == (b"a", b"c", b"b")
    masks = (np.arange(6) % 2 == 0).reshape(3, 2)
    cats = np.arange(3, dtype=np.int64).reshape(3, 1)
    m, c = plan.take(masks, cats)
    np.testing.assert_array_equal(m, masks[[0, 2]])
    np.testing.assert_array_equal(c, cats[[0, 2]])


# ---------------------------------------------------------------------------
# bit-for-bit: a defer-nothing screen IS the unscreened engine, per driver
# ---------------------------------------------------------------------------

def _summary(engine, out):
    return (
        out["objs"].tolist(),
        list(engine.memo),
        engine.n_evaluations,
        engine.n_memo_hits,
        engine.n_deferred,
    )


@pytest.mark.ci
def test_blocking_engine_passthrough_screen_is_bit_for_bit():
    ref_eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga())
    ref = _summary(ref_eng, ref_eng.run())
    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(), screen=_passthrough_screen)
    got = _summary(eng, eng.run())
    assert got == ref


@pytest.mark.ci
@pytest.mark.parametrize("driver", ["sequential", "stacked", "async"])
def test_island_drivers_passthrough_screen_is_bit_for_bit(driver):
    def build(screen):
        icfg = nsga2.IslandConfig(
            num_islands=3, migration_interval=2,
            stacked=(driver == "stacked"),
            async_pipeline=(driver == "async"),
        )
        return nsga2.IslandNSGA2(
            N_BITS, CATS, _objective, _ga(), icfg,
            stacked_evaluate=_stacked if driver == "stacked" else None,
            dispatch_evaluate=_dispatch if driver == "async" else None,
            screen=screen,
        )

    ref_d = build(None)
    ref = _summary(ref_d, ref_d.run())
    got_d = build(_passthrough_screen)
    got = _summary(got_d, got_d.run())
    assert got == ref


@pytest.mark.ci
def test_service_passthrough_screen_is_bit_for_bit():
    def run(screen_factory):
        svc = eval_service.EvalService(
            _stacked, N_BITS, CATS,
            cfg=eval_service.ServiceConfig(wave_slots=2, coalesce_s=0.01),
            screen_factory=screen_factory,
        )
        with svc:
            svc.submit(eval_service.SearchRequest(request_id="r", ga=_ga()))
            res = svc.result("r")
        assert res.ok
        return (
            res.result["objs"].tolist(), res.n_evaluations,
            res.n_memo_hits, res.n_deferred,
        )

    assert run(lambda: _passthrough_screen) == run(None)


# ---------------------------------------------------------------------------
# deferring screens: honesty + state round-trips
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_deferred_final_front_is_exact():
    """The reported front must be exact rows even when rows were deferred."""
    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(), screen=_stub_screen)
    out = eng.run()
    assert eng.n_deferred > 0  # the stub actually deferred something
    front_masks = out["masks"]
    front_cats = out["cats"]
    np.testing.assert_array_equal(out["objs"], _objective(front_masks, front_cats))
    # no surviving front row carries the 99.0 stub prediction
    assert not (out["all_objs"] == 99.0).any()


@pytest.mark.ci
def test_deferred_rows_train_when_next_planned():
    """A deferred key is must_train at its next plan (prediction replaced)."""
    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(gens=4), screen=_stub_screen)
    eng.run()
    # post-final-generation every planned key was trained: side table only
    # holds keys never planned again, and none of them are in the memo
    assert all(k not in eng.memo for k in eng._deferred)
    for k, v in eng.memo.items():
        assert not np.array_equal(v, [99.0, 99.0])


@pytest.mark.ci
def test_screened_counters_conserve_rows():
    """evals + hits + deferred == rows presented, exactly, per generation."""
    presented = []
    real_eval = _objective

    def counting_eval(m, c):
        return real_eval(m, c)

    eng = nsga2.NSGA2(N_BITS, CATS, counting_eval, _ga(), screen=_stub_screen)
    plan_pool = eng.plan_pool

    def counting_plan(masks, cats, claimed=None):
        presented.append(masks.shape[0])
        return plan_pool(masks, cats, claimed)

    eng.plan_pool = counting_plan
    eng.run()
    assert eng.n_evaluations + eng.n_memo_hits + eng.n_deferred == sum(presented)


@pytest.mark.ci
def test_deferred_checkpoint_round_trip_bit_for_bit():
    """Interrupt/resume mid-campaign with a live deferred table."""
    ref = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(gens=6), screen=_stub_screen)
    ref_out = ref.run()

    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(gens=6), screen=_stub_screen)
    state = {}

    def hook(engine, gens_done):
        if gens_done == 3:
            state["st"] = engine.state_dict()

    eng.run(checkpoint_hook=hook)
    assert state["st"]["arrays"].get("deferred_keys") is not None

    resumed = nsga2.NSGA2(
        N_BITS, CATS, _objective, _ga(gens=6), screen=_stub_screen
    )
    resumed.set_state(state["st"])
    out = resumed.run()
    assert out["objs"].tolist() == ref_out["objs"].tolist()
    assert list(resumed.memo) == list(ref.memo)
    assert resumed.n_deferred == ref.n_deferred
    assert sorted(resumed._deferred) == sorted(ref._deferred)


@pytest.mark.ci
def test_island_shared_deferred_table_counts_cross_island_hit():
    """Island B planning a key island A deferred answers from the side
    table (a memo-hit-like gather), never re-screens or re-trains it."""
    icfg = nsga2.IslandConfig(num_islands=2, migration_interval=2)
    drv = nsga2.IslandNSGA2(
        N_BITS, CATS, _objective, _ga(gens=5), icfg, screen=_stub_screen
    )
    out = drv.run()
    assert out["n_deferred"] == drv.n_deferred
    # the side table is one shared dict aliased across islands
    assert all(isl._deferred is drv._deferred for isl in drv.islands)
    # deferred predictions never leak into the shared exact memo
    for v in drv.memo.values():
        assert not np.array_equal(v, [99.0, 99.0])


@pytest.mark.ci
def test_service_screened_request_flags_deferred_rows():
    svc = eval_service.EvalService(
        _stacked, N_BITS, CATS,
        cfg=eval_service.ServiceConfig(wave_slots=2, coalesce_s=0.01),
        screen_factory=lambda: _stub_screen,
    )
    with svc:
        svc.submit(eval_service.SearchRequest(request_id="r", ga=_ga(gens=5)))
        res = svc.result("r")
    assert res.ok
    assert res.n_deferred > 0
    # service memo stays exact-rows-only
    for v in svc.shared._table.values():
        assert not np.array_equal(v, [99.0, 99.0])


@pytest.mark.ci
def test_screen_requires_memoize():
    with pytest.raises(ValueError, match="memoize"):
        nsga2.NSGA2(
            N_BITS, CATS, _objective, _ga(memoize=False),
            screen=_passthrough_screen,
        )
    with pytest.raises(ValueError, match="memoize"):
        nsga2.IslandNSGA2(
            N_BITS, CATS, _objective, _ga(memoize=False),
            nsga2.IslandConfig(num_islands=2), screen=_passthrough_screen,
        )


# ---------------------------------------------------------------------------
# gradient/GA hybrid x surrogate screen (PR 10)
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_score_pool_rows_are_must_train_past_the_screen():
    """Warm-start rows must be exact: even under a deferring screen,
    ``score_pool`` force-trains every unseen row — nothing is answered
    with a surrogate prediction, and the deferred counter stays zero."""
    rng = np.random.default_rng(2)
    # keys with even first bytes — exactly the rows _stub_screen defers
    masks = rng.uniform(size=(6, N_BITS)) < 0.5
    masks[:, :8] = False  # first key byte even for every row
    cats = np.zeros((6, len(CATS)), np.int64)
    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(), screen=_stub_screen)
    objs = eng.score_pool(masks, cats)
    np.testing.assert_array_equal(objs, _objective(masks, cats))
    assert eng.n_deferred == 0
    assert eng.n_evaluations == len(set(nsga2.genome_keys(masks, cats)))
    for v in eng.memo.values():
        assert not np.array_equal(v, [99.0, 99.0])  # no prediction leaked


@pytest.mark.ci
def test_hybrid_hooks_at_defaults_with_screen_are_bit_for_bit():
    """Screen on, hybrid knobs at defaults: bit-for-bit the PR-9 search."""
    ref_eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(), screen=_stub_screen)
    ref = _summary(ref_eng, ref_eng.run())
    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(), screen=_stub_screen)
    eng.set_refiner(lambda m, c: (m.copy(), c.copy()), every=0)
    assert _summary(eng, eng.run()) == ref


@pytest.mark.ci
def test_warm_rows_then_screened_run_keeps_screen_honesty():
    """A warm-seeded screened search: warm rows stay exact memo entries,
    the screen still defers only its own plannable rows, and the final
    front is exact-objectives-only."""
    rng = np.random.default_rng(4)
    wm = rng.uniform(size=(4, N_BITS)) < 0.5
    wc = np.zeros((4, len(CATS)), np.int64)
    eng = nsga2.NSGA2(N_BITS, CATS, _objective, _ga(gens=4), screen=_stub_screen)
    eng.score_pool(wm, wc)
    eng.seed_warm(wm, wc)
    out = eng.run()
    assert eng.n_deferred > 0  # the screen still worked
    for v in eng.memo.values():
        assert not np.array_equal(v, [99.0, 99.0])
    np.testing.assert_array_equal(out["objs"], _objective(out["masks"], out["cats"]))


# ---------------------------------------------------------------------------
# the dedupe walk exists only in the pipeline module
# ---------------------------------------------------------------------------

@pytest.mark.ci
def test_no_driver_reimplements_the_memo_halves():
    """grep-level acceptance: the inline plan walk lives in evalpipe only."""
    import pathlib

    root = pathlib.Path(nsga2.__file__).parent
    offenders = []
    for py in root.rglob("*.py"):
        if py.name == "evalpipe.py":
            continue
        text = py.read_text()
        if "k not in unseen" in text or "not in table and" in text:
            offenders.append(py.name)
    assert not offenders, f"inline plan/dedupe walk outside evalpipe: {offenders}"
