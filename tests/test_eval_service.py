"""Concurrency suite for the co-design evaluation service.

The load-bearing property is the bit-for-bit coalescing argument of
``core.eval_service``: a search submitted concurrently with other
requests yields a Pareto front, memo insertion order, and eval/hit
counters IDENTICAL to running it alone against the same starting memo —
cross-request sharing lives strictly below the engine, in the wave
scheduler and shared table.  The suite proves that analytically (fast,
ci-marked) and against the real QAT evaluator (tier-1), plus the failure
modes around it: the two-thread memo-lock hammer (counter conservation),
cross-request dedupe training a twice-born genome exactly once, a
request dying mid-wave leaving every other request's memo view intact,
admission queueing/rejection, deadlines, and shared-memo persistence.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import eval_service, memo_store, nsga2
from repro.runtime import admission as admission_rt
from repro.runtime import failure as failure_rt

N_BITS = 12
CATS = (3, 2)


def _objective(masks, cats):
    """Analytic 2-objective stand-in: pure function of the genome."""
    masks = np.asarray(masks, bool)
    bits = masks.sum(axis=1).astype(np.float64)
    cat0 = np.asarray(cats, np.int64)[:, 0].astype(np.float64)
    return np.stack([bits + cat0, masks.shape[1] - bits], axis=1)


def _stacked(batches):
    """Island-evaluator contract over the analytic objective."""
    return [
        _objective(m, c) if np.shape(m)[0] else None for m, c in batches
    ]


def _slow_stacked(delay_s):
    """A stacked evaluate slow enough to force real thread overlap."""

    def f(batches):
        time.sleep(delay_s)
        return _stacked(batches)

    return f


def _ga(seed=0, pop=6, gens=4, **kw):
    return nsga2.NSGA2Config(
        pop_size=pop, n_generations=gens, seed=seed, **kw
    )


def _service(stacked=_stacked, **cfg_kw):
    cfg_kw.setdefault("wave_slots", 3)
    cfg_kw.setdefault("coalesce_s", 0.02)
    return eval_service.EvalService(
        stacked, N_BITS, CATS, cfg=eval_service.ServiceConfig(**cfg_kw)
    )


def _solo(seed, memo=None, pop=6, gens=4):
    """Reference: the same search run alone against ``memo``."""
    eng = nsga2.NSGA2(
        N_BITS, CATS, _objective, _ga(seed, pop, gens), memo=memo
    )
    return eng, eng.run()


def _key_to_genome(key: bytes):
    """Invert ``nsga2.genome_keys`` for one key (test-side check)."""
    masks = np.frombuffer(key[:N_BITS], np.uint8).astype(bool)[None]
    cats = np.frombuffer(key[N_BITS:], np.int64).reshape(1, len(CATS))
    return masks, cats


def _assert_result_matches_solo(res, solo_engine, solo_out):
    """The full bit-for-bit identity: front, memo order, counters."""
    assert res.ok, res.error
    np.testing.assert_array_equal(res.result["objs"], solo_out["objs"])
    np.testing.assert_array_equal(res.result["masks"], solo_out["masks"])
    np.testing.assert_array_equal(res.result["cats"], solo_out["cats"])
    assert res.memo_keys == list(solo_engine.memo)
    assert res.n_evaluations == solo_out["n_evaluations"]
    assert res.n_memo_hits == solo_out["n_memo_hits"]
    assert [r["n_evals"] for r in res.result["history"]] == [
        r["n_evals"] for r in solo_out["history"]
    ]


# ---------------------------------------------------------------------------
# Bit-for-bit coalescing (the acceptance property).
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_concurrent_searches_equal_each_run_alone_warm_memo():
    """Two coalesced searches == each run alone with the same warm memo."""
    warm_engine, _ = _solo(seed=5)
    warm = dict(warm_engine.memo)
    solos = {s: _solo(s, memo=warm) for s in (1, 2)}
    with _service(stacked=_slow_stacked(0.002)) as svc:
        results = svc.run_all(
            [
                eval_service.SearchRequest("a", ga=_ga(1), memo=warm),
                eval_service.SearchRequest("b", ga=_ga(2), memo=warm),
            ]
        )
        stats = svc.stats()
    for res, seed in zip(results, (1, 2)):
        _assert_result_matches_solo(res, *solos[seed])
    # the waves really did carry more than one request at least once
    assert stats["waves"]["n_waves"] >= 1
    assert stats["shared_memo"]["rows_requested"] > 0


@pytest.mark.ci
def test_second_identical_request_costs_zero_device_rows():
    """A solved question re-asked is answered entirely from the table."""
    with _service() as svc:
        svc.submit(eval_service.SearchRequest("first", ga=_ga(3)))
        first = svc.result("first")
        trained_after_first = svc.stats()["shared_memo"]["trained"]
        svc.submit(eval_service.SearchRequest("again", ga=_ga(3)))
        again = svc.result("again")
        stats = svc.stats()
    assert first.ok and again.ok
    np.testing.assert_array_equal(
        again.result["objs"], first.result["objs"]
    )
    # the rerun was admitted with a snapshot of the now-complete table,
    # so its engine answered every pool row from its local memo without
    # dispatching a single wave...
    rows = 6 + 2 * 6 * 4  # setup pool + per-generation pools (_ga defaults)
    assert first.n_evaluations + first.n_memo_hits == rows
    assert again.n_evaluations == 0
    assert again.n_memo_hits == rows
    # ...and the device trained nothing new, service-wide
    assert stats["shared_memo"]["trained"] == trained_after_first


@pytest.mark.ci
def test_cross_request_dedupe_trains_twice_born_genome_once():
    """Unique genomes across all requests == rows that reached the device."""
    seeds = (7, 7, 8)  # two identical searches + one distinct
    with _service(stacked=_slow_stacked(0.002)) as svc:
        results = svc.run_all(
            [
                eval_service.SearchRequest(f"r{i}", ga=_ga(s))
                for i, s in enumerate(seeds)
            ]
        )
        stats = svc.stats()
    assert all(r.ok for r in results)
    unique = set()
    for r in results:
        unique.update(r.memo_keys)
    sm = stats["shared_memo"]
    # every unique genome trained exactly once, service-wide — rows born
    # in two requests were answered by one device row (in-wave coalesce
    # or table hit, depending on how the waves happened to form)
    assert sm["trained"] == len(unique) == sm["entries"]
    assert sm["hits"] + sm["coalesced"] == sm["rows_requested"] - sm["trained"]
    assert sm["hits"] + sm["coalesced"] > 0  # sharing actually happened


# ---------------------------------------------------------------------------
# Failure isolation (reuses runtime.failure.FailureInjector).
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_request_death_mid_wave_leaves_other_views_intact():
    """A request dying mid-campaign corrupts nothing outside itself."""
    solo_engine, solo_out = _solo(seed=1)
    with _service(stacked=_slow_stacked(0.005)) as svc:
        svc.submit(
            eval_service.SearchRequest(
                "victim", ga=_ga(2),
                injector=failure_rt.FailureInjector(crash_at_step=1),
            )
        )
        svc.submit(eval_service.SearchRequest("survivor", ga=_ga(1)))
        victim = svc.result("victim")
        survivor = svc.result("survivor")
        # the service keeps serving after a request death
        svc.submit(eval_service.SearchRequest("after", ga=_ga(1)))
        after = svc.result("after")
        snapshot = svc.shared.snapshot()
        stats = svc.stats()
    assert isinstance(victim.error, failure_rt.DeviceLossError)
    # the survivor is bit-for-bit the solo run: the victim's death moved
    # nothing in anyone else's engine-local memo view
    _assert_result_matches_solo(survivor, solo_engine, solo_out)
    assert after.ok
    np.testing.assert_array_equal(after.result["objs"], solo_out["objs"])
    # the shared table holds only settled pure-function rows — including
    # whatever the victim's completed waves committed before it died
    for key, val in snapshot.items():
        np.testing.assert_array_equal(val, _objective(*_key_to_genome(key))[0])
    assert stats["admission"]["n_admitted"] == 3
    assert stats["admission"]["active"] == 0  # the dead request released


# ---------------------------------------------------------------------------
# Thread-safe shared memo (the plan/commit lock) — regression hammer.
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_memo_lock_hammer_counter_conservation():
    """Two engines, one aliased memo, two threads: counters conserve.

    Regression for the shared-memo race: plan/commit halves now run under
    one lock (shared by every engine aliasing the dict, the IslandNSGA2
    arrangement), so hammering the same memo from two request threads
    must preserve ``n_evaluations + n_memo_hits == rows submitted`` per
    engine and never corrupt an entry.  Identical seeds maximise key
    collisions; the slow objective forces real interleaving.
    """
    lock = threading.RLock()
    shared_memo: dict = {}

    def slow_objective(masks, cats):
        time.sleep(0.002)
        return _objective(masks, cats)

    pop, gens = 8, 5
    engines = []
    for _ in range(2):
        eng = nsga2.NSGA2(
            N_BITS, CATS, slow_objective, _ga(0, pop, gens),
            memo_lock=lock,
        )
        eng._memo = shared_memo  # alias ONE dict, ONE lock (island idiom)
        engines.append(eng)
    errors: list[BaseException] = []

    def drive(eng):
        try:
            eng.run()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=drive, args=(e,)) for e in engines]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    rows_requested = pop + 2 * pop * gens  # setup pool + per-gen pools
    for eng in engines:
        assert eng.n_evaluations + eng.n_memo_hits == rows_requested
    # no entry was torn by concurrent writes: every cached vector is the
    # pure objective of its genome key
    for key, val in shared_memo.items():
        np.testing.assert_array_equal(val, _objective(*_key_to_genome(key))[0])


# ---------------------------------------------------------------------------
# Wave scheduler unit behaviour (deterministic, no thread races).
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_wave_coalesces_and_dedupes_deterministically():
    """Two overlapping batches queued before start form ONE deduped wave."""
    shared = eval_service.SharedMemo()
    calls: list[list[int]] = []

    def observing_stacked(batches):
        calls.append([int(np.shape(m)[0]) for m, _ in batches])
        return _stacked(batches)

    sched = eval_service.WaveScheduler(
        observing_stacked, shared, wave_slots=2, coalesce_s=0.05
    )
    masks = np.zeros((8, N_BITS), bool)
    for i in range(8):
        masks[i, : i + 1] = True  # 8 distinct genomes
    cats = np.zeros((8, len(CATS)), np.int64)
    resolve_a = sched.submit(masks[:4], cats[:4])
    resolve_b = sched.submit(masks[2:], cats[2:])  # rows 2,3 overlap
    with sched:
        objs_a = resolve_a()
        objs_b = resolve_b()
    np.testing.assert_array_equal(objs_a, _objective(masks[:4], cats[:4]))
    np.testing.assert_array_equal(objs_b, _objective(masks[2:], cats[2:]))
    assert calls == [[4, 4]]  # one wave: 4 owned by a, 6-2 owned by b
    assert shared.n_rows_requested == 10
    assert shared.n_trained == 8
    assert shared.n_coalesced == 2
    assert len(shared) == 8


@pytest.mark.ci
def test_wave_failure_fails_its_requests_not_the_service():
    """A raising stacked program errors the wave's resolves; later waves run."""
    shared = eval_service.SharedMemo()
    fail_next = {"flag": True}

    def flaky(batches):
        if fail_next["flag"]:
            fail_next["flag"] = False
            raise failure_rt.DeviceLossError("wave lost")
        return _stacked(batches)

    masks = np.eye(4, N_BITS, dtype=bool)
    cats = np.zeros((4, len(CATS)), np.int64)
    with eval_service.WaveScheduler(
        flaky, shared, wave_slots=2, coalesce_s=0.01
    ) as sched:
        bad = sched.submit(masks[:2], cats[:2])
        with pytest.raises(failure_rt.DeviceLossError):
            bad()
        good = sched.submit(masks[2:], cats[2:])
        np.testing.assert_array_equal(
            good(), _objective(masks[2:], cats[2:])
        )
    # the failed wave committed nothing
    assert len(shared) == 2
    assert shared.n_trained == 2


# ---------------------------------------------------------------------------
# Admission + deadlines (runtime.admission).
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_admission_bounds_concurrency_without_changing_results():
    """max_active=1 serialises the searches; results stay bit-for-bit."""
    solos = {s: _solo(s) for s in (1, 2, 3)}
    with _service(
        stacked=_slow_stacked(0.002),
        admission=admission_rt.AdmissionConfig(max_active=1),
    ) as svc:
        results = svc.run_all(
            [eval_service.SearchRequest(f"r{s}", ga=_ga(s)) for s in (1, 2, 3)]
        )
        stats = svc.stats()
    for res, seed in zip(results, (1, 2, 3)):
        assert res.ok, res.error
        np.testing.assert_array_equal(
            res.result["objs"], solos[seed][1]["objs"]
        )
    assert stats["admission"]["peak_active"] == 1
    assert stats["admission"]["peak_queued"] >= 1
    assert stats["waves"]["mean_occupancy"] == 1.0  # serialised = solo waves


@pytest.mark.ci
def test_admission_rejects_on_queue_overflow():
    ctrl = admission_rt.AdmissionController(
        admission_rt.AdmissionConfig(max_active=1, max_queue=0)
    )
    ctrl.admit("first")
    with pytest.raises(admission_rt.AdmissionError):
        ctrl.admit("second")
    ctrl.release()
    assert ctrl.stats()["n_rejected"] == 1
    ctrl.admit("third")  # slot free again
    ctrl.release()


@pytest.mark.ci
def test_admission_is_fifo_under_contention():
    """Waiters are admitted in strict submission order."""
    ctrl = admission_rt.AdmissionController(
        admission_rt.AdmissionConfig(max_active=1, max_queue=8)
    )
    order: list[int] = []
    ctrl.admit("holder")
    started = []

    def waiter(i):
        started.append(i)
        ctrl.admit(f"w{i}")
        order.append(i)
        ctrl.release()

    threads = []
    for i in range(4):
        t = threading.Thread(target=waiter, args=(i,))
        threads.append(t)
        t.start()
        while i not in started:  # enqueue strictly one at a time
            time.sleep(0.001)
        while ctrl.queued < i + 1:
            time.sleep(0.001)
    ctrl.release()
    for t in threads:
        t.join()
    assert order == [0, 1, 2, 3]


@pytest.mark.ci
def test_request_watchdog_with_fake_clock():
    now = {"t": 0.0}
    wd = admission_rt.RequestWatchdog(deadline_s=10.0, clock=lambda: now["t"])
    wd.start("a")
    now["t"] = 5.0
    wd.start("b")
    assert wd.expired() == []
    assert wd.remaining("a") == 5.0
    now["t"] = 11.0
    assert wd.expired() == ["a"]
    assert wd.finish("a") == 11.0
    assert wd.expired() == []  # finished requests stop being tracked
    now["t"] = 16.0
    assert wd.expired() == ["b"]


@pytest.mark.ci
def test_service_reports_deadline_exceeded():
    """An overdue request surfaces as a deadline error, not a hang."""
    with _service(
        stacked=_slow_stacked(0.05),
        admission=admission_rt.AdmissionConfig(deadline_s=0.01),
    ) as svc:
        svc.submit(eval_service.SearchRequest("slow", ga=_ga(1)))
        res = svc.result("slow", timeout=0.02)
        assert isinstance(res.error, TimeoutError)
        assert "deadline" in str(res.error)
        # close() still waits for the thread — the search finishes in the
        # background and its true result stays retrievable
    final = svc.result("slow")
    assert final.ok


# ---------------------------------------------------------------------------
# Shared-memo persistence (core.memo_store integration).
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_shared_memo_persists_and_reloads(tmp_path):
    path = str(tmp_path / "memo")
    fp = {"dataset": "analytic", "v": 1}
    svc = eval_service.EvalService(
        _stacked, N_BITS, CATS,
        cfg=eval_service.ServiceConfig(
            wave_slots=3, coalesce_s=0.02, memo_path=path, persist_every_s=0.0
        ),
        fingerprint=fp,
    )
    with svc:
        svc.submit(eval_service.SearchRequest("warmup", ga=_ga(4)))
        res = svc.result("warmup")
        mid_run_saves = svc.stats()["shared_memo"]["n_saves"]
    assert res.ok
    assert mid_run_saves >= 1  # periodic persistence fired while serving
    assert memo_store.memo_path_exists(path)
    # a new service instance starts warm: the same search costs zero rows
    svc2 = eval_service.EvalService(
        _stacked, N_BITS, CATS,
        cfg=eval_service.ServiceConfig(
            wave_slots=3, coalesce_s=0.02, memo_path=path
        ),
        fingerprint=fp,
    )
    assert len(svc2.shared) == len(res.memo_keys)
    with svc2:
        svc2.submit(eval_service.SearchRequest("rerun", ga=_ga(4)))
        rerun = svc2.result("rerun")
        stats2 = svc2.stats()
    assert rerun.ok
    np.testing.assert_array_equal(rerun.result["objs"], res.result["objs"])
    assert stats2["shared_memo"]["trained"] == 0  # fully table-served
    # a service with a different fingerprint refuses the stored memo
    with pytest.raises(ValueError, match="refusing to reuse"):
        eval_service.EvalService(
            _stacked, N_BITS, CATS,
            cfg=eval_service.ServiceConfig(memo_path=path),
            fingerprint={"dataset": "other", "v": 2},
        )


# ---------------------------------------------------------------------------
# Real-QAT acceptance test (tier-1): coalescing correctness on the actual
# objective, via the stacked island evaluator.
# ---------------------------------------------------------------------------


def test_service_backend_answers_as_the_search_memo(tmp_path):
    """``make_service_backend``'s objectives are the ones a search with the
    same config stores in its memo, so the two memos are interchangeable."""
    from repro.core import codesign

    cfg = codesign.CodesignConfig(
        dataset="seeds", pop_size=4, n_generations=2, step_scale=0.1,
        max_steps=30, memo_path=str(tmp_path / "memo"),
    )
    codesign.run_codesign(cfg)
    memo = memo_store.load_memo(cfg.memo_path, cfg.memo_fingerprint())
    backend = codesign.make_service_backend(cfg, wave_slots=2)
    n_mask = backend["n_mask_bits"]
    keys = list(memo)
    masks = np.stack([np.frombuffer(k[:n_mask], np.uint8) for k in keys]).astype(bool)
    cats = np.stack([np.frombuffer(k[n_mask:], np.int64) for k in keys])
    half = len(keys) // 2
    got = backend["stacked_evaluate"](
        [(masks[:half], cats[:half]), (masks[half:], cats[half:])]
    )
    assert len(keys) > 2
    np.testing.assert_array_equal(
        np.concatenate(got), np.stack([memo[k] for k in keys])
    )


def test_concurrent_qat_search_equals_solo_real_evaluator():
    """Tier-1 acceptance: concurrent == alone on the real QAT objective."""
    from repro.core import codesign

    cd_cfg = codesign.CodesignConfig(
        dataset="seeds", pop_size=4, n_generations=2,
        step_scale=0.1, max_steps=30,
    )
    backend = codesign.make_service_backend(cd_cfg, wave_slots=2)
    slots = 2

    def row_evaluate(masks, cats):
        empty = (
            np.zeros((0, backend["n_mask_bits"]), bool),
            np.zeros((0, len(backend["cat_cardinalities"])), np.int64),
        )
        return backend["stacked_evaluate"](
            [(masks, cats)] + [empty] * (slots - 1)
        )[0]

    ga = nsga2.NSGA2Config(
        pop_size=cd_cfg.pop_size, n_generations=cd_cfg.n_generations,
        seed=cd_cfg.seed,
    )
    solo_engine = nsga2.NSGA2(
        backend["n_mask_bits"], backend["cat_cardinalities"],
        row_evaluate, ga, memo={},
    )
    solo_out = solo_engine.run()

    svc = eval_service.EvalService(
        backend["stacked_evaluate"], backend["n_mask_bits"],
        backend["cat_cardinalities"],
        cfg=eval_service.ServiceConfig(wave_slots=slots, coalesce_s=0.05),
        fingerprint=backend["fingerprint"],
    )
    other_ga = nsga2.NSGA2Config(
        pop_size=cd_cfg.pop_size, n_generations=cd_cfg.n_generations, seed=11,
    )
    with svc:
        results = svc.run_all(
            [
                eval_service.SearchRequest("main", ga=ga, memo={}),
                eval_service.SearchRequest("other", ga=other_ga, memo={}),
            ]
        )
        stats = svc.stats()
    _assert_result_matches_solo(results[0], solo_engine, solo_out)
    assert results[1].ok
    assert stats["shared_memo"]["trained"] >= 1


@pytest.mark.ci
def test_codesign_serve_exits_nonzero_when_a_request_fails(monkeypatch):
    """The serve CLI reports failed requests and exits non-zero."""
    from repro.core import codesign
    from repro.launch import codesign_serve

    def broken(batches):
        raise failure_rt.DeviceLossError("wave lost")

    def backend(cfg, wave_slots=4):
        return {
            "stacked_evaluate": broken, "fingerprint": {},
            "n_mask_bits": N_BITS, "cat_cardinalities": CATS,
            "screen_factory": None,
        }

    monkeypatch.setattr(codesign, "make_service_backend", backend)
    monkeypatch.setattr(codesign_serve, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as exc:
        codesign_serve.main(
            ["--requests", "2", "--pop", "4", "--gens", "1", "--slots", "2"]
        )
    assert "2 of 2 requests failed" in str(exc.value.code)
