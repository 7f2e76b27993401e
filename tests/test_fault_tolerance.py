"""Fault-tolerant campaigns: snapshots, elastic recovery, chaos drills.

Three layers of coverage:

* ``@pytest.mark.ci`` analytic tests drive the GA state machinery
  (``NSGA2.state_dict``/``set_state``, the ``ElasticGARunner`` recovery
  loop) with a closed-form objective — no training, finishes in seconds.
  The invariant throughout: an interrupted-and-recovered search is
  bit-for-bit the uninterrupted one (front, histories, counters, memo
  contents AND insertion order), and recovery replays only the rows the
  crash actually lost.
* a subprocess test (8 fake host devices) checks the elastic re-mesh
  actually moves the evaluators onto the surviving device subset.
* ``@pytest.mark.chaos`` tests run the same drills through the real QAT
  trainer via ``core.codesign`` — a device-group kill and a host-process
  kill mid-campaign — and account for replayed QAT rows exactly.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import codesign, memo_store, nsga2
from repro.runtime import elastic, failure, straggler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# analytic harness: closed-form objectives, no training
# ---------------------------------------------------------------------------


def _bitcount_eval(masks, cats):
    """Two smooth objectives with a real trade-off, pure in the genome."""
    h = masks.shape[1] // 2
    return np.stack(
        [masks[:, :h].mean(axis=1), 1.0 - masks[:, h:].mean(axis=1)], axis=1
    )


def _engine(evaluate=_bitcount_eval, **kw):
    cfg = nsga2.NSGA2Config(pop_size=6, n_generations=6, seed=3, **kw)
    return nsga2.NSGA2(24, (), evaluate, cfg)


def _stacked_bitcount(batches):
    return [_bitcount_eval(m, c) if m.shape[0] else None for m, c in batches]


def _deferred_bitcount(masks, cats):
    m, c = masks.copy(), cats.copy()
    return lambda: _bitcount_eval(m, c)


def _island_driver(evaluate=_bitcount_eval, schedule="sequential"):
    cfg = nsga2.NSGA2Config(pop_size=5, n_generations=5, seed=1)
    icfg = nsga2.IslandConfig(
        num_islands=3, migration_interval=2, migration_size=1,
        stacked=schedule == "stacked", async_pipeline=schedule == "dispatched",
    )
    return nsga2.IslandNSGA2(
        20, (), evaluate, cfg, icfg,
        stacked_evaluate=_stacked_bitcount if schedule == "stacked" else None,
        dispatch_evaluate=_deferred_bitcount if schedule == "dispatched" else None,
    )


def _assert_same_front(out, ref):
    np.testing.assert_array_equal(out["masks"], ref["masks"])
    np.testing.assert_array_equal(out["cats"], ref["cats"])
    np.testing.assert_array_equal(out["objs"], ref["objs"])


def _assert_same_result(out, ref):
    _assert_same_front(out, ref)
    assert out["n_evaluations"] == ref["n_evaluations"]
    assert out["n_memo_hits"] == ref["n_memo_hits"]


# -- snapshot / restore ------------------------------------------------------


@pytest.mark.ci
def test_nsga2_snapshot_roundtrip_is_bit_for_bit():
    ref_engine = _engine()
    ref = ref_engine.run()

    src = _engine()
    src.setup()
    for _ in range(3):
        src.step()
    snap = src.state_dict()
    # meta travels through the checkpoint manifest: it must survive JSON
    meta = json.loads(json.dumps(snap["meta"]))

    dst = _engine()
    dst.set_state({"arrays": snap["arrays"], "meta": meta})
    out = dst.run()

    _assert_same_result(out, ref)
    trace = [(r["gen"], r["front_size"], r["n_evals"]) for r in out["history"]]
    ref_trace = [(r["gen"], r["front_size"], r["n_evals"]) for r in ref["history"]]
    assert trace == ref_trace
    # memo contents AND insertion order survive the round trip
    assert list(dst.memo) == list(ref_engine.memo)
    for k in dst.memo:
        np.testing.assert_array_equal(dst.memo[k], ref_engine.memo[k])


@pytest.mark.ci
def test_hybrid_engine_snapshot_roundtrip_is_bit_for_bit():
    """A hybrid search (warm-seeded population + refinement operator)
    interrupted at a generation boundary and restored into a fresh
    engine — with the same refiner re-attached, exactly as
    ``codesign._run_elastic`` re-wires it on resume — finishes
    bit-for-bit identical to the uninterrupted hybrid run."""

    def refine(masks, cats):
        # deterministic, host-RNG-free: flip the lowest kept bit
        out = np.asarray(masks, bool).copy()
        out[:, 1] = ~out[:, 1]
        return out, np.asarray(cats, np.int64).copy()

    warm = np.zeros((3, 24), bool)
    warm[0, :8] = True
    warm[1, 8:16] = True
    warm[2, 16:] = True
    wc = np.zeros((3, 0), np.int64)

    def hybrid_engine():
        eng = _engine()
        eng.score_pool(warm, wc)
        eng.seed_warm(warm, wc)
        eng.set_refiner(refine, every=2, top_k=2)
        return eng

    ref_engine = hybrid_engine()
    ref = ref_engine.run()

    src = hybrid_engine()
    src.setup()
    for _ in range(2):
        src.step()
    snap = src.state_dict()
    meta = json.loads(json.dumps(snap["meta"]))

    dst = _engine()
    # state restore happens BEFORE the run hook re-attaches the refiner
    # (the warm pass is skipped on resume: pop is already set)
    dst.set_state({"arrays": snap["arrays"], "meta": meta})
    dst.set_refiner(refine, every=2, top_k=2)
    out = dst.run()

    _assert_same_result(out, ref)
    assert list(dst.memo) == list(ref_engine.memo)
    for k in dst.memo:
        np.testing.assert_array_equal(dst.memo[k], ref_engine.memo[k])


@pytest.mark.ci
def test_pre_setup_snapshot_restores_a_blank_engine():
    blank = _engine().state_dict()
    dst = _engine()
    dst.set_state(json.loads(json.dumps({"arrays": {}, "meta": blank["meta"]})))
    assert dst.pop is None and dst.gens_done == 0
    _assert_same_result(dst.run(), _engine().run())


@pytest.mark.ci
def test_snapshot_refuses_mid_generation():
    eng = _engine()
    eng.setup()
    pool_masks, pool_cats = eng.step_begin()
    with pytest.raises(RuntimeError, match="generation boundaries"):
        eng.state_dict()
    eng.step_commit(_bitcount_eval(pool_masks, pool_cats), 0.0)
    eng.state_dict()  # legal again at the boundary


@pytest.mark.ci
def test_snapshot_rejects_wrong_search_config():
    src = _engine()
    src.setup()
    snap = src.state_dict()
    other = nsga2.NSGA2(16, (), _bitcount_eval, nsga2.NSGA2Config(pop_size=6))
    with pytest.raises(ValueError, match="mask bits"):
        other.set_state(snap)


# -- host-restart: durable checkpoint through the real CheckpointManager ----


def _check_island_restart(tmp_path, schedule):
    ref_driver = _island_driver(schedule=schedule)
    ref = ref_driver.run()

    mgr = CheckpointManager(str(tmp_path / "ck"), keep_n=2)
    interrupted = _island_driver(schedule=schedule)

    def hook(driver, gens_done):
        st = driver.state_dict()
        mgr.save(gens_done, st["arrays"], extra={"meta": st["meta"]})
        if gens_done == 2:
            raise failure.HostFailure("drill: host process died")

    with pytest.raises(failure.HostFailure):
        interrupted.run(checkpoint_hook=hook)
    mgr.wait()  # the boundary-2 write must be durable before the "restart"

    # fresh process: a brand-new driver restored from disk
    resumed = _island_driver(schedule=schedule)
    tree, manifest = mgr.restore()
    assert manifest["step"] == 2
    resumed.set_state({"arrays": tree, "meta": manifest["extra"]["meta"]})
    assert resumed.gens_done == 2
    out = resumed.run()
    mgr.close()

    _assert_same_result(out, ref)
    assert resumed.migrations == ref_driver.migrations
    assert list(resumed.memo) == list(ref_driver.memo)
    # generations 0..1 were NOT re-trained after the restore
    resumed_rows = sum(r["n_evals"] for r in resumed.agg_history[2:])
    assert out["n_evaluations"] == ref["n_evaluations"]
    assert resumed_rows == sum(r["n_evals"] for r in ref_driver.agg_history[2:])
    return out


@pytest.mark.ci
def test_island_checkpoint_restart_matches_uninterrupted(tmp_path):
    _check_island_restart(tmp_path, "sequential")


@pytest.mark.ci
@pytest.mark.parametrize("schedule", ["stacked", "dispatched"])
def test_island_checkpoint_restart_on_each_schedule(tmp_path, schedule):
    out = _check_island_restart(tmp_path, schedule)
    # and the resumed search is the sequential schedule's, bit for bit
    _assert_same_result(out, _island_driver().run())


# -- device loss: in-process rollback + memo-backed replay -------------------


@pytest.mark.ci
def test_device_loss_replays_only_the_lost_batch():
    counted = {"rows": 0}

    def counting_eval(masks, cats):
        counted["rows"] += masks.shape[0]
        return _bitcount_eval(masks, cats)

    ref_driver = _island_driver(counting_eval)
    ref = ref_driver.run()
    ref_rows = counted["rows"]
    assert ref_rows == ref["n_evaluations"]

    state = {"calls": 0, "rows": 0, "lost_rows": None}
    crash_at = 7  # a mid-campaign batch; one crash only

    def chaos_eval(masks, cats):
        call, state["calls"] = state["calls"], state["calls"] + 1
        state["rows"] += masks.shape[0]  # counted BEFORE the batch "trains"
        if call == crash_at and state["lost_rows"] is None:
            state["lost_rows"] = masks.shape[0]
            raise failure.DeviceLossError("drill: device group lost mid-batch")
        return _bitcount_eval(masks, cats)

    driver = _island_driver(chaos_eval)
    rebuilt = []
    runner = elastic.ElasticGARunner(
        driver=driver,
        run_fn=lambda hook: driver.run(checkpoint_hook=hook),
        rebuild=rebuilt.append,
        probe=lambda: 2,
    )
    out = runner.run()

    _assert_same_front(out, ref)
    assert driver.migrations == ref_driver.migrations
    assert list(driver.memo) == list(ref_driver.memo)
    # the keep-memo rollback shifts counters (rows committed after the
    # boundary replay as memo hits, not evaluations) but conserves the sum
    assert (
        out["n_evaluations"] + out["n_memo_hits"]
        == ref["n_evaluations"] + ref["n_memo_hits"]
    )
    # everything committed before the crash replays as a memo hit: the only
    # re-dispatched rows are the interrupted batch's own
    assert state["lost_rows"] is not None, "the drill never fired"
    assert state["rows"] == ref_rows + state["lost_rows"]
    # and the evaluators were rebuilt on the probed survivor count
    assert rebuilt == [2]
    assert [r["reason"] for r in runner.recoveries] == ["device-loss"]
    assert runner.recoveries[0]["n_devices"] == 2


@pytest.mark.ci
def test_repeated_random_device_loss_still_bit_for_bit():
    ref_driver = _island_driver()
    ref = ref_driver.run()

    injector = failure.FailureInjector(seed=5, crash_rate=0.15, crash_mode="device")
    state = {"calls": 0}

    def chaos_eval(masks, cats):
        injector.maybe_fail(state["calls"])
        state["calls"] += 1
        return _bitcount_eval(masks, cats)

    driver = _island_driver(chaos_eval)
    runner = elastic.ElasticGARunner(
        driver=driver,
        run_fn=lambda hook: driver.run(checkpoint_hook=hook),
        max_recoveries=100,
    )
    out = runner.run()

    _assert_same_front(out, ref)
    assert driver.migrations == ref_driver.migrations
    assert list(driver.memo) == list(ref_driver.memo)
    assert (
        out["n_evaluations"] + out["n_memo_hits"]
        == ref["n_evaluations"] + ref["n_memo_hits"]
    )
    assert runner.recoveries, "crash_rate=0.15 never fired — drill is inert"


@pytest.mark.ci
def test_max_recoveries_reraises():
    def always_dies(masks, cats):
        raise failure.DeviceLossError("drill: permanent failure")

    driver = _island_driver(always_dies)
    runner = elastic.ElasticGARunner(
        driver=driver,
        run_fn=lambda hook: driver.run(checkpoint_hook=hook),
        max_recoveries=2,
    )
    with pytest.raises(failure.DeviceLossError):
        runner.run()
    assert len(runner.recoveries) == 2


# -- straggler eviction at the boundary --------------------------------------


@pytest.mark.ci
def test_straggler_evict_remeshes_without_rollback():
    wd = straggler.StragglerWatchdog(evict_after=1, readmit_after=50)
    for s in range(12):
        wd.observe(s, 0.1)

    driver = _island_driver()
    driver.run()
    gens = driver.gens_done
    driver.agg_history.append({"gen": gens, "gen_s": 9.9})  # one glacial gen

    rebuilt, saved = [], []
    runner = elastic.ElasticGARunner(
        driver=driver,
        run_fn=lambda hook: driver.run(checkpoint_hook=hook),
        rebuild=rebuilt.append,
        probe=lambda: 4,
        watchdog=wd,
        checkpoint_cb=lambda d, g, urgent: saved.append((g, urgent)),
    )
    runner._on_boundary(driver, gens)

    # eviction re-meshes (no rollback: the driver's state is untouched)...
    assert rebuilt == [4]
    assert [r["reason"] for r in runner.recoveries] == ["straggler-evict"]
    assert driver.gens_done == gens
    # ...and the straggler event makes the boundary checkpoint urgent
    assert saved == [(gens, True)]


# ---------------------------------------------------------------------------
# re-meshed evaluator placement (subprocess: 8 host devices)
# ---------------------------------------------------------------------------


def _run_subprocess(code: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_rebuild_places_evaluators_on_surviving_devices():
    _run_subprocess("""
    import jax
    from repro.core import qat, trainer
    from repro.data import uci_synth
    from repro.parallel import sharding as shd

    assert jax.device_count() == 8
    X, y, spec = uci_synth.load("seeds")
    X_tr, y_tr, X_te, y_te = uci_synth.stratified_split(X, y, 0.7, 0)
    mlp_cfg = qat.MLPConfig(
        layer_sizes=(spec.n_features, spec.hidden, spec.n_classes), adc_bits=4
    )
    eval_cfg = trainer.EvalConfig(max_steps=5, step_scale=0.1, seed=0)

    ev = trainer.make_population_evaluator(X_tr, y_tr, X_te, y_te, mlp_cfg, eval_cfg)
    assert ev.mesh.devices.size == 8

    # two device groups "die": the rebuilt evaluator lives on the first 6
    ev6 = ev.rebuild(6)
    assert ev6.mesh.devices.size == 6
    assert list(ev6.mesh.devices.ravel()) == jax.devices()[:6]

    # stacked island evaluator: same contract on the (island, data) mesh
    isl = trainer.make_island_evaluator(
        X_tr, y_tr, X_te, y_te, mlp_cfg, eval_cfg, num_islands=2
    )
    assert isl.mesh.devices.size == 8
    isl4 = isl.rebuild(4)
    assert isl4.mesh.devices.size == 4
    assert list(isl4.mesh.devices.ravel()) == jax.devices()[:4]

    # the sharding layer accepts an explicit survivor subset, too
    assert shd.population_mesh(3).devices.size == 3
    print("REMESH-OK")
    """)


# ---------------------------------------------------------------------------
# chaos drills through the real QAT trainer (tier-1, `-m chaos` selectable)
# ---------------------------------------------------------------------------

_CHAOS_KW = dict(
    dataset="seeds", pop_size=4, n_generations=3, step_scale=0.1,
    max_steps=30, num_islands=2, migration_interval=1, migration_size=1,
)


def _assert_same_campaign(res, ref, memo_a, memo_b):
    np.testing.assert_array_equal(res.front_masks, ref.front_masks)
    np.testing.assert_array_equal(res.front_cats, ref.front_cats)
    np.testing.assert_array_equal(res.front_acc, ref.front_acc)
    assert res.migrations == ref.migrations
    m_a, m_b = memo_store.load_memo(memo_a), memo_store.load_memo(memo_b)
    assert list(m_a) == list(m_b), "memo key insertion order differs"
    for k in m_a:
        np.testing.assert_array_equal(m_a[k], m_b[k])


def _reference_campaign(tmp):
    ref_drill = elastic.DrillConfig()
    ref = codesign.run_codesign(codesign.CodesignConfig(
        **_CHAOS_KW, memo_path=os.path.join(tmp, "memo_ref"), drill=ref_drill,
    ))
    # sanity: with no injector the drill tap counts exactly the trained rows
    assert ref_drill.rows_dispatched == ref.n_evaluations
    return ref


@pytest.mark.chaos
def test_codesign_chaos_device_group_kill(tmp_path):
    tmp = str(tmp_path)
    ref = _reference_campaign(tmp)

    # kill a device group at batch ordinal 5 — island 1's generation-1 batch
    drill = elastic.DrillConfig(
        injector=failure.FailureInjector(crash_at_step=5, crash_mode="device"),
    )
    res = codesign.run_codesign(codesign.CodesignConfig(
        **_CHAOS_KW, memo_path=os.path.join(tmp, "memo_chaos"),
        checkpoint_dir=os.path.join(tmp, "ck"), drill=drill,
    ))

    _assert_same_campaign(
        res, ref, os.path.join(tmp, "memo_ref"), os.path.join(tmp, "memo_chaos")
    )
    assert [r["reason"] for r in res.recoveries] == ["device-loss"]
    # recovery replays exactly the lost island's unseen rows for the
    # interrupted generation — everything committed earlier is a memo hit
    lost_rows = ref.island_history[1][1]["n_evals"]
    assert drill.rows_dispatched == ref.n_evaluations + lost_rows


@pytest.mark.chaos
def test_codesign_chaos_host_restart(tmp_path):
    tmp = str(tmp_path)
    ref = _reference_campaign(tmp)

    # the host process dies at batch ordinal 4 — island 0's gen-1 batch
    drill_1 = elastic.DrillConfig(
        injector=failure.FailureInjector(crash_at_step=4, crash_mode="host"),
    )
    with pytest.raises(failure.HostFailure):
        codesign.run_codesign(codesign.CodesignConfig(
            **_CHAOS_KW, checkpoint_dir=os.path.join(tmp, "ck"), drill=drill_1,
        ))

    # "fresh process": resume from the durable checkpoint directory
    drill_2 = elastic.DrillConfig()
    res = codesign.run_codesign(codesign.CodesignConfig(
        **_CHAOS_KW, memo_path=os.path.join(tmp, "memo_res"),
        checkpoint_dir=os.path.join(tmp, "ck"), resume=True, drill=drill_2,
    ))

    _assert_same_campaign(
        res, ref, os.path.join(tmp, "memo_ref"), os.path.join(tmp, "memo_res")
    )
    # across both processes: reference rows + exactly the interrupted batch
    lost_rows = ref.island_history[0][1]["n_evals"]
    total = drill_1.rows_dispatched + drill_2.rows_dispatched
    assert total == ref.n_evaluations + lost_rows


@pytest.mark.chaos
def test_codesign_checkpointing_is_invisible_and_resume_is_a_noop(tmp_path):
    tmp = str(tmp_path)
    ref = _reference_campaign(tmp)

    # checkpointing alone must not perturb the search
    res = codesign.run_codesign(codesign.CodesignConfig(
        **_CHAOS_KW, memo_path=os.path.join(tmp, "memo_ck"),
        checkpoint_dir=os.path.join(tmp, "ck"),
    ))
    _assert_same_campaign(
        res, ref, os.path.join(tmp, "memo_ref"), os.path.join(tmp, "memo_ck")
    )
    assert res.n_evaluations == ref.n_evaluations

    # resuming a finished campaign restores the final state and trains nothing
    drill = elastic.DrillConfig()
    res2 = codesign.run_codesign(codesign.CodesignConfig(
        **_CHAOS_KW, checkpoint_dir=os.path.join(tmp, "ck"), resume=True,
        drill=drill,
    ))
    np.testing.assert_array_equal(res2.front_masks, ref.front_masks)
    np.testing.assert_array_equal(res2.front_acc, ref.front_acc)
    assert res2.n_evaluations == ref.n_evaluations  # counters carried over
    assert drill.rows_dispatched == 0  # zero new QAT rows
