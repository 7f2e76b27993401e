"""The program's span recorder and counters (``core.spans``), where they sit in
the search and the evaluator, and the named scopes of the QAT program."""

import contextlib
import json
import re
import sys
import threading
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

from repro.core import chromosome, codesign, nsga2, qat, spans, trainer
from repro.data import uci_synth

ROOT = Path(__file__).resolve().parents[1]


def _names(log):
    return [s["name"] for s in log.spans]


def test_spans_nest_with_their_parent_per_thread():
    barrier = threading.Barrier(2)

    def worker():
        with spans.span("nsga2.generation"):
            barrier.wait(timeout=10)
            with spans.span("nsga2.variation"):
                barrier.wait(timeout=10)

    with spans.recording() as log:
        with spans.span("codesign.search"):
            t = threading.Thread(target=worker)
            t.start()
            with spans.span("codesign.setup"):
                barrier.wait(timeout=10)
                barrier.wait(timeout=10)
            t.join(timeout=10)
    assert not t.is_alive()
    recs = log.spans
    by = {s["name"]: i for i, s in enumerate(recs)}
    assert recs[by["codesign.search"]]["parent"] is None
    assert recs[by["codesign.setup"]]["parent"] == by["codesign.search"]
    # the worker's spans never nest under the caller's thread's spans
    assert recs[by["nsga2.generation"]]["parent"] is None
    assert recs[by["nsga2.variation"]]["parent"] == by["nsga2.generation"]
    assert recs[by["nsga2.generation"]]["thread"] != recs[by["codesign.search"]]["thread"]
    assert all(s["end"] >= s["start"] for s in recs)


def test_self_time_is_duration_less_children_and_dump(tmp_path):
    with spans.recording() as log:
        with spans.span("trainer.call", rows=3, bucket=4):
            with spans.span("trainer.input"):
                pass
            with spans.span("trainer.dispatch"):
                pass
        spans.count("trainer.rows", 3)
    recs, selfs = log.spans, log.self_times()
    assert selfs[1] == pytest.approx(recs[1]["dur"])
    assert selfs[0] == pytest.approx(recs[0]["dur"] - recs[1]["dur"] - recs[2]["dur"])
    assert recs[0]["attrs"] == {"rows": 3, "bucket": 4}
    path = tmp_path / "spans.jsonl"
    log.dump(path)
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [x["name"] for x in lines[:-1]] == ["trainer.call", "trainer.input", "trainer.dispatch"]
    assert lines[-1]["counters"]["trainer.rows"] == 3


def test_names_are_declared_once():
    with pytest.raises(ValueError):
        spans.span("nsga2.nothing")
    with pytest.raises(ValueError):
        spans.scope("nothing")
    with spans.recording():
        with pytest.raises(ValueError):
            spans.count("trainer.nothing", 1)
    # the trace reduction and the program read the same tuples
    assert len(set(spans.SPANS)) == len(spans.SPANS)
    assert len(set(spans.COUNTERS)) == len(spans.COUNTERS)


def test_recording_off_keeps_nothing_and_count_allocates_nothing():
    with spans.recording() as log:
        pass
    with spans.span("nsga2.generation") as s:
        pass
    assert s.dur >= 0.0 and s.parent is None
    spans.count("trainer.rows", 1)  # warm any lazy state
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            spans.count("trainer.rows", 5)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = [tracemalloc.Filter(True, spans.__file__)]
    grown = after.filter_traces(flt).compare_to(before.filter_traces(flt), "lineno")
    assert sum(d.size_diff for d in grown) <= 0
    assert log.spans == [] and not any(log.counters.values())
    assert not spans.is_recording()


def _analytic_ga(**kw):
    def evaluate(masks, cats):
        h = masks.shape[1] // 2
        return np.stack([masks[:, :h].mean(1), 1.0 - masks[:, h:].mean(1)], axis=1)

    return nsga2.NSGA2(16, (3, 4), evaluate,
                       nsga2.NSGA2Config(pop_size=12, n_generations=4, seed=5, **kw))


@pytest.mark.parametrize("driver", ["run"])
def test_history_times_are_the_generation_and_evaluate_spans(driver):
    ga = _analytic_ga()
    with spans.recording() as log:
        out = getattr(ga, driver)()
    recs = log.spans
    gens = [s for s in recs if s["name"] == "nsga2.generation"]
    evals = [s for s in recs if s["name"] == "nsga2.evaluate"]
    assert [round(s["dur"], 4) for s in gens] == [h["gen_s"] for h in out["history"]]
    assert [round(s["dur"], 4) for s in evals] == [h["eval_s"] for h in out["history"]]
    assert [s["attrs"]["gen"] for s in gens] == [h["gen"] for h in out["history"]]
    for i, g in enumerate(recs):
        if g["name"] in ("nsga2.variation", "nsga2.evaluate", "nsga2.select") and g["parent"]:
            assert recs[g["parent"]]["name"] in ("nsga2.generation", "nsga2.setup")
    assert _names(log).count("nsga2.setup") == 1
    assert _names(log).count("nsga2.plan") == 1 + len(gens)


@pytest.mark.parametrize("schedule", ["sequential", "stacked", "dispatched"])
def test_island_history_is_one_generation_span_per_island_and_wave_shares(schedule):
    def evaluate(masks, cats):
        h = masks.shape[1] // 2
        return np.stack([masks[:, :h].mean(1), 1.0 - masks[:, h:].mean(1)], axis=1)

    K, G = 3, 4
    drv = nsga2.IslandNSGA2(
        16, (3, 4), evaluate, nsga2.NSGA2Config(pop_size=8, n_generations=G, seed=5),
        nsga2.IslandConfig(num_islands=K, migration_interval=2,
                           stacked=schedule == "stacked",
                           async_pipeline=schedule == "dispatched"),
    )
    with spans.recording() as log:
        out = drv.run()
    gens = [s for s in log.spans if s["name"] == "nsga2.generation"]
    # every island's begin opens its span before the wave is evaluated
    assert [s["attrs"]["gen"] for s in gens] == [g for g in range(G) for _ in range(K)]
    assert _names(log).count("nsga2.setup") == 1
    for g, agg in enumerate(out["history"]):
        shares = [h[g]["gen_s"] for h in out["island_history"]]
        assert [h[g]["gen"] for h in out["island_history"]] == [g] * K
        assert len(set(shares)) == 1, shares
        assert agg["gen"] == g and agg["gen_s"] == round(sum(shares), 4)
        # each island's span lies inside the wave the shares add up to
        assert max(s["dur"] for s in gens[g * K:(g + 1) * K]) <= agg["gen_s"] + 1e-3


AXES = ("adc", "act", "wprec")
AXES_SCOPES = ("act", "wprec")  # only in programs whose genome has those axes


def _seeds_evaluator(max_steps=6, step_scale=0.02, axes=("adc",)):
    X, y, spec = uci_synth.load("seeds")
    data = uci_synth.stratified_split(X, y, 0.7, 0)
    mlp = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes), adc_bits=4)
    cfg = trainer.EvalConfig(max_steps=max_steps, step_scale=step_scale, genome_axes=axes)
    return trainer.make_population_evaluator(*data, mlp, cfg), spec, data, cfg


def _rows(spec, n, seed, axes=("adc",)):
    rng = np.random.default_rng(seed)
    masks = rng.uniform(size=(n, chromosome.n_mask_bits(spec.n_features, 4))) < 0.6
    cats = np.stack([rng.integers(0, c, n) for c in chromosome.cat_cardinalities(axes, 2)], 1)
    dec = chromosome.decode_batch(masks, cats, spec.n_features, 4, axes=axes)
    return (dec["masks"], dec["weight_bits"], dec["act_bits"], dec["batch_size"],
            dec["epochs"], dec["lr"], np.arange(n, dtype=np.int32),
            *codesign._extra_rows(dec))


def _program_text(axes=("adc",)):
    ev, spec, _, _ = _seeds_evaluator(axes=axes)
    rows = _rows(spec, 4, 0, axes)
    return ev.program.lower(*(ev.shard_fn(a) for a in rows)).compile().as_text()


def test_program_builds_tick_once_per_new_bucket():
    trainer.clear_programs()  # the evaluator's program is built here, not found
    ev, spec, _, _ = _seeds_evaluator()
    with spans.recording() as log:
        ev(*_rows(spec, 3, 0))  # bucket 4: built
        ev(*_rows(spec, 4, 1))  # bucket 4 again
        first = log.counters["trainer.program_builds"]
        ev(*_rows(spec, 6, 2))  # bucket 8: built
    assert first == 1
    assert log.counters["trainer.program_builds"] == 2
    calls = [s for s in log.spans if s["name"] == "trainer.call"]
    assert [(c["attrs"]["rows"], c["attrs"]["bucket"]) for c in calls] == [(3, 4), (4, 4), (6, 8)]
    assert log.counters["trainer.rows"] == 13 and log.counters["trainer.padded_rows"] == 3
    with spans.recording() as again:
        ev(*_rows(spec, 7, 3))
    assert again.counters["trainer.program_builds"] == 0


def test_row_step_counters_match_the_benchmark_work_function():
    sys.path.insert(0, str(ROOT))
    try:
        from bench import work
    finally:
        sys.path.remove(str(ROOT))
    ev, spec, data, cfg = _seeds_evaluator(max_steps=40, step_scale=0.05)
    rows = [_rows(spec, n, 10 + n) for n in (5, 8)]
    with spans.recording() as log:
        for r in rows:
            ev(*r)
    t = {"step_scale": cfg.step_scale, "max_steps": cfg.max_steps}
    useful = sum(float((work.useful_steps(r[3], r[4], len(data[1]), t) * r[3]).sum())
                 for r in rows)
    assert log.counters["trainer.useful_row_steps"] == useful
    assert log.counters["trainer.scanned_row_steps"] == (8 + 8) * 40 * cfg.max_batch


def test_island_evaluator_counts_its_stacked_rows():
    X, y, spec = uci_synth.load("seeds")
    data = uci_synth.stratified_split(X, y, 0.7, 0)
    mlp = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes), adc_bits=4)
    ev = trainer.make_island_evaluator(*data, mlp, trainer.EvalConfig(max_steps=6),
                                       num_islands=2)
    with spans.recording() as log:
        ev([_rows(spec, 3, 0), _rows(spec, 0, 1)])
    c = log.counters
    assert (c["trainer.rows"], c["trainer.padded_rows"], c["trainer.program_builds"]) == (3, 5, 1)
    assert c["trainer.scanned_row_steps"] == 2 * 4 * 6 * 128


def _in_scope(name, op_names):
    return any(re.search(rf"(^|[/(]){name}\)*/", o) for o in op_names)


def test_scopes_are_in_the_program_metadata():
    op_names = re.findall(r'op_name="([^"]*)"', _program_text())
    for name in spans.SCOPES:
        assert _in_scope(name, op_names) == (name not in AXES_SCOPES), name
    assert any("transpose(jvp(layer))" in o for o in op_names)  # backward ops keep the scope
    # the three-axis genome's program carries every scope, its own two too
    op_names = re.findall(r'op_name="([^"]*)"', _program_text(AXES))
    for name in spans.SCOPES:
        assert _in_scope(name, op_names), name


def _without_source_lines(text):
    """A compiled program's text without where in the source each
    operation was traced (the tables of files, functions and frames, and
    each operation's reference to them): operations, shapes, op_names."""
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(\d+ [^\n]*\n)*",
                  "\n", text)
    return re.sub(r" ?(stack_frame_id|source_(end_)?(line|column))=\d+| ?source_file=\"[^\"]*\"",
                  "", text)


def test_three_axis_scopes_leave_the_adc_only_program_alone(monkeypatch):
    texts = {axes: _without_source_lines(_program_text(axes)) for axes in (("adc",), AXES)}
    scope = spans.scope
    monkeypatch.setattr(
        spans, "scope",
        lambda name: contextlib.nullcontext() if name in AXES_SCOPES else scope(name))
    trainer.clear_programs()  # programs traced with the scopes in are shared
    # without the two scopes the ADC-only program's text is the same, to
    # the byte; the three-axis program's is not (the two scopes are in it)
    assert _without_source_lines(_program_text()) == texts[("adc",)]
    assert "op_name=" in texts[("adc",)]
    assert _without_source_lines(_program_text(AXES)) != texts[AXES]


def test_search_is_bit_for_bit_with_scopes_in(monkeypatch):
    cfg = codesign.CodesignConfig(dataset="seeds", pop_size=8, n_generations=2,
                                  max_steps=12, step_scale=0.05, seed=3)
    with spans.recording() as log:
        scoped = codesign.run_codesign(cfg)
    monkeypatch.setattr(spans, "scope", lambda name: contextlib.nullcontext())
    trainer.clear_programs()  # else the plain search reuses the scoped programs
    plain = codesign.run_codesign(cfg)
    np.testing.assert_array_equal(scoped.front_acc, plain.front_acc)
    np.testing.assert_array_equal(scoped.front_masks, plain.front_masks)
    np.testing.assert_array_equal(scoped.front_cats, plain.front_cats)
    assert scoped.conv_acc == plain.conv_acc
    recs = log.spans
    names = [s["name"] for s in recs]
    assert names[:2] == ["codesign.search", "codesign.setup"]
    assert names[-1] == "codesign.result"
    last_call = max(i for i, n in enumerate(names) if n == "trainer.call")
    assert names[recs[last_call]["parent"]] == "codesign.baseline"
    assert set(names) == set(spans.SPANS)
    # a search that starts from no program builds one per bucket it calls
    buckets = {s["attrs"]["bucket"] for s in recs if s["name"] == "trainer.call"}
    assert log.counters["trainer.program_builds"] == len(buckets)
