"""Reductions of a profiler trace by the program's own spans and named
scopes (``bench/program_trace.py``), and the harness's traced run with them in."""

import json
from pathlib import Path

import jax
import pytest

from bench import harness, window, work
from bench import program_trace as pt
from bench import trace_reduce as tr
from tiny import tree

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / "trace_wave.json"
SPANS = ("codesign.search", "nsga2.generation", "nsga2.variation", "trainer.dispatch")
MS = 1_000_000
SCOPES = ("adc", "layer", "gather", "loss", "sgd", "test")


def _trace():
    """One device over 100 ms: a search span holding a generation, which
    holds its variation and a dispatch with JAX's lowering inside it."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.traced", 0, 100 * MS],
            ["codesign.search", 2 * MS, 96 * MS],
            ["nsga2.generation", 10 * MS, 80 * MS],
            ["nsga2.variation", 10 * MS, 10 * MS],
            ["trainer.dispatch", 20 * MS, 40 * MS],
            ["lower_sharding_computation", 25 * MS, 20 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit__evaluate_padded(1)", 60 * MS, 30 * MS],
                                               ["jit_slice(2)", 95 * MS, 1 * MS]]},
            {"name": "XLA Ops", "events": [
                ["%while.1", 60 * MS, 30 * MS],
                ["%fusion.1", 61 * MS, 20 * MS],
                ["%fusion.2", 81 * MS, 5 * MS],
                ["%slice.1", 95 * MS, 1 * MS],
                ["%tiny", 96 * MS + 10_000, 1 * MS]]}]},
    ]}


# the op_names of the program, as its compiled text gives them
HLO = """HloModule jit__evaluate_padded
  %fusion.1 = s32[917504]{0} fusion(%a, %b), kind=kCustom, calls=%c, metadata={op_name="jit(_evaluate_padded)/vmap()/while/body/closed_call/jvp(adc)/jit(quantize_pruned)/gather" stack_frame_id=73}
  %fusion.2 = f32[3]{0} fusion(%d), kind=kLoop, metadata={op_name="jit(_evaluate_padded)/vmap()/while/body/closed_call/transpose(jvp(layer))/dot_general"}
  ROOT %while.1 = (s32[]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(_evaluate_padded)/vmap()/while"}
  %copy.1 = f32[3]{0} copy(%e)
"""


def test_idle_goes_to_the_innermost_program_span():
    t = _trace()
    window = tr.window_of(t, "bench.traced")
    idle = dict(pt.idle_by_span(t, window, SPANS))
    # gaps: [0, 60) midpoint 30 ms inside the dispatch (the lowering event
    # is JAX's, not a program span); [90, 95) in the search, after its
    # generation; [97.01, 100) after the search; [96, 96.01) is under 50 us
    assert idle == pytest.approx({"trainer.dispatch": 0.060, "codesign.search": 0.005,
                                  pt.NO_SPAN: 0.00299})
    red = tr.reduce(t, window)
    long_gaps = sum(s for n, s in red["idle_gaps"] if n != tr.SHORT_GAP)
    assert sum(idle.values()) == pytest.approx(long_gaps)
    # without a span over a gap it is named as such
    assert dict(pt.idle_by_span(t, window, ()))[pt.NO_SPAN] == pytest.approx(long_gaps)


def test_op_names_come_from_the_compiled_text():
    names = pt.op_names_from_hlo(HLO)
    assert names == {
        "%fusion.1": "jit(_evaluate_padded)/vmap()/while/body/closed_call/jvp(adc)/"
                     "jit(quantize_pruned)/gather",
        "%fusion.2": "jit(_evaluate_padded)/vmap()/while/body/closed_call/"
                     "transpose(jvp(layer))/dot_general",
        "%while.1": "jit(_evaluate_padded)/vmap()/while"}
    scopes = ("adc", "layer", "gather")
    assert [pt._scope(names[k], scopes) for k in ("%fusion.1", "%fusion.2", "%while.1")] == [
        "adc", "layer", pt.NO_SCOPE]
    # the scope is a component of the name stack, not the operation's own name
    assert pt._scope("jit(f)/vmap()/while/body/closed_call/gather", scopes) == pt.NO_SCOPE
    assert pt._scope("jit(f)/vmap()/while/body/closed_call/gather/gather", scopes) == "gather"


def test_scope_seconds_take_nested_operations_out():
    t, names = _trace(), pt.op_names_from_hlo(HLO)
    got = pt.scope_seconds(t, (0, 100 * MS), ("adc", "layer", "loss"), "_evaluate_padded",
                           names)
    # the loop's own time is what its body's operations leave of it; the
    # slice belongs to another program, whose names it does not share
    assert got == pytest.approx({"adc": 0.020, "layer": 0.005, pt.NO_SCOPE: 0.005})
    clipped = pt.scope_seconds(t, (0, 71 * MS), ("adc", "layer"), "_evaluate_padded", names)
    assert clipped == pytest.approx({"adc": 0.010, pt.NO_SCOPE: 0.001})
    assert pt.scope_seconds(t, (0, 100 * MS), ("adc",), "_evaluate_padded", None) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace_reduces_as_before_with_program_spans_in():
    t = json.loads(FIXTURE.read_text())
    lo, hi = t["window"]
    expected = json.loads(json.dumps(t["expected"]))
    # program spans are host events: only the labels of idle gaps may move
    t["planes"].append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["trainer.call", lo, hi - lo], ["trainer.dispatch", lo + 10, hi - lo - 20]]}]})
    red = tr.reduce(t, (lo, hi))
    assert {k: v for k, v in red.items() if k != "idle_gaps"} == {
        k: v for k, v in expected.items() if k != "idle_gaps"}
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        sum(s for _, s in expected["idle_gaps"]))
    idle = pt.idle_by_span(t, (lo, hi), ("trainer.call", "trainer.dispatch"))
    long_gaps = sum(s for n, s in red["idle_gaps"] if n != tr.SHORT_GAP)
    assert sum(s for _, s in idle) == pytest.approx(long_gaps)
    assert dict(idle)["trainer.dispatch"] > 0


def test_traced_run_with_the_programs_spans_in(tmp_path):
    # the program's spans are host events of the trace; the harness's
    # reduction and its answers do not depend on them
    root = tree(tmp_path)
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    cell = harness.load_cell("seeds.wave1024", root)
    out = harness.run(cell, 2**31 + 5, 0.1, True, dev, 0.0)
    assert out["correct"] is True
    # the recorded counters are the row-steps the window's genomes train
    driver = window.load_driver(root, cell.traffic["driver"])(cell)
    driver.draw(2**31 + 5)
    rows = [driver.pool[i % len(driver.pool)] for i in range(out["window"]["waves"])]
    n_train = work.split_sizes(cell.config["dataset"])[0]
    t = cell.config["trainer"]
    useful = sum((work.useful_steps(r[3], r[4], n_train, t) * r[3]).sum() for r in rows)
    scanned = out["window"]["rows"] * t["max_steps"] * t["max_batch"]
    got = out["metrics"]
    assert got["useful_row_step_share.wave"]["value"] == pytest.approx(100 * useful / scanned,
                                                                       rel=1e-12)
    assert got["input_path_ms_per_call.wave"]["value"] > 0
    # a CPU trace has no device planes: the device's readings are left out
    assert "qat_adc_share" not in got
    # the driver's waves ran a program whose text carries every scope
    driver.prepare()
    driver.draw(3)
    names = driver.op_names()
    assert {pt._scope(o, SCOPES) for o in names.values()} == set(SCOPES) | {pt.NO_SCOPE}


def test_traced_search_counts_repeat_for_a_seed(tmp_path):
    root = tree(tmp_path)
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    cell = harness.load_cell("seeds.search", root)
    runs = [harness.run(cell, 2**31 + 7, 0.1, True, dev, 0.0)["metrics"] for _ in range(2)]
    for k in ("program_builds_per_search", "useful_row_step_share.search"):
        assert runs[0][k]["value"] == runs[1][k]["value"]
    assert runs[0]["program_builds_per_search"]["value"] >= 1
    assert 0 < runs[0]["useful_row_step_share.search"]["value"] < 100
    assert runs[0]["input_path_ms_per_call.search"]["value"] > 0
    assert not {"device_idle_s.build", "device_idle_s.host"} & set(runs[0])


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/vmap()/while/body/closed_call/transpose(jvp(adc))/mul", "adc"),
    ("jit(f)/vmap()/while/body/closed_call/adc/layer/dot_general", "layer"),
    ("jit(f)/vmap()/while/body/closed_call/jvp(layer)/jvp(adc)/gather", "adc"),
    ("jit(f)/while/body/sgd/add;jit(f)/while/body/loss/mul", "sgd"),
    ("jit(f)/while/body/adc", pt.NO_SCOPE),
    ("", pt.NO_SCOPE),
])
def test_scope_is_the_innermost_named_one(op_name, scope):
    assert pt._scope(op_name, set(SCOPES)) == scope


def test_self_time_takes_out_each_level_of_nesting():
    # a loop holding a call holding an op, then a sibling after the loop
    evs = [(0, 100), (10, 60), (20, 30), (70, 80), (100, 110)]
    assert pt._self_ns(evs) == [100 - 50 - 10, 50 - 10, 10, 10, 10]
    assert pt._self_ns(list(reversed(evs))) == list(reversed(pt._self_ns(evs)))


def test_an_operation_runs_in_the_last_program_started():
    modules = sorted([(10, "jit_a(1)"), (50, "jit_b(2)")])
    assert [pt._module_at(modules, t) for t in (5, 10, 49, 50, 99)] == [
        "", "jit_a(1)", "jit_a(1)", "jit_b(2)", "jit_b(2)"]


@pytest.mark.parametrize("reduction", ["idle_by_span", "scope_seconds"])
def test_nothing_to_read_without_device_operations(reduction):
    t = {"planes": [p for p in _trace()["planes"] if p["name"].startswith("/host")]}
    names = pt.op_names_from_hlo(HLO)
    got = (pt.idle_by_span(t, (0, 100 * MS), SPANS) if reduction == "idle_by_span"
           else pt.scope_seconds(t, (0, 100 * MS), SCOPES, "_evaluate_padded", names))
    assert got is None


def test_idle_and_scopes_sum_over_devices():
    t = _trace()
    second = json.loads(json.dumps(t["planes"][1]))
    second["name"] = "/device:TPU:1"
    t["planes"].append(second)
    one = dict(pt.idle_by_span(_trace(), (0, 100 * MS), SPANS))
    two = dict(pt.idle_by_span(t, (0, 100 * MS), SPANS))
    assert two == pytest.approx({k: 2 * v for k, v in one.items()})
    names = pt.op_names_from_hlo(HLO)
    assert pt.scope_seconds(t, (0, 100 * MS), SCOPES, "_evaluate_padded", names) == \
        pytest.approx({"adc": 0.040, "layer": 0.010, pt.NO_SCOPE: 0.010})
