"""The per-layer readers of the program's spans, counters and traced scopes:
each gives its number on a hand-made run record, and nothing where the
record lacks what it reads."""

import copy

import pytest

from bench import window
from tiny import ROOT


def _log(inputs_self_s):
    """A recording: one evaluator call per input-path self time, counters."""
    spans = []
    for s in inputs_self_s:
        spans.append({"name": "trainer.call", "self": 0.5, "parent": None})
        spans.append({"name": "trainer.input", "self": s, "parent": len(spans) - 1})
    spans.append({"name": "trainer.input", "self": None, "parent": None})  # still open
    return {"spans": spans, "counters": {"trainer.program_builds": 4,
                                         "trainer.useful_row_steps": 234,
                                         "trainer.scanned_row_steps": 1000}}


SEARCH = {
    "searches": [{}, {}],
    "spans": _log([0.002, 0.004]),
    "trace": {"devices": [{}, {}], "idle_spans": [
        ["trainer.dispatch", 3.0], ["nsga2.variation", 0.5], ["nsga2.select", 0.25],
        ["codesign.search", 2.0], ["host: no span", 1.0]]},
}
WAVE = {
    "waves": 3, "program": "_evaluate_padded",
    "spans": _log([0.003, 0.004, 0.005]),
    "trace": {"devices": [{}], "modules_s": {"jit__evaluate_padded(1)": 2.0, "jit_slice(2)": 0.5},
              "scope_s": {"adc": 0.25, "gather": 1.5, "other": 0.1}},
}

# metric, record, the key path it reads (taken out: nothing to read), value
CASES = [
    ("program_builds_per_search", SEARCH, ("spans",), 2.0),
    ("device_idle_s.build", SEARCH, ("trace", "idle_spans"), 1.5),
    ("device_idle_s.host", SEARCH, ("trace", "idle_spans"), 0.375),
    ("input_path_ms_per_call.search", SEARCH, ("spans",), 3.0),
    ("input_path_ms_per_call.wave", WAVE, ("spans",), 4.0),
    ("useful_row_step_share.search", SEARCH, ("spans",), 23.4),
    ("useful_row_step_share.wave", WAVE, ("spans",), 23.4),
    ("qat_adc_share", WAVE, ("trace", "scope_s"), 12.5),
]


def _read(name, rec):
    return window.load_file(ROOT, "metrics", name).read(rec)


@pytest.mark.parametrize("name, rec, path, value", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_its_number(name, rec, path, value):
    assert _read(name, rec) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name, rec, path, value", CASES, ids=[c[0] for c in CASES])
def test_reader_gives_nothing_without_its_key(name, rec, path, value):
    rec = copy.deepcopy(rec)
    inner = rec
    for k in path[:-1]:
        inner = inner[k]
    del inner[path[-1]]
    assert _read(name, rec) is None
    # nor in the other kind of cell, nor in an untraced run's record
    other = WAVE if rec.get("searches") else SEARCH
    assert _read(name, other) is None
    assert _read(name, {**rec, "spans": None, "trace": None}) is None


def test_a_span_or_counter_never_recorded_reads_nothing():
    rec = copy.deepcopy(WAVE)
    rec["spans"]["spans"] = [s for s in rec["spans"]["spans"] if s["name"] != "trainer.input"]
    rec["spans"]["counters"]["trainer.scanned_row_steps"] = 0
    assert _read("input_path_ms_per_call.wave", rec) is None
    assert _read("useful_row_step_share.wave", rec) is None


def test_no_idle_under_the_spans_reads_zero():
    rec = copy.deepcopy(SEARCH)
    rec["trace"]["idle_spans"] = [["codesign.search", 2.0]]
    assert _read("device_idle_s.build", rec) == 0.0
    assert _read("device_idle_s.host", rec) == 0.0
