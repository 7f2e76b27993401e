"""A later PR adds a cell with files and entries of its own, and edits none."""

import hashlib
import json

import jax
import pytest

from bench import harness
from tiny import add_cell, tree


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_cell_is_found_by_name(tmp_path):
    root = tree(tmp_path)
    before = _digests(root)
    old = json.loads((root / "BENCHMARK.json").read_text())

    # seeds.search_async, listed under PERF.md's open questions: the async
    # pipeline driver on the paper search, as one new traffic file
    traffic = json.loads((root / "bench/traffic/paper_search.json").read_text())
    traffic["search"]["async_pipeline"] = True
    (root / "bench/traffic/paper_search_async.json").write_text(json.dumps(traffic))
    add_cell(root, {"name": "seeds.search_async", "config": "printed-mlp-seeds",
                    "traffic": "paper_search_async", "chips": 1, "why": "async"}, "seeds.search")
    bench = json.loads((root / "BENCHMARK.json").read_text())

    after = _digests(root)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {root.joinpath("BENCHMARK.json").relative_to(root)}
    # only entries were added to BENCHMARK.json
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][: len(old[key])] == [
            {**o, **({"workloads": n["workloads"]} if "workloads" in o else {})}
            for o, n in zip(old[key], bench[key])]

    cell = harness.load_cell("seeds.search_async", root)
    assert cell.traffic["search"]["async_pipeline"] is True
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "search_s"]
    assert "jit_s_per_search" in [m["name"] for m in cell.per_layer]
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    out = harness.run(cell, 2**31 + 17, 0.1, False, dev, 0.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "search_s"}
    assert list(out)[-1] == "checks"


NEW_DRIVER = '''"""Waves whose rows arrive in two halves, one evaluator call each."""

from pathlib import Path

from bench import window

Wave = window.load_driver(Path(__file__).resolve().parents[2], "wave")


class Driver(Wave):
    def draw(self, seed):
        super().draw(seed)
        self.pool = [tuple(a[s] for a in rows) for rows in self.pool
                     for s in (slice(None, len(rows[0]) // 2), slice(len(rows[0]) // 2, None))]
'''

NEW_METRIC = '''"""Evaluator calls per second of the window (host clock)."""


def read(rec):
    return rec["waves"] / rec["window_s"] if rec.get("waves") else None
'''


def test_new_traffic_kind_and_metric_are_files_of_their_own(tmp_path):
    root = tree(tmp_path)
    before = _digests(root)

    (root / "bench/drivers/half_waves.py").write_text(NEW_DRIVER)
    (root / "bench/metrics/calls_per_s.py").write_text(NEW_METRIC)
    traffic = json.loads((root / "bench/traffic/eval_wave.json").read_text())
    traffic["driver"] = "half_waves"
    (root / "bench/traffic/eval_half_waves.json").write_text(json.dumps(traffic))
    add_cell(root, {"name": "seeds.half_waves", "config": "printed-mlp-seeds",
                    "traffic": "eval_half_waves", "chips": 1, "why": "halves"}, "seeds.wave1024")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "calls_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": ["seeds.half_waves"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(root)
    assert {p for p in before if before[p] != after[p]} == {
        root.joinpath("BENCHMARK.json").relative_to(root)}

    cell = harness.load_cell("seeds.half_waves", root)
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    out = harness.run(cell, 2**31 + 19, 0.1, False, dev, 0.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "qat_rows_per_s", "calls_per_s"}
    assert out["window"]["rows"] == out["window"]["waves"] * 16


TALLY_REFERENCE = '''"""The ADC-only genome's reference, counting each call the benchmark makes."""

from pathlib import Path

from bench import window

_base = window.load_file(Path(__file__).resolve().parents[2], "references", "adc_genome")
CALLS = {}


def _tally(name):
    fn = getattr(_base, name)

    def call(*a, **k):
        CALLS[name] = CALLS.get(name, 0) + 1
        return fn(*a, **k)
    return call


load_dataset, split, draw, decode, make_qat_reference, area = map(
    _tally, ("load_dataset", "split", "draw", "decode", "make_qat_reference", "area"))
'''


def test_new_configuration_brings_its_own_reference(tmp_path):
    root = tree(tmp_path)
    before = _digests(root)

    (root / "bench/references/adc_genome_tally.py").write_text(TALLY_REFERENCE)
    cfg = json.loads((root / "bench/configs/printed-mlp-seeds.json").read_text())
    cfg["reference"] = "adc_genome_tally"
    (root / "bench/configs/printed-mlp-seeds-tally.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][0], "name": "printed-mlp-seeds-tally",
                             "file": "bench/configs/printed-mlp-seeds-tally.json"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_cell(root, {"name": "seeds.search_tally", "config": "printed-mlp-seeds-tally",
                    "traffic": "paper_search", "chips": 1, "why": "tally"}, "seeds.search")

    after = _digests(root)
    assert {p for p in before if before[p] != after[p]} == {
        root.joinpath("BENCHMARK.json").relative_to(root)}

    cell = harness.load_cell("seeds.search_tally", root)
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    out = harness.run(cell, 2**31 + 29, 0.1, False, dev, 0.0)
    assert out["correct"] is True
    # the warm-up, the window's data, the check and the front's area all
    # went through the configuration's own module
    assert set(cell.ref.CALLS) == {"load_dataset", "split", "draw", "decode",
                                   "make_qat_reference", "area"}


def test_a_configuration_naming_no_such_reference_is_an_error(tmp_path):
    root = tree(tmp_path)
    p = root / "bench/configs/printed-mlp-seeds.json"
    p.write_text(json.dumps({**json.loads(p.read_text()), "reference": "no_such_genome"}))
    with pytest.raises(ValueError, match="bench/references/no_such_genome.py"):
        harness.load_cell("seeds.wave1024", root)
