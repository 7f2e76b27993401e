"""The three-axis genome's cells on Cardiotocography: found by name, run at
the configuration's matmul precision, and added beside the other cells
with files and entries of their own."""

import json

import jax
import pytest

from bench import harness, window
from tiny import ROOT, tree

# the three-axis genome's cells, each beside the seeds cell it is like
AXES_CELLS = {"cardio.search_fullaxes": "seeds.search",
              "cardio.wave1024_fullaxes": "seeds.wave1024"}
AXES_CONFIG, AXES_METRIC = "printed-mlp-cardio-axes", "qat_axes_share"


@pytest.mark.parametrize("name", list(AXES_CELLS))
def test_three_axis_cell_runs_by_name(tmp_path, name):
    root = tree(tmp_path)
    cell = harness.load_cell(name, root)
    like = harness.load_cell(AXES_CELLS[name], root)
    assert cell.config["genome_axes"] == ["adc", "act", "wprec"]
    assert [m["name"] for m in cell.end_to_end] == [m["name"] for m in like.end_to_end]
    extra = {AXES_METRIC} if "wave" in name else set()
    assert {m["name"] for m in cell.per_layer} == {m["name"] for m in like.per_layer} | extra
    dev = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    out = harness.run(cell, 2**31 + 23, 0.1, False, dev, 0.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert jax.config.jax_default_matmul_precision is None


@pytest.mark.parametrize("name", list(AXES_CELLS))
def test_driver_runs_at_the_configurations_precision_and_puts_it_back(tmp_path, name):
    root = tree(tmp_path)
    cell = harness.load_cell(name, root)
    assert cell.config["trainer"]["matmul_precision"] == "highest"
    driver = window.load_driver(root, cell.traffic["driver"])(cell)
    before = jax.config.jax_default_matmul_precision
    driver.prepare()
    assert jax.config.jax_default_matmul_precision == "highest"
    driver.draw(3)
    driver.window(0.0, None)
    assert jax.config.jax_default_matmul_precision == "highest"
    driver.release()
    assert jax.config.jax_default_matmul_precision == before


def _without_axes_entries(bench: dict) -> dict:
    """``bench`` without the three-axis configuration, its cells and metric."""
    out = json.loads(json.dumps(bench))
    out["configs"] = [c for c in out["configs"] if c["name"] != AXES_CONFIG]
    out["workloads"] = [w for w in out["workloads"] if w["name"] not in AXES_CELLS]
    out["per_layer"] = [m for m in out["per_layer"] if m["name"] != AXES_METRIC]
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n in m["workloads"] if n not in AXES_CELLS]
    return out


def test_three_axis_cells_only_add_entries_and_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # added at the end of each list, the new names at the end of each
    # workloads list, and beside exactly the cells they are like
    assert bench["configs"][-1]["name"] == AXES_CONFIG
    assert [w["name"] for w in bench["workloads"][-2:]] == list(AXES_CELLS)
    assert bench["per_layer"][-1]["name"] == AXES_METRIC
    for m in bench["end_to_end"] + bench["per_layer"][:-1]:
        names = m.get("workloads")
        if names is None:
            continue
        added = [n for n in names if n in AXES_CELLS]
        assert names[len(names) - len(added):] == added
        assert added == [n for n, like in AXES_CELLS.items() if like in names]
    # the other cells load what they loaded without these entries ...
    root = tree(tmp_path)
    old = {w["name"] for w in _without_axes_entries(bench)["workloads"]}
    with_axes = {n: harness.load_cell(n, root) for n in old}
    (root / "BENCHMARK.json").write_text(json.dumps(_without_axes_entries(bench)))

    def entries(metrics):
        return [{k: v for k, v in m.items() if k != "workloads"} for m in metrics]
    for n in old:
        a, b = with_axes[n], harness.load_cell(n, root)
        assert (a.config, a.traffic, a.limits) == (b.config, b.traffic, b.limits)
        assert entries(a.end_to_end + a.per_layer) == entries(b.end_to_end + b.per_layer)

    # ... and none of the files the new cells load
    def files(cell_name, bench):
        w = next(w for w in bench["workloads"] if w["name"] == cell_name)
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        cfg = json.loads((ROOT / conf["file"]).read_text())
        traffic = json.loads((ROOT / "bench/traffic" / f"{w['traffic']}.json").read_text())
        return {conf["file"], f"references/{cfg['reference']}", f"traffic/{w['traffic']}",
                f"drivers/{traffic['driver']}", f"limits/{cell_name}"}
    old_files = set().union(*(files(n, bench) for n in old))
    for n in AXES_CELLS:
        assert not files(n, bench) & old_files
