"""The benchmark's own copies agree with the program where they must."""

import json

import numpy as np
import pytest

from bench import window
from tiny import ROOT


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"printed-mlp-{name}.json").read_text())


def _ref(cfg):
    """The reference module the configuration names."""
    return window.load_file(ROOT, "references", cfg["reference"])


@pytest.mark.parametrize("name", ["cardio", "seeds"])
def test_configuration_states_what_the_program_runs(name):
    from repro.core import area, chromosome, nsga2, trainer
    from repro.data import uci_synth

    cfg = _cfg(name)
    spec = uci_synth.DATASETS[cfg["dataset"]["name"]]
    ds = cfg["dataset"]
    assert (ds["n_features"], ds["n_classes"], ds["n_samples"], ds["seed"]) == (
        spec.n_features, spec.n_classes, spec.n_samples, spec.seed)
    assert cfg["layer_sizes"] == [spec.n_features, spec.hidden, spec.n_classes]
    g = cfg["genes"]
    assert tuple(g["weight_bits"]) == chromosome.WEIGHT_BITS_CHOICES
    assert tuple(g["act_bits"]) == chromosome.ACT_BITS_CHOICES
    assert tuple(g["batch_size"]) == chromosome.BATCH_CHOICES
    assert tuple(g["epochs"]) == chromosome.EPOCH_CHOICES
    assert tuple(g["lr"]) == chromosome.LR_CHOICES
    assert tuple(cfg["init_density"]) == nsga2.NSGA2Config().init_density
    d = trainer.EvalConfig()
    t = cfg["trainer"]
    assert (t["max_steps"], t["max_batch"], t["momentum"]) == (d.max_steps, d.max_batch, d.momentum)
    m = area.EGFET_4BIT
    assert cfg["area_gates"] == {"comparator": m.a_comp, "or": m.a_or, "and": m.a_and}


@pytest.mark.parametrize("name", ["cardio", "seeds"])
def test_inputs_genomes_and_area_match_the_program(name):
    from repro.core import area, chromosome, codesign
    from repro.data import uci_synth

    cfg = _cfg(name)
    reference = _ref(cfg)
    x, y = reference.load_dataset(cfg["dataset"])
    px, py, _ = uci_synth.load(cfg["dataset"]["name"])
    assert np.array_equal(x, px) and np.array_equal(y, py)
    for a, b in zip(reference.split(x, y, 0.7, 12), uci_synth.stratified_split(px, py, 0.7, 12)):
        assert np.array_equal(a, b)
    masks, cats = reference.draw(np.random.default_rng(3), 16, cfg)
    rows = reference.decode(masks, cats, cfg)
    dec = chromosome.decode_batch(masks, cats, cfg["dataset"]["n_features"], cfg["adc_bits"])
    want = (dec["masks"], dec["weight_bits"], dec["act_bits"], dec["batch_size"],
            dec["epochs"], dec["lr"], codesign._genome_seeds(masks, cats))
    for a, b in zip(rows, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    np.testing.assert_allclose(
        reference.area(masks, cats, cfg), area.adc_cost_batch(rows[0], cfg["adc_bits"])[0],
        rtol=1e-12)


def test_reference_trains_as_the_program_does_on_cpu():
    # on the CPU both run true float32 dots, so they agree row for row
    from repro.core import qat, trainer

    cfg = _cfg("seeds")
    cfg["trainer"]["max_steps"] = 40
    reference = _ref(cfg)
    data = reference.split(*reference.load_dataset(cfg["dataset"]), 0.7, 4)
    rows = reference.decode(*reference.draw(np.random.default_rng(5), 8, cfg), cfg)
    ev = trainer.make_population_evaluator(
        *data, qat.MLPConfig(tuple(cfg["layer_sizes"])), trainer.EvalConfig(max_steps=40, seed=4))
    prog = np.asarray(ev(*rows))
    ref = reference.make_qat_reference(cfg, len(data[1]))(*data, 4, *rows)
    np.testing.assert_array_equal(prog, ref)


FIXTURE = ROOT / "bench" / "fixtures" / "window_numbers.npz"


def _recorded(name: str):
    """A tiny window of the cell ``name`` recorded on the CPU (its groups,
    fronts and run seed), with the numbers the benchmark's check gave on it
    when the ADC-only genome's reference was ``bench/reference.py``: as
    answered, and with each answer given to the row before it."""
    z = np.load(FIXTURE)
    p = f"{name}/"
    groups, fronts = [], []
    for i in range(sum(1 for k in z.files if k.startswith(p) and k.endswith("/eval_seed"))):
        q = f"{p}g{i}/"
        g = window.Group(int(z[q + "eval_seed"]), tuple(z[q + f"data{k}"] for k in range(4)))
        g.add(tuple(z[q + f"rows{k}"] for k in range(7)), z[q + "acc"])
        groups.append(g)
    for j in range(sum(1 for k in z.files if k.startswith(p) and k.endswith("/area"))):
        q = f"{p}f{j}/"
        fronts.append({k: z[q + k] for k in ("masks", "cats", "acc", "area")}
                      | {"seed": int(z[q + "seed"])})
    numbers = {kind: {k.rsplit("/", 1)[1]: float(z[k]) for k in z.files
                      if k.startswith(f"{p}{kind}/")} for kind in ("numbers", "altered")}
    return {"groups": groups, "fronts": fronts}, int(z[p + "seed"]), numbers


@pytest.mark.parametrize("name", ["seeds.search", "seeds.wave1024"])
def test_check_gives_the_numbers_it_gave_before_the_move(tmp_path, name):
    from bench import check, harness
    from tiny import tree

    cell = harness.load_cell(name, tree(tmp_path))
    win, seed, recorded = _recorded(name)
    assert check.numbers(cell, win, seed) == recorded["numbers"]
    for g in win["groups"]:
        g.acc = [np.roll(a, 1) for a in g.acc]
    assert check.numbers(cell, win, seed) == recorded["altered"]
    assert any(v > 0 for v in recorded["altered"].values())
