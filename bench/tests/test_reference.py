"""The benchmark's own copies agree with the program where they must."""

import json

import numpy as np
import pytest

from bench import genomes, reference
from tiny import ROOT


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"printed-mlp-{name}.json").read_text())


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
    x, y = reference.load_dataset(cfg["dataset"])
    px, py, _ = uci_synth.load(cfg["dataset"]["name"])
    assert np.array_equal(x, px) and np.array_equal(y, py)
    for a, b in zip(reference.split(x, y, 0.7, 12), uci_synth.stratified_split(px, py, 0.7, 12)):
        assert np.array_equal(a, b)
    masks, cats = genomes.draw(np.random.default_rng(3), 16, cfg)
    rows = reference.decode(masks, cats, cfg)
    dec = chromosome.decode_batch(masks, cats, cfg["dataset"]["n_features"], cfg["adc_bits"])
    want = (dec["masks"], dec["weight_bits"], dec["act_bits"], dec["batch_size"],
            dec["epochs"], dec["lr"], codesign._genome_seeds(masks, cats))
    for a, b in zip(rows, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    np.testing.assert_allclose(
        reference.adc_area(rows[0], cfg["adc_bits"], cfg["area_gates"]),
        area.adc_cost_batch(rows[0], cfg["adc_bits"])[0], rtol=1e-12)


def test_reference_trains_as_the_program_does_on_cpu():
    # on the CPU both run true float32 dots, so they agree row for row
    from repro.core import qat, trainer

    cfg = _cfg("seeds")
    cfg["trainer"]["max_steps"] = 40
    data = reference.split(*reference.load_dataset(cfg["dataset"]), 0.7, 4)
    rows = reference.decode(*genomes.draw(np.random.default_rng(5), 8, cfg), cfg)
    ev = trainer.make_population_evaluator(
        *data, qat.MLPConfig(tuple(cfg["layer_sizes"])), trainer.EvalConfig(max_steps=40, seed=4))
    prog = np.asarray(ev(*rows))
    ref = reference.make_qat_reference(cfg, len(data[1]))(*data, 4, *rows)
    np.testing.assert_array_equal(prog, ref)
