"""The three-axis genome's reference, ``bench/references/full_genome.py``,
agrees with the program where it must: tables, inputs, genomes, area, and
the trained accuracies at ``highest``."""

import json

import jax
import numpy as np
import pytest

import test_reference as base
from bench import window
from tiny import ROOT

NAME = "cardio-axes"


def _cfg():
    return json.loads((ROOT / "bench" / "configs" / f"printed-mlp-{NAME}.json").read_text())


def _ref(cfg):
    return window.load_file(ROOT, "references", cfg["reference"])


def test_three_axis_configuration_states_what_the_program_runs():
    base.test_configuration_states_what_the_program_runs(NAME)


def test_three_axis_inputs_genomes_and_area_match_the_program():
    from repro.core import area, chromosome, codesign
    from repro.data import uci_synth

    cfg = _cfg()
    reference = _ref(cfg)
    x, y = reference.load_dataset(cfg["dataset"])
    px, py, _ = uci_synth.load(cfg["dataset"]["name"])
    assert np.array_equal(x, px) and np.array_equal(y, py)
    for a, b in zip(reference.split(x, y, 0.7, 12), uci_synth.stratified_split(px, py, 0.7, 12)):
        assert np.array_equal(a, b)
    masks, cats = reference.draw(np.random.default_rng(3), 16, cfg)
    axes, n_layers = tuple(cfg["genome_axes"]), len(cfg["layer_sizes"]) - 1
    assert cats.shape[1] == len(chromosome.cat_cardinalities(axes, n_layers))
    rows = reference.decode(masks, cats, cfg)
    dec = chromosome.decode_batch(masks, cats, cfg["dataset"]["n_features"], cfg["adc_bits"],
                                  axes=axes, n_layers=n_layers)
    want = (dec["masks"], dec["weight_bits"], dec["act_bits"], dec["batch_size"],
            dec["epochs"], dec["lr"], codesign._genome_seeds(masks, cats),
            *codesign._extra_rows(dec))
    assert len(rows) == len(want)
    for a, b in zip(rows, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    program_area = area.genome_area_batch(
        dec["masks"], cfg["adc_bits"], cfg["layer_sizes"], dec["weight_bits"],
        dec["act_bits"], act_sel=dec["act_sel"], wprec=dec["wprec"])[0]
    np.testing.assert_allclose(reference.area(masks, cats, cfg), program_area, rtol=1e-12)


def test_three_axis_tables_are_the_programs():
    from repro.core import area, chromosome

    cfg = _cfg()
    assert tuple(cfg["act_choices"]) == chromosome.ACT_APPROX_CHOICES
    assert tuple(float(b) for b in cfg["wprec_bits"]) == chromosome.WPREC_BITS
    assert chromosome.TERNARY_BITS == 0.0 and 0 in cfg["wprec_bits"]
    d = cfg["datapath_area"]
    assert (d["adder_bit"], d["output_stage_bit"]) == (area._A_ADD_BIT, area._A_RELU_BIT)
    assert tuple(d["act_area_scale"]) == area.ACT_APPROX_AREA_SCALE
    # the reference keys its circuits by the configuration's names
    assert set(_ref(cfg).CIRCUITS) == set(cfg["act_choices"])


def _axes_case(n=16, max_steps=60):
    """The three-axis configuration at a CPU test's size: ``n`` seeded
    genomes that between them take every activation circuit and every
    weight precision, ternary among them, in each layer; the program's
    accuracies at ``highest``; the reference's arguments."""
    from repro.core import qat, trainer

    cfg = _cfg()
    cfg["trainer"]["max_steps"] = max_steps
    reference = _ref(cfg)
    data = reference.split(*reference.load_dataset(cfg["dataset"]), 0.7, 4)
    masks, cats = reference.draw(np.random.default_rng(5), n, cfg)
    cats[:, 5] = np.arange(n) % len(cfg["act_choices"])
    cats[:, 6] = np.arange(n) // 4 % len(cfg["wprec_bits"])
    cats[:, 7] = (np.arange(n) + 2) % len(cfg["wprec_bits"])
    cats[:, 4] = 2  # the largest learning rate, so a wrong circuit shows in few steps
    rows = reference.decode(masks, cats, cfg)
    ev = trainer.make_population_evaluator(
        *data, qat.MLPConfig(tuple(cfg["layer_sizes"])),
        trainer.EvalConfig(max_steps=max_steps, seed=4, genome_axes=tuple(cfg["genome_axes"])))
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(ev(*rows))
    return cfg, reference, data, rows, prog


@pytest.fixture(scope="module")
def axes_case():
    return _axes_case()


def test_full_genome_reference_trains_as_the_program_does_on_cpu(axes_case):
    # Both at highest, on the CPU, where every dot is a true float32 dot:
    # the reference writes the same equations, so the two agree row for
    # row, and no tolerance is needed (a difference in the order of one
    # float32 sum would show here, and would be written down beside it).
    cfg, reference, data, rows, prog = axes_case
    assert set(rows[7][:, 0]) == set(range(len(cfg["act_choices"])))
    assert (rows[8] == 0).any(axis=0).all()  # ternary in every layer
    ref = reference.make_qat_reference(cfg, len(data[1]))(*data, 4, *rows)
    np.testing.assert_array_equal(prog, ref)


@pytest.mark.parametrize("fault", ["act_sel ignored", "wprec ignored"])
def test_a_reference_that_drops_an_axis_fails_the_comparison(axes_case, fault):
    cfg, reference, data, rows, prog = axes_case
    planted = list(rows)
    if fault == "act_sel ignored":
        planted[7] = np.zeros_like(rows[7])  # every hidden layer exact ReLU
    else:
        planted[8] = np.repeat(rows[1][:, None], rows[8].shape[1], axis=1)  # scalar weight bits
    ref = reference.make_qat_reference(cfg, len(data[1]))(*data, 4, *planted)
    gap = np.abs(prog - ref) * len(data[3])
    assert gap.max() >= 2


def test_three_axis_front_members_are_rows_the_program_answered(tmp_path):
    # check.front_numbers matches each member's decoded rows but the last
    # with the rows the program answered; the last is the ADC-only
    # genome's training seed, here the seed is the seventh of nine rows,
    # so the search cell cannot hold front_unmatched (its limits file) and
    # this test holds the members to the program's rows, every row but
    # the training seed, which is the crc32 of the raw genome, not of its
    # decoded level masks
    from bench import harness
    from tiny import tree

    cell = harness.load_cell("cardio.search_fullaxes", tree(tmp_path))
    driver = window.load_driver(cell.root, cell.traffic["driver"])(cell)
    driver.prepare()
    driver.draw(5)
    win = driver.window(0.0, None)
    driver.release()
    by_seed = {g.eval_seed: g for g in win["groups"]}
    for f in win["fronts"]:
        rows, acc = by_seed[f["seed"]].arrays()
        masks = np.asarray(f["masks"], bool).reshape(len(f["acc"]), -1)
        members = cell.ref.decode(masks, f["cats"], cell.config)
        assert len(members) == len(rows) == 9
        reported = 1.0 - (np.float32(1.0) - acc).astype(np.float64)
        for m in range(len(f["acc"])):
            same = reported == f["acc"][m]
            for i, (col, v) in enumerate(zip(rows, members)):
                if i != 6:
                    same &= np.all((col == v[m]).reshape(len(col), -1), axis=1)
            assert same.any()
