"""The pruned-ADC quantizer's compare-and-max encoder.

``core/adc.quantize_pruned`` encodes each input as the largest kept level
whose threshold it reaches, a masked max over the level axis.  Its former
formulation ranked the input against a sorted kept-threshold table and
gathered the level id of that rank (``_gather_quantize_pruned`` below, kept
here as an oracle).  Both give the same int32 levels, equal to the
gate-level circuit, for every width, on every threshold and on either side
of it, under ``vmap`` with a mask per row, and through a whole population
QAT run; and the compiled programs hold no gather or sort in the ADC stage.
"""

import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adc, chromosome, qat, trainer
from repro.data import uci_synth

ROOT = Path(__file__).resolve().parents[1]
N_BITS = [1, 2, 3, 4, 5]


@partial(jax.jit, static_argnames=("n_bits",))
def _gather_quantize_pruned(x, mask, n_bits, vref=1.0):
    """The rank -> argsort/compact -> ``take_along_axis`` encoder."""
    mask = adc.force_level0(mask)
    n = 1 << n_bits
    x = jnp.clip(x, 0.0, vref * (1.0 - 0.5 / n))
    thr = adc.kept_thresholds(mask, n_bits, vref)
    rank = jnp.sum(x[..., None] >= thr, axis=-1).astype(jnp.int32)
    lvl_ids = jnp.arange(1, n, dtype=jnp.int32)
    keep = mask[..., 1:]
    order = jnp.argsort(jnp.where(keep, lvl_ids, jnp.iinfo(jnp.int32).max), axis=-1)
    compact = jnp.where(
        jnp.arange(n - 1) < jnp.sum(keep, axis=-1, keepdims=True),
        jnp.take_along_axis(jnp.broadcast_to(lvl_ids, keep.shape), order, axis=-1),
        0,
    )
    padded = jnp.concatenate(
        [jnp.zeros(compact.shape[:-1] + (1,), compact.dtype), compact], axis=-1
    )
    return jnp.take_along_axis(
        jnp.broadcast_to(padded, x.shape[:-1] + padded.shape), rank[..., None], axis=-1
    )[..., 0]


def _probes(n_bits: int) -> np.ndarray:
    """Every threshold, the float32 just below each, 0, negatives, the
    clip point and values at or above vref (= 1)."""
    thr = np.arange(1, 1 << n_bits, dtype=np.float32) / np.float32(1 << n_bits)
    below = np.nextafter(thr, np.float32(-np.inf))
    edges = np.asarray([0.0, -0.0, -1e-7, -0.4, 1.0 - 0.5 / (1 << n_bits),
                        np.nextafter(np.float32(1.0), np.float32(0.0)), 1.0, 1.5, 7.0])
    return np.concatenate([thr, below, edges]).astype(np.float32)


def _masks(rng, shape, n_bits):
    """Random keep-masks with the all-pruned and the full mask among them."""
    m = rng.uniform(size=shape + (1 << n_bits,)) < 0.5
    m.reshape(-1, 1 << n_bits)[:2] = np.asarray([False, True])[:, None]
    return m


@pytest.mark.parametrize("n_bits", N_BITS)
def test_encoder_equals_gather_oracle_and_circuit_on_every_edge(n_bits):
    rng = np.random.default_rng(n_bits)
    probes = _probes(n_bits)
    x = np.concatenate([probes, rng.uniform(-0.2, 1.2, 64).astype(np.float32)])
    mask = _masks(rng, (7,), n_bits)
    x = np.repeat(x[:, None], 7, axis=1)  # every probe through every channel
    got = np.asarray(adc.quantize_pruned(jnp.asarray(x), jnp.asarray(mask), n_bits))
    assert got.dtype == np.int32
    oracle = np.asarray(_gather_quantize_pruned(jnp.asarray(x), jnp.asarray(mask), n_bits))
    np.testing.assert_array_equal(got, oracle)
    np.testing.assert_array_equal(got, adc.circuit_simulate(x, mask, n_bits))


@pytest.mark.parametrize("n_bits", N_BITS)
def test_encoder_equals_gather_oracle_under_vmap_with_a_mask_per_row(n_bits):
    rng = np.random.default_rng(100 + n_bits)
    P, B, C = 4, 128, 7
    x = rng.uniform(-0.2, 1.2, (P, B, C)).astype(np.float32)
    probes = _probes(n_bits)
    x[:, : len(probes)] = probes[:, None]
    masks = _masks(rng, (P, C), n_bits)
    got = np.asarray(jax.vmap(lambda a, m: adc.quantize_pruned(a, m, n_bits))(x, masks))
    oracle = np.asarray(jax.vmap(lambda a, m: _gather_quantize_pruned(a, m, n_bits))(x, masks))
    np.testing.assert_array_equal(got, oracle)
    for p in range(P):
        np.testing.assert_array_equal(got[p], adc.circuit_simulate(x[p], masks[p], n_bits))


def _seeds_evaluator():
    X, y, spec = uci_synth.load("seeds")
    data = uci_synth.stratified_split(X, y, 0.7, 0)
    mlp = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes), adc_bits=4)
    cfg = trainer.EvalConfig(max_steps=24, step_scale=0.05)
    return trainer.make_population_evaluator(*data, mlp, cfg), spec


def _rows(spec, n, seed):
    """Decoded genomes with pruned masks, as NSGA-II draws them."""
    rng = np.random.default_rng(seed)
    bits = rng.uniform(size=(n, chromosome.n_mask_bits(spec.n_features, 4))) < 0.6
    cats = np.stack([rng.integers(0, c, n) for c in chromosome.cat_cardinalities(("adc",), 2)], 1)
    dec = chromosome.decode_batch(bits, cats, spec.n_features, 4)
    return (dec["masks"], dec["weight_bits"], dec["act_bits"], dec["batch_size"],
            dec["epochs"], dec["lr"], np.arange(n, dtype=np.int32))


def _adc_sorts_and_gathers(text: str) -> list[str]:
    """op_names of the gathers and sorts a compiled program's text puts
    inside ``quantize_pruned`` or the named scope ``adc``."""
    sys.path.insert(0, str(ROOT))
    try:
        from bench import program_trace as pt
    finally:
        sys.path.remove(str(ROOT))
    return [o for o in pt.op_names_from_hlo(text).values()
            if o.rsplit("/", 1)[-1] in ("gather", "sort")
            and ("quantize_pruned" in o or pt._scope(o, {"adc"}) == "adc")]


def test_no_gather_or_sort_in_the_adc_stage(monkeypatch):
    x = jnp.zeros((4, 128, 7), jnp.float32)
    m = jnp.ones((4, 7, 16), bool)

    def compiled(fn):
        return jax.jit(jax.vmap(lambda a, k: fn(a, k, 4))).lower(x, m).compile().as_text()

    # the check sees the oracle's gathers and sorts, so it would see them come back
    assert _adc_sorts_and_gathers(compiled(_gather_quantize_pruned))
    assert _adc_sorts_and_gathers(compiled(adc.quantize_pruned)) == []

    def program_text():
        ev, spec = _seeds_evaluator()
        rows = _rows(spec, 4, 0)
        return ev.program.lower(*(ev.shard_fn(a) for a in rows)).compile().as_text()

    assert _adc_sorts_and_gathers(program_text()) == []
    monkeypatch.setattr(adc, "quantize_pruned", _gather_quantize_pruned)
    trainer.clear_programs()  # the evaluators' programs are shared
    assert _adc_sorts_and_gathers(program_text())


def test_population_accuracies_are_bit_identical_to_the_gather_oracle(monkeypatch):
    ev, spec = _seeds_evaluator()
    rows = _rows(spec, 6, 1)
    assert not rows[0].all()  # pruned levels take part
    got = np.asarray(ev(*rows))
    monkeypatch.setattr(adc, "quantize_pruned", _gather_quantize_pruned)
    trainer.clear_programs()  # the evaluators' programs are shared
    oracle_ev, _ = _seeds_evaluator()
    oracle = np.asarray(oracle_ev(*rows))
    np.testing.assert_array_equal(got, oracle)
