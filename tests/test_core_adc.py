"""Properties of the pruned flash-ADC digital twin (paper §II-A)."""

import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional test dependency (see requirements-test.txt): pip install hypothesis",
)

import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import adc

N_BITS = 4
N_LEVELS = 1 << N_BITS


def masks_strategy(n_channels=2):
    return hnp.arrays(np.bool_, (n_channels, N_LEVELS)).map(
        lambda m: np.concatenate([np.ones((m.shape[0], 1), bool), m[:, 1:]], axis=1)
    )


@settings(max_examples=60, deadline=None)
@given(
    mask=masks_strategy(),
    x=hnp.arrays(
        np.float32,
        (7, 2),
        elements=st.floats(0, 1, width=32, exclude_max=True),
    ),
)
def test_fast_quantizer_equals_circuit(mask, x):
    """The compare-and-max quantizer IS the gate-level pruned flash ADC."""
    fast = np.asarray(adc.quantize_pruned(jnp.asarray(x), jnp.asarray(mask), N_BITS))
    circ = adc.circuit_simulate(x, mask, N_BITS)
    np.testing.assert_array_equal(fast, circ)


@settings(max_examples=30, deadline=None)
@given(mask=masks_strategy(1))
def test_output_levels_are_kept_levels(mask):
    x = np.linspace(0, 0.999, 257, dtype=np.float32)[:, None]
    lv = np.asarray(adc.quantize_pruned(jnp.asarray(x), jnp.asarray(mask), N_BITS))
    kept = set(np.where(mask[0])[0].tolist())
    assert set(np.unique(lv).tolist()) <= kept


@settings(max_examples=30, deadline=None)
@given(mask=masks_strategy(1))
def test_monotone_nonincreasing_loss(mask):
    """Quantization floors: level(x) <= floor-level(x) and monotone in x."""
    x = np.sort(np.random.default_rng(0).uniform(0, 1, 64)).astype(np.float32)[:, None]
    lv = np.asarray(adc.quantize_pruned(jnp.asarray(x), jnp.asarray(mask), N_BITS))[:, 0]
    assert (np.diff(lv) >= 0).all()
    full = np.floor(np.clip(x[:, 0], 0, 1 - 0.5 / N_LEVELS) * N_LEVELS)
    assert (lv <= full).all()


def test_full_mask_is_conventional_adc():
    x = np.random.default_rng(1).uniform(0, 1, (100, 3)).astype(np.float32)
    full = np.ones((3, N_LEVELS), bool)
    lv = np.asarray(adc.quantize_pruned(jnp.asarray(x), jnp.asarray(full), N_BITS))
    ref = np.floor(np.clip(x, 0, 1 - 0.5 / N_LEVELS) * N_LEVELS).astype(np.int64)
    np.testing.assert_array_equal(lv, ref)


def test_level0_cannot_be_pruned():
    m = np.zeros((1, N_LEVELS), bool)  # even all-zeros keeps level 0
    x = np.asarray([[0.0], [0.5], [0.93]], np.float32)
    lv = np.asarray(adc.quantize_pruned(jnp.asarray(x), jnp.asarray(m), N_BITS))
    np.testing.assert_array_equal(lv, 0)


def test_ste_gradient_is_identity():
    import jax

    mask = jnp.asarray(np.ones((1, N_LEVELS), bool))
    g = jax.grad(lambda x: adc.quantize_pruned_ste(x[None, :], mask, N_BITS).sum())(
        jnp.asarray([0.37])
    )
    np.testing.assert_allclose(np.asarray(g), 1.0)


def test_idempotent_on_kept_grid():
    """Re-quantizing a dequantized output is the identity."""
    rng = np.random.default_rng(2)
    mask = rng.uniform(size=(2, N_LEVELS)) < 0.5
    mask[:, 0] = True
    x = rng.uniform(0, 1, (50, 2)).astype(np.float32)
    lv1 = adc.quantize_pruned(jnp.asarray(x), jnp.asarray(mask), N_BITS)
    v1 = adc.levels_to_values(lv1, N_BITS)
    lv2 = adc.quantize_pruned(v1, jnp.asarray(mask), N_BITS)
    np.testing.assert_array_equal(np.asarray(lv1), np.asarray(lv2))
