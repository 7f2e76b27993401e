"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what the CPU backend and the Pallas interpreter
accept: unaligned kernel blocks, too much fast memory, programs that do not
fit the device.  These tests compile the main path's kernels and programs
at real widths for a ``v5e:2x2`` topology.  Nothing runs, so they say
nothing about values or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import qat, trainer
from repro.data import uci_synth
from repro.kernels.fused_qat import ops as fq_ops
from repro.kernels.fused_qat.fused_qat import (
    fused_qat_backward_pallas,
    fused_qat_forward_pallas,
)
from repro.parallel import sharding as shd

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# cardio: the widest paper topology, 21 inputs x 5 hidden x 3 classes
B, C, F, N_BITS = 128, 21, 5, 4
POP = 24  # configs.printed_mlp.codesign_config(full=True).pop_size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cardio():
    X, y, spec = uci_synth.load("cardio")
    Xtr, ytr, Xte, yte = uci_synth.stratified_split(X, y, 0.7, 0)
    mlp = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes))
    return (Xtr, ytr, Xte, yte), mlp


def _row_shapes(lead: tuple[int, ...], sharding_for, axes=("adc",)):
    """ShapeDtypeStructs of one evaluator call (ADC-only genome, or with
    the activation selector and per-layer weight bits of the "act" and
    "wprec" axes)."""
    specs = [
        ((C, 1 << N_BITS), jnp.bool_), ((), jnp.float32), ((), jnp.float32),
        ((), jnp.int32), ((), jnp.int32), ((), jnp.float32), ((), jnp.int32),
    ]
    if "act" in axes:
        specs.append(((1,), jnp.int32))
    if "wprec" in axes:
        specs.append(((2,), jnp.float32))
    out = []
    for shape, dt in specs:
        full = lead + shape
        out.append(jax.ShapeDtypeStruct(full, dt, sharding=sharding_for(full)))
    return out


def _kernel_shapes(one_chip):
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    T = (1 << N_BITS) - 1  # kept-threshold slots of a 2^N-level bank
    return s((B, C)), s((C, T)), s((C, T), jnp.int32), s((C, F))


def test_fused_forward_kernel_compiles_for_v5e(one_chip):
    x, thr, ids, w = _kernel_shapes(one_chip)
    b = jax.ShapeDtypeStruct((F,), jnp.float32, sharding=one_chip)
    text = fused_qat_forward_pallas.lower(
        x, thr, ids, w, b, scale=1.0 / (1 << N_BITS), interpret=False
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_backward_kernel_compiles_for_v5e(one_chip):
    x, thr, ids, w = _kernel_shapes(one_chip)
    g = jax.ShapeDtypeStruct((B, F), jnp.float32, sharding=one_chip)
    text = fused_qat_backward_pallas.lower(
        x, thr, ids, w, g, scale=1.0 / (1 << N_BITS), interpret=False
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_population_program_compiles_for_v5e(one_chip, cardio, monkeypatch):
    """The paper-budget population program with the kernel compiled in.

    The program asks the default backend whether to interpret the kernel;
    here that backend is the CPU, so the test steers it to the TPU answer.
    """
    monkeypatch.setattr(fq_ops, "_auto_interpret", lambda: False)
    data, mlp = cardio
    ev = trainer.make_population_evaluator(
        *data, mlp, trainer.EvalConfig(max_steps=600, use_fused_kernel=True)
    )
    compiled = ev.program.lower(*_row_shapes((POP,), lambda _: one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stacked_island_program_compiles_for_four_chips(topo, cardio):
    """vmap(vmap(train_one)) over a (4, 1) island mesh: no collectives."""
    data, mlp = cardio
    mesh = shd.island_mesh(4, devices=topo.devices)
    assert dict(mesh.shape) == {"island": 4, "data": 1}
    ev = trainer.make_island_evaluator(
        *data, mlp, trainer.EvalConfig(max_steps=600), num_islands=4, mesh=mesh
    )
    rules = shd.island_rules()

    def sharding_for(shape):
        axes = ("island", "population") + (None,) * (len(shape) - 2)
        return shd.logical_sharding(shape, axes, mesh, rules)

    compiled = ev.program.lower(*_row_shapes((4, POP), sharding_for)).compile()
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    out = compiled.output_shardings
    assert len(out.device_set) == 4 and out.spec[0] == "island"
    assert np.prod(compiled.input_shardings[0][0].mesh.devices.shape) == 4


def test_three_axis_population_program_compiles_for_v5e_at_highest(one_chip, cardio):
    """The three-axis genome's paper-budget program with float32 dots: every
    dot at highest, and operations under the ``act`` and ``wprec`` scopes."""
    data, mlp = cardio
    axes = ("adc", "act", "wprec")
    ev = trainer.make_population_evaluator(
        *data, mlp, trainer.EvalConfig(max_steps=600, genome_axes=axes))
    with jax.default_matmul_precision("highest"):
        text = ev.program.lower(*_row_shapes((POP,), lambda _: one_chip, axes)).compile().as_text()
    precisions = set(re.findall(r"operand_precision=\{([^}]*)\}", text))
    assert precisions == {"highest,highest"}
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("act", "wprec"):
        assert any(re.search(rf"(^|[/(]){scope}\)*/", o) for o in op_names), scope
