"""Fused pruned-ADC QAT kernel vs the pure-JAX reference it replaces.

Interpreter-mode equivalence (CPU CI): exhaustive small-N forward checks,
STE gradient agreement under ``jax.grad`` (including multi-tile dw
accumulation), the population-vmapped path, and drop-in identity inside
``core.qat.mlp_forward`` / ``core.trainer`` / ``core.codesign``.

Numerical contract: the discrete comparator/encoder decisions are exact
(a wrong level would shift an output by ~vref/2^N times a weight, orders
of magnitude above any tolerance here); the final matmul may differ from
the reference by 1 ulp because XLA fuses the in-kernel dot+bias into an
FMA while the two-program reference rounds twice — hence tight
``allclose`` (fp32 tolerance) rather than bitwise equality.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codesign, qat, trainer
from repro.data import uci_synth
from repro.kernels.fused_qat import fused_qat_first_layer
from repro.kernels.fused_qat import ops as fq_ops
from repro.kernels.fused_qat import ref as fq_ref


def _x_grid(n_bits: int) -> np.ndarray:
    """Inputs covering every level cell and both sides of every threshold."""
    n = 1 << n_bits
    thr = np.arange(1, n) / n
    pts = np.concatenate(
        [thr, thr - 1e-6, thr + 1e-6, np.linspace(0.0, 1.0 - 1e-6, 17), [0.0]]
    )
    return np.clip(pts, 0.0, 1.0 - 1e-7).astype(np.float32)


@pytest.mark.parametrize("n_bits", [1, 2, 3])
def test_fused_forward_exhaustive_small_n(n_bits):
    """ALL single-channel masks x an input grid spanning every level cell."""
    n = 1 << n_bits
    rng = np.random.default_rng(n_bits)
    x = jnp.asarray(_x_grid(n_bits)[:, None])  # (B, 1)
    w = jnp.asarray(rng.normal(size=(1, 4)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
    for bits in itertools.product([False, True], repeat=n - 1):
        mask = jnp.asarray(np.array([True, *bits])[None, :])  # level 0 forced
        out = fused_qat_first_layer(x, mask, w, b, n_bits, interpret=True)
        ref = fq_ref.fused_qat_ref(x, mask, w, b, n_bits)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("n_bits", [2, 3])
@pytest.mark.parametrize("C", [3, 7])
def test_fused_forward_multichannel(n_bits, C):
    rng = np.random.default_rng(100 * n_bits + C)
    x = jnp.asarray(rng.uniform(0, 1, (129, C)).astype(np.float32))
    mask = rng.uniform(size=(C, 1 << n_bits)) < 0.5
    mask[:, 0] = True
    w = jnp.asarray(rng.normal(size=(C, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    out = fused_qat_first_layer(x, jnp.asarray(mask), w, b, n_bits, block_b=32)
    ref = fq_ref.fused_qat_ref(x, jnp.asarray(mask), w, b, n_bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_fused_ste_gradients_match_reference():
    """jax.grad agreement incl. dw accumulation across multiple batch tiles."""
    rng = np.random.default_rng(7)
    B, C, F, n_bits = 37, 5, 6, 4  # block_b=8 -> 5 grid steps, padded tail
    x = jnp.asarray(rng.uniform(0, 1, (B, C)).astype(np.float32))
    mask = rng.uniform(size=(C, 16)) < 0.6
    mask[:, 0] = True
    mask = jnp.asarray(mask)
    w = jnp.asarray(rng.normal(size=(C, F)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(F,)).astype(np.float32))

    # non-linear loss so cotangents vary across rows
    def loss_fused(x, w, b):
        return jnp.sum(jnp.sin(fused_qat_first_layer(x, mask, w, b, n_bits, block_b=8)))

    def loss_ref(x, w, b):
        return jnp.sum(jnp.sin(fq_ref.fused_qat_ref(x, mask, w, b, n_bits)))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for got, want, name in zip(gf, gr, ("dx", "dw", "db")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6, err_msg=name
        )


def test_fused_vmap_population_axis():
    """Heterogeneous per-genome masks through vmap, values + dw gradients."""
    rng = np.random.default_rng(3)
    P, B, C, F = 4, 16, 3, 5
    xs = jnp.asarray(rng.uniform(0, 1, (P, B, C)).astype(np.float32))
    masks = rng.uniform(size=(P, C, 16)) < 0.5
    masks[:, :, 0] = True
    masks = jnp.asarray(masks)
    ws = jnp.asarray(rng.normal(size=(P, C, F)).astype(np.float32))
    bs = jnp.asarray(rng.normal(size=(P, F)).astype(np.float32))

    fused = jax.vmap(lambda x, m, w, b: fused_qat_first_layer(x, m, w, b, 4))
    ref = jax.vmap(lambda x, m, w, b: fq_ref.fused_qat_ref(x, m, w, b, 4))
    np.testing.assert_allclose(
        np.asarray(fused(xs, masks, ws, bs)), np.asarray(ref(xs, masks, ws, bs)),
        rtol=1e-6, atol=1e-6,
    )
    gf = jax.grad(lambda ws: jnp.sum(jnp.cos(fused(xs, masks, ws, bs))))(ws)
    gr = jax.grad(lambda ws: jnp.sum(jnp.cos(ref(xs, masks, ws, bs))))(ws)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=1e-6, atol=1e-6)


def test_mlp_forward_fused_is_drop_in():
    """use_fused=True: identical logits and parameter gradients."""
    rng = np.random.default_rng(0)
    cfg = qat.MLPConfig((5, 8, 3))
    params = qat.init_mlp(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.uniform(0, 1, (37, 5)).astype(np.float32))
    mask = rng.uniform(size=(5, 16)) < 0.6
    mask[:, 0] = True
    mask = jnp.asarray(mask)
    y = jnp.asarray(rng.integers(0, 3, 37).astype(np.int32))

    ref = qat.mlp_forward(params, x, cfg, mask)
    out = qat.mlp_forward(params, x, cfg, mask, use_fused=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)

    def loss(p, fused):
        return qat.cross_entropy(
            qat.mlp_forward(p, x, cfg, mask, use_fused=fused), y
        )

    g_ref = jax.grad(loss)(params, False)
    g_out = jax.grad(loss)(params, True)
    for k in g_ref:
        np.testing.assert_allclose(
            np.asarray(g_out[k]), np.asarray(g_ref[k]), rtol=1e-6, atol=1e-7,
            err_msg=k,
        )


def test_population_evaluator_fused_matches_unfused():
    """Full QAT training loops agree: same test accuracies per chromosome."""
    X, y, spec = uci_synth.load("seeds")
    Xtr, ytr, Xte, yte = uci_synth.stratified_split(X, y)
    cfg = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes))
    evs = [
        trainer.make_population_evaluator(
            Xtr, ytr, Xte, yte, cfg,
            trainer.EvalConfig(max_steps=25, use_fused_kernel=fused),
        )
        for fused in (False, True)
    ]
    rng = np.random.default_rng(0)
    P = 4
    masks = rng.uniform(size=(P, spec.n_features, 16)) < 0.7
    masks[:, :, 0] = True
    args = (
        masks,
        np.full(P, 8.0, np.float32), np.full(P, 4.0, np.float32),
        np.full(P, 32, np.int32), np.full(P, 40, np.int32),
        np.full(P, 0.05, np.float32), np.arange(P, dtype=np.int32),
    )
    acc_ref, acc_fused = (np.asarray(ev(*args)) for ev in evs)
    np.testing.assert_allclose(acc_fused, acc_ref, atol=1e-7)


def test_codesign_fused_identical_pareto_front():
    """run_codesign(use_fused_kernel=True) reproduces the exact search."""
    kw = dict(dataset="seeds", pop_size=6, n_generations=2,
              step_scale=0.1, max_steps=40)
    r_ref = codesign.run_codesign(codesign.CodesignConfig(**kw))
    r_fused = codesign.run_codesign(
        codesign.CodesignConfig(**kw, use_fused_kernel=True)
    )
    np.testing.assert_array_equal(r_fused.front_masks, r_ref.front_masks)
    np.testing.assert_array_equal(r_fused.front_cats, r_ref.front_cats)
    np.testing.assert_array_equal(r_fused.front_acc, r_ref.front_acc)
    assert r_fused.conv_acc == r_ref.conv_acc


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_auto_interpret_compiles_on_tpu_interprets_on_cpu(
    backend, interpret, monkeypatch
):
    monkeypatch.setattr(fq_ops.jax, "default_backend", lambda: backend)
    assert fq_ops._auto_interpret() is interpret


def test_auto_interpret_refuses_other_backends(monkeypatch):
    """No silent interpreted run on an accelerator the kernel was not built for."""
    monkeypatch.setattr(fq_ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        fq_ops._auto_interpret()
