"""Generalized approximation genome: lowering, encoding, and regression.

Three layers of guarantees for the multi-axis search space:

* numerics — every activation approximation and weight-precision lowering
  in ``core.qat`` agrees with an explicit NumPy reference on exhaustive
  small-N grids, including through the vmapped ``lax.switch`` path;
* encoding — ``core.chromosome`` round-trips genomes across every axis
  subset, all-zero genes decode to the exact pre-axes defaults, and the
  ADC-only layout is byte-identical to the legacy constants;
* regression — an ADC-only ``run_codesign`` reproduces the pre-axes
  search bit for bit (front, memo insertion order, counters) against an
  inline reference pipeline built from the raw engine pieces, and a
  full-axes run produces a valid joint Pareto front.
"""

import itertools
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import area, chromosome, codesign, nsga2, qat, trainer
from repro.data import uci_synth

# ---------------------------------------------------------------------------
# activation approximations vs NumPy reference
# ---------------------------------------------------------------------------

_GRID = np.linspace(-2.0, 2.0, 41).astype(np.float32)


def _np_act_reference(name: str, x: np.ndarray) -> np.ndarray:
    if name == "relu":
        return np.maximum(x, 0.0)
    if name == "sat01":
        return np.clip(x, 0.0, 1.0)
    if name == "pwl2":
        return np.maximum(x, 0.0) - 0.5 * np.maximum(x - 0.5, 0.0)
    if name == "step":
        return (x > 0.5).astype(np.float32)
    raise AssertionError(name)


@pytest.mark.ci
@pytest.mark.parametrize("idx,name", list(enumerate(chromosome.ACT_APPROX_CHOICES)))
def test_act_approx_matches_numpy_reference(idx, name):
    got = np.asarray(qat.ACT_APPROX_FNS[idx](jnp.asarray(_GRID)))
    np.testing.assert_allclose(got, _np_act_reference(name, _GRID), atol=1e-6)


@pytest.mark.ci
@pytest.mark.parametrize("idx", range(len(chromosome.ACT_APPROX_CHOICES)))
def test_act_approx_switch_bit_identical_to_direct_call(idx):
    """The traced selector must return the selected branch's exact values,
    including under vmap (where switch lowers to compute-all + select)."""
    direct = np.asarray(qat.ACT_APPROX_FNS[idx](jnp.asarray(_GRID)))
    via_switch = np.asarray(qat.act_approx(jnp.asarray(_GRID), idx))
    assert (direct == via_switch).all()
    batch = jnp.stack([jnp.asarray(_GRID)] * 3)
    sels = jnp.full((3,), idx, jnp.int32)
    vm = np.asarray(jax.vmap(qat.act_approx)(batch, sels))
    assert (vm == direct[None]).all()


@pytest.mark.ci
def test_act_approx_gradients_are_finite_and_nonzero():
    """Every approximation must be trainable (step via its STE surrogate)."""
    for idx in range(len(chromosome.ACT_APPROX_CHOICES)):
        g = np.asarray(
            jax.grad(lambda x: jnp.sum(qat.act_approx(x, idx)))(
                jnp.asarray(_GRID)
            )
        )
        assert np.isfinite(g).all()
        assert np.abs(g).sum() > 0


# ---------------------------------------------------------------------------
# weight-precision lowerings vs NumPy reference
# ---------------------------------------------------------------------------


def _np_pow2_reference(w: np.ndarray, bits: float) -> np.ndarray:
    e_lo = -(2.0 ** (bits - 1.0)) + 1.0
    mag = np.abs(w)
    e = np.clip(np.round(np.log2(np.maximum(mag, 1e-12))), e_lo, 0.0)
    q = np.sign(w) * np.exp2(e)
    return np.where(mag < np.exp2(e_lo - 1.0), 0.0, q).astype(np.float32)


def _np_ternary_reference(w: np.ndarray) -> np.ndarray:
    mag = np.abs(w)
    thr = 0.7 * mag.mean()
    live = mag > thr
    scale = mag[live].sum() / max(live.sum(), 1.0)
    return np.where(live, np.sign(w) * scale, 0.0).astype(np.float32)


@pytest.mark.ci
def test_quantize_ternary_matches_numpy_reference():
    rng = np.random.default_rng(0)
    for _ in range(5):
        w = rng.uniform(-1, 1, (7, 5)).astype(np.float32)
        got = np.asarray(qat.quantize_ternary(jnp.asarray(w)))
        np.testing.assert_allclose(got, _np_ternary_reference(w), atol=1e-6)


@pytest.mark.ci
def test_quantize_ternary_codes_are_three_valued():
    w = np.random.default_rng(1).uniform(-1, 1, (64,)).astype(np.float32)
    q = np.asarray(qat.quantize_ternary(jnp.asarray(w)))
    assert len(np.unique(np.round(q, 6))) <= 3


@pytest.mark.ci
@pytest.mark.parametrize("bits", chromosome.WPREC_BITS)
def test_quantize_layer_weights_selects_correct_branch(bits):
    rng = np.random.default_rng(2)
    w = rng.uniform(-1, 1, (9, 4)).astype(np.float32)
    got = np.asarray(qat.quantize_layer_weights(jnp.asarray(w), bits))
    if bits > 0:
        want = _np_pow2_reference(w, bits)
        also = np.asarray(qat.quantize_pow2(jnp.asarray(w), bits))
    else:
        want = _np_ternary_reference(w)
        also = np.asarray(qat.quantize_ternary(jnp.asarray(w)))
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert (got == also).all()  # branchless select is value-exact


@pytest.mark.ci
def test_quantize_layer_weights_gradient_is_ste():
    w = jnp.asarray(np.random.default_rng(3).uniform(-1, 1, (6,)), jnp.float32)
    for bits in chromosome.WPREC_BITS:
        g = np.asarray(jax.grad(lambda x: jnp.sum(qat.quantize_layer_weights(x, bits)))(w))
        np.testing.assert_allclose(g, np.ones_like(g), atol=1e-6)


# ---------------------------------------------------------------------------
# genome encode/decode across axis subsets
# ---------------------------------------------------------------------------

SUBSETS = [("adc",), ("adc", "act"), ("adc", "wprec"), ("adc", "act", "wprec")]


@pytest.mark.ci
def test_normalize_axes_accepts_strings_and_canonicalises_order():
    assert chromosome.normalize_axes("wprec,adc,act") == ("adc", "act", "wprec")
    assert chromosome.normalize_axes(("act", "adc")) == ("adc", "act")
    with pytest.raises(ValueError):
        chromosome.normalize_axes(("act",))  # adc mandatory
    with pytest.raises(ValueError):
        chromosome.normalize_axes("adc,bogus")


@pytest.mark.ci
def test_adc_only_layout_is_the_legacy_one():
    assert chromosome.cat_cardinalities(("adc",), n_layers=2) == chromosome.CAT_CARDINALITIES
    assert chromosome.cat_cardinalities(("adc",), n_layers=7) == chromosome.CAT_CARDINALITIES


@pytest.mark.ci
@pytest.mark.parametrize("axes", SUBSETS)
@pytest.mark.parametrize("n_layers", [2, 3])
def test_encode_decode_round_trip(axes, n_layers):
    rng = np.random.default_rng(7)
    cards = chromosome.cat_cardinalities(axes, n_layers)
    P, C, bits = 5, 3, 3
    masks = rng.integers(0, 2, (P, chromosome.n_mask_bits(C, bits))).astype(bool)
    cats = np.stack([rng.integers(0, c, P) for c in cards], axis=1)
    dec = chromosome.decode_batch(masks, cats, C, bits, axes=axes, n_layers=n_layers)
    groups = chromosome.split_cats(cats, axes, n_layers)
    # base genes round-trip through the choice tables
    assert (dec["weight_bits"] == np.asarray(chromosome.WEIGHT_BITS_CHOICES)[cats[:, 0]]).all()
    assert (dec["lr"] == np.float32(chromosome.LR_CHOICES)[cats[:, 4]]).all()
    if "act" in axes:
        assert dec["act_sel"].shape == (P, n_layers - 1)
        assert (dec["act_sel"] == groups["act"]).all()
    else:
        assert "act_sel" not in dec
    if "wprec" in axes:
        assert dec["wprec"].shape == (P, n_layers)
        wprec_bits = np.asarray(chromosome.WPREC_BITS, np.float32)
        assert (dec["wprec"] == wprec_bits[groups["wprec"]]).all()
    else:
        assert "wprec" not in dec
    # scalar decode agrees with row 0 of the batch decode
    one = chromosome.decode(masks[0], cats[0], C, bits, axes=axes, n_layers=n_layers)
    assert (one.mask == dec["masks"][0]).all()
    assert one.weight_bits == dec["weight_bits"][0]
    if "wprec" in axes:
        assert (one.wprec == dec["wprec"][0]).all()


@pytest.mark.ci
@pytest.mark.parametrize("axes", SUBSETS)
def test_all_zero_genes_decode_to_exact_defaults(axes):
    C, bits, n_layers = 2, 2, 2
    cards = chromosome.cat_cardinalities(axes, n_layers)
    masks = np.ones((1, chromosome.n_mask_bits(C, bits)), bool)
    dec = chromosome.decode_batch(
        masks, np.zeros((1, len(cards)), np.int64), C, bits, axes=axes, n_layers=n_layers
    )
    assert dec["weight_bits"][0] == 8 and dec["act_bits"][0] == 4
    if "act" in axes:
        assert (dec["act_sel"] == 0).all()  # exact ReLU
    if "wprec" in axes:
        assert (dec["wprec"] == 8.0).all()  # exact po2-8


@pytest.mark.ci
def test_decode_rejects_wrong_gene_count():
    masks = np.ones((1, chromosome.n_mask_bits(2, 2)), bool)
    with pytest.raises(ValueError):
        chromosome.decode_batch(
            masks, np.zeros((1, 5), np.int64), 2, 2,
            axes=("adc", "act", "wprec"), n_layers=2,
        )


# ---------------------------------------------------------------------------
# forward-pass equivalence: default gene values select the pre-axes program
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_mlp_forward_default_genes_bit_identical_to_legacy_path():
    rng = np.random.default_rng(11)
    cfg = qat.MLPConfig((4, 6, 3))
    params = qat.init_mlp(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.uniform(0, 1, (8, 4)), jnp.float32)
    mask = jnp.ones((4, 16), bool)
    legacy = np.asarray(qat.mlp_forward(params, x, cfg, mask, 8.0, 4.0))
    via_axes = np.asarray(
        qat.mlp_forward(
            params, x, cfg, mask, 8.0, 4.0,
            act_sel=jnp.zeros((1,), jnp.int32),
            layer_weight_bits=jnp.asarray([8.0, 8.0]),
        )
    )
    assert (legacy == via_axes).all()


def test_exhaustive_small_net_agreement_all_axis_combos():
    """Every (activation, wprec) gene combo through mlp_forward must equal
    a NumPy re-implementation of the quantized forward pass."""
    rng = np.random.default_rng(13)
    cfg = qat.MLPConfig((3, 4, 2), adc_bits=2)
    params = qat.init_mlp(jax.random.PRNGKey(1), cfg)
    p_np = {k: np.asarray(v) for k, v in params.items()}
    x = rng.uniform(0, 1, (5, 3)).astype(np.float32)
    mask = np.ones((3, 4), bool)

    def np_forward(act_idx, wbits):
        def quant_in(v):  # full mask -> floor onto the level grid i/2^N
            n = 1 << cfg.adc_bits
            thr = np.arange(1, n) / n
            return np.sum(v[..., None] >= thr, axis=-1) / n

        def quant_w(w, b):
            return _np_pow2_reference(w, b) if b > 0 else _np_ternary_reference(w)

        h = quant_in(x)
        h = h @ quant_w(p_np["w0"], wbits[0]) + p_np["b0"]
        h = _np_act_reference(chromosome.ACT_APPROX_CHOICES[act_idx], h)
        n = 2.0**cfg.act_bits
        h = np.clip(np.round(np.clip(h, 0, 1) * (n - 1)), 0, n - 1) / (n - 1)
        return h @ quant_w(p_np["w1"], wbits[1]) + p_np["b1"]

    for act_idx, w0, w1 in itertools.product(
        range(len(chromosome.ACT_APPROX_CHOICES)),
        chromosome.WPREC_BITS,
        chromosome.WPREC_BITS,
    ):
        got = np.asarray(
            qat.mlp_forward(
                params, jnp.asarray(x), cfg, jnp.asarray(mask), 8.0, 4.0,
                act_sel=jnp.asarray([act_idx], jnp.int32),
                layer_weight_bits=jnp.asarray([w0, w1], jnp.float32),
            )
        )
        np.testing.assert_allclose(
            got, np_forward(act_idx, (w0, w1)), atol=1e-5,
            err_msg=f"act={act_idx} wprec=({w0},{w1})",
        )


# ---------------------------------------------------------------------------
# area model: genome costing
# ---------------------------------------------------------------------------


@pytest.mark.ci
def test_mlp_genome_cost_defaults_match_scalar_proxy():
    layers = [7, 9, 4]
    a, p = area.mlp_pow2_cost(layers)
    ab, pb = area.mlp_genome_cost_batch(
        layers, np.asarray([8.0, 8.0]), np.asarray([4.0, 4.0])
    )
    np.testing.assert_allclose(ab, a)
    np.testing.assert_allclose(pb, p)


@pytest.mark.ci
def test_genome_area_decreases_with_cheaper_choices():
    layers = [5, 8, 3]
    masks = np.ones((1, 5, 16), bool)
    wb, ab = np.asarray([8.0]), np.asarray([4.0])
    base = area.genome_area_batch(masks, 4, layers, wb, ab)[0][0]
    tern = area.genome_area_batch(
        masks, 4, layers, wb, ab, wprec=np.asarray([[0.0, 0.0]])
    )[0][0]
    cheap_act = area.genome_area_batch(
        masks, 4, layers, wb, ab, act_sel=np.asarray([[3]])
    )[0][0]
    assert tern < base
    assert cheap_act < base
    both = area.genome_area_batch(
        masks, 4, layers, wb, ab,
        act_sel=np.asarray([[3]]), wprec=np.asarray([[0.0, 0.0]]),
    )[0][0]
    assert both < min(tern, cheap_act)


# ---------------------------------------------------------------------------
# bit-for-bit regression: ADC-only run_codesign == inline reference pipeline
# ---------------------------------------------------------------------------


def _reference_adc_only_search(cfg: codesign.CodesignConfig, memo_sink: dict):
    """The PR 7-era ADC-only pipeline, rebuilt inline from raw pieces:
    decode (no axes) -> crc32 genome seeds -> population evaluator (seven
    arrays) -> (1 - acc, area / conv_area) -> memoized NSGA2."""
    X, y, spec = uci_synth.load(cfg.dataset)
    X_tr, y_tr, X_te, y_te = uci_synth.stratified_split(X, y, 0.7, cfg.seed)
    mlp_cfg = qat.MLPConfig(
        layer_sizes=(spec.n_features, spec.hidden, spec.n_classes),
        adc_bits=cfg.adc_bits,
    )
    ev = trainer.make_population_evaluator(
        X_tr, y_tr, X_te, y_te, mlp_cfg,
        trainer.EvalConfig(
            max_steps=cfg.max_steps, step_scale=cfg.step_scale, seed=cfg.seed
        ),
    )
    conv_area, _ = area.conventional_cost(spec.n_features, cfg.adc_bits)

    def evaluate(mask_genes, cat_genes):
        dec = chromosome.decode_batch(
            mask_genes, cat_genes, spec.n_features, cfg.adc_bits
        )
        keys = nsga2.genome_keys(mask_genes, cat_genes)
        seeds = np.asarray([zlib.crc32(k) & 0x7FFFFFFF for k in keys], np.int32)
        accs = np.asarray(
            ev(
                dec["masks"], dec["weight_bits"], dec["act_bits"],
                dec["batch_size"], dec["epochs"], dec["lr"], seeds,
            )
        )
        areas, _ = area.adc_cost_batch(dec["masks"], cfg.adc_bits)
        return np.stack([1.0 - accs, areas / conv_area], axis=1)

    ga = nsga2.NSGA2(
        n_mask_bits=chromosome.n_mask_bits(spec.n_features, cfg.adc_bits),
        cat_cardinalities=chromosome.CAT_CARDINALITIES,
        evaluate=evaluate,
        cfg=nsga2.NSGA2Config(
            pop_size=cfg.pop_size, n_generations=cfg.n_generations,
            seed=cfg.seed, memoize=True,
        ),
    )
    out = ga.run()
    memo_sink.update(ga.memo)
    return out


def test_adc_only_codesign_bit_identical_to_pr7_reference(tmp_path):
    cfg = codesign.CodesignConfig(
        dataset="seeds", pop_size=8, n_generations=3,
        step_scale=0.05, max_steps=30,
        memo_path=str(tmp_path / "memo"),
    )
    assert cfg.axes() == ("adc",)
    ref_memo: dict = {}
    ref = _reference_adc_only_search(cfg, ref_memo)
    res = codesign.run_codesign(cfg)
    # front: same genomes, same objective values, same order
    assert (np.asarray(ref["cats"]) == np.asarray(res.front_cats)).all()
    ref_dec = chromosome.decode_batch(
        ref["masks"], ref["cats"], res.spec.n_features, cfg.adc_bits
    )
    assert (ref_dec["masks"] == res.front_masks).all()
    np.testing.assert_array_equal(1.0 - ref["objs"][:, 0], res.front_acc)
    # counters
    assert int(ref["n_evaluations"]) == res.n_evaluations
    assert int(ref["n_memo_hits"]) == res.n_memo_hits
    # memo: same keys in the same insertion order, same cached objectives
    from repro.core import memo_store

    saved = memo_store.load_memo(str(tmp_path / "memo"), cfg.memo_fingerprint())
    assert list(saved.keys()) == list(ref_memo.keys())
    for k in ref_memo:
        np.testing.assert_array_equal(saved[k], ref_memo[k])


def test_full_axes_codesign_produces_valid_joint_front():
    cfg = codesign.CodesignConfig(
        dataset="seeds", pop_size=8, n_generations=3,
        step_scale=0.05, max_steps=30, genome_axes="adc,act,wprec",
    )
    res = codesign.run_codesign(cfg)
    assert res.genome_axes == ("adc", "act", "wprec")
    assert res.front_acc.size >= 1
    assert res.front_cats.shape[1] == len(
        chromosome.cat_cardinalities(res.genome_axes, 2)
    )
    assert (res.front_area > 0).all()
    assert np.isfinite(res.front_acc).all()
    # the front is mutually non-dominated in (1 - acc, area)
    objs = np.stack([1.0 - res.front_acc, res.front_area], axis=1)
    for i, j in itertools.permutations(range(len(objs)), 2):
        assert not (
            (objs[i] <= objs[j]).all() and (objs[i] < objs[j]).any()
        ), "dominated point on the joint front"


@pytest.mark.ci
def test_memo_fingerprint_only_widens_when_axes_do():
    adc = codesign.CodesignConfig(dataset="seeds")
    full = codesign.CodesignConfig(dataset="seeds", genome_axes=("adc", "act", "wprec"))
    assert "genome_axes" not in adc.memo_fingerprint()
    assert full.memo_fingerprint()["genome_axes"] == ["adc", "act", "wprec"]
    assert "genome_axes" not in adc.search_fingerprint()


@pytest.mark.ci
def test_fingerprints_record_a_matmul_precision_other_than_default():
    cfgs = [codesign.CodesignConfig(dataset="seeds"),
            codesign.CodesignConfig(dataset="cardio", genome_axes=("adc", "act", "wprec"))]
    plain = {"dataset": "seeds", "adc_bits": 4, "step_scale": 1.0, "max_steps": 600, "seed": 0}
    # at JAX's default precision every configuration's fingerprints are
    # what they were before the key existed, to the byte
    assert json.dumps(cfgs[0].memo_fingerprint()) == json.dumps(plain)
    before = [(json.dumps(c.memo_fingerprint()), json.dumps(c.search_fingerprint()))
              for c in cfgs]
    for prec in ("default", "highest", "bfloat16"):
        with jax.default_matmul_precision(prec):
            for c, (memo, search) in zip(cfgs, before):
                fp, sfp = c.memo_fingerprint(), c.search_fingerprint()
                if prec == "default":
                    assert (json.dumps(fp), json.dumps(sfp)) == (memo, search)
                else:
                    # a memo kept at one precision is never read at another
                    assert fp.pop("matmul_precision") == prec
                    assert sfp.pop("matmul_precision") == prec
                    assert (json.dumps(fp), json.dumps(sfp)) == (memo, search)
