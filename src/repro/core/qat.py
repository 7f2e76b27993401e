"""Quantization-aware training primitives (pure JAX; QKeras-equivalent).

The paper trains bespoke printed MLPs with **8-bit power-of-2 fixed-point
weights and 4-bit inputs** (the [7] baseline), exploring weight/activation
precision as part of the GA chromosome.  We implement:

* :func:`quantize_pow2`       — po2 weight quantizer (sign * 2^e, e clipped
  to the exponent range representable in ``bits``), straight-through grad.
* :func:`quantize_uniform`    — symmetric uniform activation quantizer, STE.
* :class:`QuantMLP`           — the printed MLP forward pass with quant
  hooks at inputs (pruned ADC), weights (po2) and hidden activations.

All quantizers are `jit`/`vmap`-safe and take their precision as traced
*clip parameters* where the GA searches them, so a whole population with
heterogeneous precisions evaluates as ONE vmapped program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import adc, spans

__all__ = [
    "quantize_pow2",
    "quantize_uniform",
    "quantize_ternary",
    "quantize_layer_weights",
    "act_approx",
    "ACT_APPROX_FNS",
    "MLPConfig",
    "init_mlp",
    "mlp_forward",
    "cross_entropy",
    "accuracy",
]


def _ste(x: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    return x + jax.lax.stop_gradient(q - x)


def quantize_pow2(w: jnp.ndarray, bits: jnp.ndarray | int = 8) -> jnp.ndarray:
    """Power-of-2 quantizer: w -> sign(w) * 2^round(log2 |w|), STE gradient.

    ``bits`` bounds the exponent range: with b bits we store sign + a
    (b-1)-bit exponent offset covering e in [e_max - 2^(b-1) + 1, e_max]
    with e_max = 0 (weights normalised to [-1, 1]).  Magnitudes below the
    smallest representable power collapse to 0 (a free pruned connection in
    the printed circuit).
    """
    bits = jnp.asarray(bits, jnp.float32)
    e_lo = -(2.0 ** (bits - 1.0)) + 1.0  # smallest exponent kept
    mag = jnp.abs(w)
    e = jnp.clip(jnp.round(jnp.log2(jnp.maximum(mag, 1e-12))), e_lo, 0.0)
    q = jnp.sign(w) * jnp.exp2(e)
    q = jnp.where(mag < jnp.exp2(e_lo - 1.0), 0.0, q)
    return _ste(w, q)


def quantize_uniform(x: jnp.ndarray, bits: jnp.ndarray | int, signed: bool = False) -> jnp.ndarray:
    """Symmetric uniform quantizer with STE (activations / logits)."""
    bits = jnp.asarray(bits, jnp.float32)
    n = jnp.exp2(bits)
    if signed:
        scale = (n / 2.0) - 1.0
        q = jnp.clip(jnp.round(x * scale), -scale, scale) / scale
    else:
        scale = n - 1.0
        q = jnp.clip(jnp.round(x * scale), 0.0, scale) / scale
    return _ste(x, q)


def quantize_ternary(w: jnp.ndarray) -> jnp.ndarray:
    """Printed ternary weights {-s, 0, +s} with STE (arXiv 2508.19660).

    Per-tensor scale ``s = mean |w|`` over the non-pruned fraction and a
    relative zero-threshold of 0.7 * mean|w| — the classic TWN rule, which
    keeps ~2/3 of weights live on a uniform init.  A ternary crossbar
    drops the multi-level po2 resistor ladder entirely: each connection is
    one of {forward, absent, inverted} printed resistors.
    """
    mag = jnp.abs(w)
    thr = 0.7 * jnp.mean(mag)
    live = mag > thr
    scale = jnp.sum(jnp.where(live, mag, 0.0)) / jnp.maximum(
        jnp.sum(live.astype(w.dtype)), 1.0
    )
    q = jnp.where(live, jnp.sign(w) * scale, 0.0)
    return _ste(w, q)


def quantize_layer_weights(w: jnp.ndarray, bits: jnp.ndarray | float) -> jnp.ndarray:
    """Per-layer weight lowering keyed by a traced float bit width.

    ``bits > 0`` selects the po2 fixed-point quantizer at that width;
    ``bits == 0`` is the ternary sentinel (chromosome.TERNARY_BITS).  The
    select is branchless (both quantizers run under vmap) so heterogeneous
    populations stay ONE jitted program and the selected branch's values
    are bit-identical to calling that quantizer alone.
    """
    with spans.scope("wprec"):
        bits = jnp.asarray(bits, jnp.float32)
        po2 = quantize_pow2(w, jnp.maximum(bits, 1.0))
        tern = quantize_ternary(w)
        return jnp.where(bits > 0.0, po2, tern)


# --- printed activation approximations (arXiv 2312.17612) ---------------
#
# Each is a cheap printed-circuit stand-in for ReLU + the [0, 1] clip that
# precedes the act_bits re-digitisation.  Order must match
# chromosome.ACT_APPROX_CHOICES; index 0 is the exact baseline.  All are
# elementwise, jit/vmap-safe, and differentiable (step via STE) so the GA
# can flip them per hidden layer inside one traced program.


def _act_relu(h: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.relu(h)


def _act_sat01(h: jnp.ndarray) -> jnp.ndarray:
    # single printed source-follower stage: hard saturation at the rail
    return jnp.clip(h, 0.0, 1.0)


def _act_pwl2(h: jnp.ndarray) -> jnp.ndarray:
    # two-segment compressive PWL: slope 1 on [0, 0.5], slope 0.5 above —
    # a resistor-divider bend approximating the printed nonlinearity
    return jax.nn.relu(h) - 0.5 * jax.nn.relu(h - 0.5)


def _act_step(h: jnp.ndarray) -> jnp.ndarray:
    # binary comparator at the mid-rail; STE uses the sat01 surrogate grad
    return _ste(_act_sat01(h), (h > 0.5).astype(h.dtype))


ACT_APPROX_FNS = (_act_relu, _act_sat01, _act_pwl2, _act_step)


def act_approx(h: jnp.ndarray, sel: jnp.ndarray | int) -> jnp.ndarray:
    """Apply the activation approximation selected by index ``sel``.

    ``sel`` may be a traced int32 from the chromosome; under vmap,
    ``lax.switch`` lowers to computing every branch + select, so values of
    the selected branch match calling it directly, bit for bit.
    """
    with spans.scope("act"):
        sel = jnp.asarray(sel, jnp.int32)
        return jax.lax.switch(sel, ACT_APPROX_FNS, h)


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    """Bespoke printed-MLP topology + quantization knobs."""

    layer_sizes: tuple[int, ...]  # (in, hidden..., classes)
    adc_bits: int = 4
    weight_bits: int = 8
    act_bits: int = 4

    @property
    def n_inputs(self) -> int:
        return self.layer_sizes[0]

    @property
    def n_classes(self) -> int:
        return self.layer_sizes[-1]


def init_mlp(key: jax.Array, cfg: MLPConfig) -> dict:
    params = {}
    keys = jax.random.split(key, len(cfg.layer_sizes) - 1)
    for i, (fi, fo) in enumerate(zip(cfg.layer_sizes[:-1], cfg.layer_sizes[1:])):
        bound = 1.0 / jnp.sqrt(fi)
        params[f"w{i}"] = jax.random.uniform(keys[i], (fi, fo), jnp.float32, -bound, bound)
        params[f"b{i}"] = jnp.zeros((fo,), jnp.float32)
    return params


def mlp_forward(
    params: dict,
    x: jnp.ndarray,
    cfg: MLPConfig,
    mask: jnp.ndarray | None = None,
    weight_bits: jnp.ndarray | int | None = None,
    act_bits: jnp.ndarray | int | None = None,
    use_fused: bool = False,
    act_sel: jnp.ndarray | None = None,
    layer_weight_bits: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Quantized forward pass.  ``mask`` = (C, 2^adc_bits) pruned-ADC masks;
    None means the conventional (full) ADC.  Precisions default to cfg but
    may be traced scalars supplied by the GA chromosome.

    ``use_fused`` routes the pruned-ADC quantizer + first-layer matmul
    through the fused Pallas kernel (``kernels.fused_qat``) instead of the
    pure-JAX pair below — same values, same STE gradient, no HBM round-trip
    of the dequantized inputs.  Requires ``mask``; the conventional-ADC
    path is untouched.

    Generalized-genome axes (both default None, which selects the literal
    pre-axes code path at trace time — programs and values are unchanged
    unless a caller opts in):

    * ``act_sel`` — (n_hidden,) int32 indices into :data:`ACT_APPROX_FNS`,
      one per hidden layer (axis "act");
    * ``layer_weight_bits`` — (n_layers,) float32 per-layer widths routed
      through :func:`quantize_layer_weights` (0.0 = ternary, axis
      "wprec"); overrides the scalar ``weight_bits`` for every layer.
    """
    wb = cfg.weight_bits if weight_bits is None else weight_bits
    ab = cfg.act_bits if act_bits is None else act_bits
    n_layers = len(cfg.layer_sizes) - 1

    def layer_w(i):
        if layer_weight_bits is None:
            return quantize_pow2(params[f"w{i}"], wb)
        return quantize_layer_weights(params[f"w{i}"], layer_weight_bits[i])

    def hidden_act(h, i):
        if act_sel is not None:
            h = act_approx(h, act_sel[i])
        else:
            h = jax.nn.relu(h)
        # printed hidden activations are re-digitised at act_bits
        return quantize_uniform(jnp.clip(h, 0.0, 1.0), ab)

    start = 0
    if mask is None:
        with spans.scope("adc"):
            h = quantize_uniform(jnp.clip(x, 0.0, 1.0), cfg.adc_bits)
    elif use_fused:
        from repro.kernels import fused_qat  # deferred: kernels -> core is one-way

        with spans.scope("layer"):
            w0 = layer_w(0)
        with spans.scope("adc"):
            h = fused_qat.fused_qat_first_layer(x, mask, w0, params["b0"], cfg.adc_bits)
        if n_layers > 1:
            with spans.scope("layer"):
                h = hidden_act(h, 0)
        start = 1
    else:
        with spans.scope("adc"):
            h = adc.quantize_pruned_ste(x, mask, cfg.adc_bits)
    for i in range(start, n_layers):
        with spans.scope("layer"):
            h = h @ layer_w(i) + params[f"b{i}"]
            if i < n_layers - 1:
                h = hidden_act(h, i)
    return h


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
