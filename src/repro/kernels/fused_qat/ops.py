"""Public custom-VJP wrapper for the fused pruned-ADC QAT first layer.

``fused_qat_first_layer`` is the drop-in for the unfused pair

    h = adc.quantize_pruned_ste(x, mask, n_bits)   # comparator bank, STE
    h @ w + b                                       # first-layer matmul

inside ``core.qat.mlp_forward``.  The po2 *weight* quantizer stays outside
(its own STE chains through the ``w`` cotangent returned here), so callers
pass the already-quantized weight.  The straight-through estimator for the
*input* quantizer is implemented by the custom VJP: the forward runs the
fused compare→encode→dequant→matmul kernel, the backward treats the
quantizer as identity and runs the fused gradient kernel (dx = g @ w^T,
dw = v^T @ g with the comparator bank recomputed — see the DESIGN note in
``fused_qat.py``).

``vmap`` support comes for free: Pallas's batching rule turns a population
axis into an extra sequential grid dimension and ``custom_vjp`` batches the
fwd/bwd pair, which is exactly how ``core.trainer``'s population-vmapped
evaluator consumes this op with heterogeneous per-genome threshold tables.

``interpret=None`` picks the mode from the backend: compiled on TPU, the
Pallas interpreter on CPU (the test backend — same kernel code, executed
serially with jnp semantics), and an error on any other backend rather
than a silent interpreted run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pruned_quant import ref as pq_ref
from repro.kernels.fused_qat.fused_qat import (
    DEFAULT_BLOCK_B,
    fused_qat_backward_pallas,
    fused_qat_forward_pallas,
)

__all__ = ["fused_qat_first_layer"]


def _auto_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"fused_qat compiles for TPU and interprets on CPU; backend "
            f"{backend!r} is neither"
        )
    return backend == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(x, thr, ids, w, b, scale, block_b, interpret):
    return fused_qat_forward_pallas(
        x, thr, ids, w, b, scale=scale, block_b=block_b, interpret=interpret
    )


def _fused_fwd(x, thr, ids, w, b, scale, block_b, interpret):
    out = fused_qat_forward_pallas(
        x, thr, ids, w, b, scale=scale, block_b=block_b, interpret=interpret
    )
    # residuals: inputs only — the dequantized activation is deliberately
    # NOT saved (the backward kernel recomputes it from x in VMEM)
    return out, (x, thr, ids, w)


def _fused_bwd(scale, block_b, interpret, res, g):
    x, thr, ids, w = res
    dx, dw = fused_qat_backward_pallas(
        x, thr, ids, w, g, scale=scale, block_b=block_b, interpret=interpret
    )
    # thr/ids are GA-searched tables, not trained: zero/symbolic-zero cotangents
    return dx, jnp.zeros_like(thr), None, dw, jnp.sum(g, axis=0)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_qat_first_layer(
    x: jnp.ndarray,
    mask: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    n_bits: int = 4,
    vref: float = 1.0,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused pruned-ADC quantize + first-layer QAT matmul with STE gradient.

    Args:
      x:    (..., C) analog inputs in [0, vref); leading axes are flattened
            into the kernel's batch dimension.
      mask: (C, 2^N) boolean keep-masks (level 0 implicitly forced).
      w:    (C, F) first-layer weights, already po2-quantized by the caller.
      b:    (F,) bias.
      n_bits: flash-ADC resolution N.
    Returns: (..., F) float32 pre-activations.
    """
    thr, ids = pq_ref.make_tables(mask, n_bits, vref)
    lead = x.shape[:-1]
    C = x.shape[-1]
    xf = x.reshape((-1, C))
    interpret = _auto_interpret() if interpret is None else interpret
    out = _fused(xf, thr, ids, w, b, vref / (1 << n_bits), block_b, interpret)
    return out.reshape(lead + (w.shape[1],))
