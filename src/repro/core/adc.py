"""Flash-ADC digital twin with per-input level pruning.

The paper's central object (§II-A): an N-bit flash ADC exposes 2^N uniform
quantization levels over [0, Vref).  Level ``i`` (i >= 1) is produced by a
comparator at threshold ``i / 2^N``; level 0 is the all-comparators-low
state and has no comparator.  *Pruning* level ``i`` removes its comparator:
an input that would have landed on a pruned level falls to the next-lower
*kept* level, and the priority encoder emits the **original** binary code of
that kept level (so downstream arithmetic keeps the uniform value grid
``v = level / 2^N``).

Two equivalent implementations are provided:

* :func:`quantize_pruned`   — fast vectorised quantizer: the comparator
  bank and priority encoder as a compare-and-max over the level axis,
  ``max_i where(mask[c, i] & (x >= i·vref/2^N), i, 0)``.  This is what
  training uses; it is also the reference oracle for the Pallas kernels in
  ``repro.kernels.pruned_quant`` and ``repro.kernels.fused_qat``, which
  share its formula.
* :func:`circuit_simulate`  — bit-exact gate-level simulation of the pruned
  flash ADC (comparator bank -> thermometer code -> level-select ANDs ->
  OR-tree encoder).  Used only by property tests to prove the fast path is
  exactly the circuit.

Masks are boolean arrays of shape ``(..., 2^N)`` where ``mask[..., i]``
keeps level ``i``.  Bit 0 is forced to 1 everywhere (level 0 is not a
comparator and cannot be pruned).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ADCSpec",
    "force_level0",
    "kept_thresholds",
    "quantize_pruned",
    "quantize_pruned_ste",
    "thermometer_code",
    "circuit_simulate",
    "levels_to_values",
]


@dataclasses.dataclass(frozen=True)
class ADCSpec:
    """Static description of the ADC frontend of one model.

    Attributes:
      n_bits:     flash-ADC resolution N (levels = 2^N).
      n_channels: number of analog input channels (one bespoke ADC each).
      vref:       full-scale reference; inputs are normalised to [0, vref).
    """

    n_bits: int = 4
    n_channels: int = 1
    vref: float = 1.0

    @property
    def n_levels(self) -> int:
        return 1 << self.n_bits

    def full_mask(self) -> jnp.ndarray:
        return jnp.ones((self.n_channels, self.n_levels), dtype=bool)


def force_level0(mask: jnp.ndarray) -> jnp.ndarray:
    """Level 0 is the comparator-free ground state: always kept."""
    return mask.at[..., 0].set(True)


def levels_to_values(levels: jnp.ndarray, n_bits: int, vref: float = 1.0) -> jnp.ndarray:
    """Dequantize level indices back onto the uniform value grid."""
    return levels.astype(jnp.float32) * (vref / (1 << n_bits))


def kept_thresholds(mask: jnp.ndarray, n_bits: int, vref: float = 1.0) -> jnp.ndarray:
    """Per-channel sorted threshold table, pruned entries pushed to +inf.

    Returns ``(..., 2^N - 1)`` of thresholds ``i * vref / 2^N`` for kept
    levels ``i >= 1``; pruned slots hold ``+inf`` so a searchsorted /
    compare-count against the table never counts them.
    """
    n = 1 << n_bits
    lvl = jnp.arange(1, n, dtype=jnp.float32) * (vref / n)
    keep = mask[..., 1:]
    thr = jnp.where(keep, lvl, jnp.inf)
    # Pruned slots are +inf which sorts to the end; kept thresholds are
    # already in ascending order, so a sort keeps them stable.
    return jnp.sort(thr, axis=-1)


@partial(jax.jit, static_argnames=("n_bits",))
def quantize_pruned(
    x: jnp.ndarray, mask: jnp.ndarray, n_bits: int, vref: float = 1.0
) -> jnp.ndarray:
    """Quantize ``x`` through per-channel pruned flash ADCs.

    The comparator bank plus priority encoder: the output is the largest
    kept level whose threshold ``x`` reaches, a masked max over the level
    axis (no sort, no gather).

    Args:
      x:    (..., C) analog inputs in [0, vref).
      mask: (C, 2^N) boolean keep-masks (bit 0 implicitly forced).
    Returns:
      (..., C) int32 level indices on the ORIGINAL 2^N grid.
    """
    mask = force_level0(mask)
    n = 1 << n_bits
    x = jnp.clip(x, 0.0, vref * (1.0 - 0.5 / n))
    lvl = jnp.arange(1, n, dtype=jnp.float32) * (vref / n)
    lvl_ids = jnp.arange(1, n, dtype=jnp.int32)
    fired = (x[..., None] >= lvl) & mask[..., 1:]  # kept comparators that fire
    return jnp.max(jnp.where(fired, lvl_ids, 0), axis=-1)


def quantize_pruned_ste(
    x: jnp.ndarray, mask: jnp.ndarray, n_bits: int, vref: float = 1.0
) -> jnp.ndarray:
    """Dequantized pruned-ADC output with a straight-through gradient.

    Forward: v = level(x) * vref / 2^N.  Backward: identity w.r.t. ``x``
    (the standard QAT STE; the mask itself is not differentiable — it is
    searched by the GA, see ``core.nsga2`` / ``core.codesign``).
    """
    levels = quantize_pruned(x, mask, n_bits, vref)
    v = levels_to_values(levels, n_bits, vref)
    return x + jax.lax.stop_gradient(v - x)


# ---------------------------------------------------------------------------
# Gate-level circuit simulation (tests only — deliberately literal).
# ---------------------------------------------------------------------------

def thermometer_code(x: np.ndarray, mask: np.ndarray, n_bits: int, vref: float = 1.0) -> np.ndarray:
    """Comparator-bank outputs of the pruned ADC, one bit per KEPT level >=1.

    Returns (..., C, 2^N - 1) uint8; pruned comparator positions are 0
    (their comparator does not exist).
    """
    n = 1 << n_bits
    x = np.clip(np.asarray(x, np.float64), 0.0, vref * (1.0 - 0.5 / n))
    thr = np.arange(1, n, dtype=np.float64) * (vref / n)
    fired = (x[..., None] >= thr).astype(np.uint8)
    keep = np.asarray(mask)[..., 1:].astype(np.uint8)
    return fired * keep


def circuit_simulate(x: np.ndarray, mask: np.ndarray, n_bits: int, vref: float = 1.0) -> np.ndarray:
    """Bit-exact pruned flash ADC: comparators -> priority encoder -> binary.

    Mirrors Fig. 3(b) of the paper: level-select signal
    ``s_i = c_i AND NOT c_j`` where ``c_j`` is the next *kept* comparator
    above ``i`` (for the topmost kept level, ``s_i = c_i``); output bit
    ``a_b = OR_{kept i with bit b set} s_i``.
    Returns (..., C) int64 level ids.
    """
    n = 1 << n_bits
    mask = np.asarray(mask).astype(bool).copy()
    mask[..., 0] = True
    tc = thermometer_code(x, mask, n_bits, vref)  # (..., C, n-1)
    batch_shape = tc.shape[:-2] if tc.ndim >= 2 else ()
    C = mask.shape[0] if mask.ndim == 2 else 1
    mask2 = mask.reshape(C, n)
    tc = tc.reshape(batch_shape + (C, n - 1)) if tc.ndim >= 2 else tc

    out = np.zeros(tc.shape[:-1], dtype=np.int64)
    for c in range(C):
        kept = [i for i in range(1, n) if mask2[c, i]]
        # level-select AND gates
        s = {}
        for idx, i in enumerate(kept):
            ci = tc[..., c, i - 1]
            if idx + 1 < len(kept):
                cj = tc[..., c, kept[idx + 1] - 1]
                s[i] = ci & (1 - cj)
            else:
                s[i] = ci
        # OR-tree encoder per output bit
        bits = np.zeros(tc.shape[:-2] + (n_bits,), dtype=np.uint8)
        for b in range(n_bits):
            acc = np.zeros(tc.shape[:-2], dtype=np.uint8)
            for i in kept:
                if (i >> b) & 1:
                    acc = acc | s[i]
            bits[..., b] = acc
        out[..., c] = sum((bits[..., b].astype(np.int64) << b) for b in range(n_bits))
    return out
