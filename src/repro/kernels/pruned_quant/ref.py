"""Pure-jnp oracle for the pruned_quant kernel.

Independent of both the kernel and the compare-and-max path in
``core.adc`` (the tests cross-check all three).
"""

from __future__ import annotations

import jax.numpy as jnp


def make_tables(mask: jnp.ndarray, n_bits: int, vref: float = 1.0):
    """mask (C, 2^N) -> (thr (C, 2^N-1) +inf-padded, ids (C, 2^N-1) int32)."""
    n = 1 << n_bits
    mask = mask.at[..., 0].set(True)
    lvl = jnp.arange(1, n, dtype=jnp.int32)
    keep = mask[..., 1:]
    thr = jnp.where(keep, lvl.astype(jnp.float32) * (vref / n), jnp.inf)
    ids = jnp.where(keep, lvl, 0)
    return thr, ids


def pruned_quantize_ref(x: jnp.ndarray, thr: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """level(b,c) = max_t ids[c,t] * [x >= thr[c,t]]  (the paper's encoder)."""
    fired = x[..., None] >= thr  # (..., C, T)
    return jnp.max(jnp.where(fired, ids, 0), axis=-1).astype(jnp.int32)
