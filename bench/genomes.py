"""Genomes as NSGA-II draws its generation 0, made from a seed.

Each genome draws its mask density uniformly from the configuration's
``init_density`` band and keeps each ADC level gene with that probability;
every categorical gene is uniform over its table.  The benchmark decodes
them itself (``reference.decode``), so the program receives only rows.
"""

from __future__ import annotations

import numpy as np

CAT_GENES = ("weight_bits", "act_bits", "batch_size", "epochs", "lr")


def draw(rng: np.random.Generator, n: int, cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """``n`` genomes: (mask genes (n, features * 2^adc_bits) bool, categorical
    genes (n, 5) int64)."""
    n_mask = cfg["dataset"]["n_features"] << cfg["adc_bits"]
    lo, hi = cfg["init_density"]
    probs = rng.uniform(lo, hi, size=(n, 1))
    masks = rng.uniform(size=(n, n_mask)) < probs
    cats = np.stack(
        [rng.integers(0, len(cfg["genes"][g]), size=n) for g in CAT_GENES], axis=1
    ).astype(np.int64)
    return masks, cats
