"""The plain reference of the ADC-only genome (``genome_axes`` ``["adc"]``).

A configuration names its reference module (``"reference": "<name>"``),
``references/<name>.py``, which the check, the drivers and
``calibrate.py`` call and nothing else.  Nothing here imports the program.
Everything is written from the paper's description and the configuration
file.  The module's contract:

* ``load_dataset`` / ``split``: the synthetic UCI replicas and the 70/30
  stratified split, regenerated from their seeds (the inputs, as weights
  would be for a model);
* ``draw(rng, n, cfg)``: genomes as NSGA-II draws its generation 0;
* ``decode(masks, cats, cfg)``: genomes to the evaluator's row arrays, in
  the order level masks, weight bits, activation bits, batch size,
  epochs, lr, training seed (the crc32 of each genome's bytes); a genome
  with more axes adds its columns after these, in the trainer's order;
* ``make_qat_reference``: one genome's quantization-aware training run in
  straightforward ``jax.numpy``, vmapped over a block of genomes, at
  ``default_matmul_precision("highest")`` in float32 (the reference) or in
  bfloat16 throughout (the control);
* ``area(masks, cats, cfg)``: each genome's area as the search reports
  it; here the pruned flash-ADC area proxy, counted gate by gate.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


# -- inputs ---------------------------------------------------------------


def _monotone_warp(x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    out = np.empty_like(x)
    for f in range(x.shape[1]):
        mode = rng.integers(0, 4)
        c = x[:, f]
        if mode == 0:
            out[:, f] = c ** (1.0 + 1.5 * rng.uniform())
        elif mode == 1:
            out[:, f] = c ** (1.0 / (1.0 + 1.5 * rng.uniform()))
        elif mode == 2:
            out[:, f] = 0.5 + 0.5 * np.tanh(3.0 * (c - 0.5)) / np.tanh(1.5)
        else:
            out[:, f] = c
    return out


def load_dataset(ds: dict) -> tuple[np.ndarray, np.ndarray]:
    """The replica of one dataset: per-class Gaussian mixture, min-max
    normalised to [0, 1], each feature warped monotonically.  ``ds`` is the
    configuration's ``dataset`` group (features, classes, samples, seed)."""
    n_f, n_c, n_s = ds["n_features"], ds["n_classes"], ds["n_samples"]
    rng = np.random.default_rng(ds["seed"])
    per_class = np.full(n_c, n_s // n_c)
    per_class[: n_s - per_class.sum()] += 1
    means = rng.uniform(0.2, 0.8, size=(n_c, n_f)) + 0.35 * np.eye(n_c, n_f)
    xs, ys = [], []
    for c in range(n_c):
        a = rng.normal(size=(n_f, n_f))
        cov = 0.045 * (a @ a.T / n_f + 0.6 * np.eye(n_f))
        xs.append(rng.multivariate_normal(means[c], cov, size=per_class[c]))
        ys.append(np.full(per_class[c], c, dtype=np.int64))
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    x = (x - x.min(0)) / (x.max(0) - x.min(0) + 1e-12)
    x = _monotone_warp(x, rng)
    perm = rng.permutation(x.shape[0])
    return x[perm].astype(np.float32), y[perm]


def split(x: np.ndarray, y: np.ndarray, train_frac: float, seed: int):
    """Stratified random split: (x_train, y_train, x_test, y_test)."""
    rng = np.random.default_rng(seed)
    tr, te = [], []
    for c in np.unique(y):
        idx = np.where(y == c)[0]
        rng.shuffle(idx)
        k = int(round(train_frac * idx.size))
        tr.extend(idx[:k].tolist())
        te.extend(idx[k:].tolist())
    tr = np.asarray(tr)
    te = np.asarray(te)
    rng.shuffle(tr)
    rng.shuffle(te)
    return x[tr], y[tr], x[te], y[te]


# -- genomes --------------------------------------------------------------

CAT_GENES = ("weight_bits", "act_bits", "batch_size", "epochs", "lr")


def draw(rng: np.random.Generator, n: int, cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """``n`` genomes: (mask genes (n, features * 2^adc_bits) bool, categorical
    genes (n, 5) int64).  Each genome draws its mask density uniformly from
    the configuration's ``init_density`` band and keeps each ADC level gene
    with that probability; every categorical gene is uniform over its
    table."""
    n_mask = cfg["dataset"]["n_features"] << cfg["adc_bits"]
    lo, hi = cfg["init_density"]
    probs = rng.uniform(lo, hi, size=(n, 1))
    masks = rng.uniform(size=(n, n_mask)) < probs
    cats = np.stack(
        [rng.integers(0, len(cfg["genes"][g]), size=n) for g in CAT_GENES], axis=1
    ).astype(np.int64)
    return masks, cats


def genome_seeds(masks: np.ndarray, cats: np.ndarray) -> np.ndarray:
    """Training seed of each genome: crc32 of its bytes (bool mask genes,
    then int64 categorical genes), masked to 31 bits."""
    m = np.ascontiguousarray(np.asarray(masks, bool))
    c = np.ascontiguousarray(np.asarray(cats, np.int64))
    return np.asarray(
        [zlib.crc32(m[i].tobytes() + c[i].tobytes()) & 0x7FFFFFFF
         for i in range(m.shape[0])],
        np.int32,
    )


def decode(masks: np.ndarray, cats: np.ndarray, cfg: dict) -> tuple:
    """Genomes → the seven evaluator row arrays of the ADC-only genome:
    (level masks, weight bits, activation bits, batch size, epochs, lr,
    training seed)."""
    g = cfg["genes"]
    n_levels = 1 << cfg["adc_bits"]
    p = masks.shape[0]
    lvl = np.asarray(masks, bool).reshape(p, cfg["dataset"]["n_features"], n_levels).copy()
    lvl[:, :, 0] = True
    cats = np.asarray(cats, np.int64)
    return (
        lvl,
        np.asarray(g["weight_bits"], np.float32)[cats[:, 0]],
        np.asarray(g["act_bits"], np.float32)[cats[:, 1]],
        np.asarray(g["batch_size"], np.int32)[cats[:, 2]],
        np.asarray(g["epochs"], np.int32)[cats[:, 3]],
        np.asarray(g["lr"], np.float32)[cats[:, 4]],
        genome_seeds(masks, cats),
    )


# -- QAT ------------------------------------------------------------------


def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)


def _po2(w, bits):
    """sign(w) * 2^round(log2|w|), exponent in [1 - 2^(bits-1), 0];
    magnitudes below half the smallest power are 0.  Straight-through."""
    e_lo = 1.0 - jnp.exp2(bits - 1.0)
    mag = jnp.abs(w)
    e = jnp.clip(jnp.round(jnp.log2(jnp.maximum(mag, 1e-12))), e_lo, 0.0)
    q = jnp.where(mag < jnp.exp2(e_lo - 1.0), 0.0, jnp.sign(w) * jnp.exp2(e))
    return _ste(w, q.astype(w.dtype))


def _uniform(x, bits):
    scale = jnp.exp2(bits) - 1.0
    q = jnp.clip(jnp.round(x * scale), 0.0, scale) / scale
    return _ste(x, q.astype(x.dtype))


def adc_levels(x: jnp.ndarray, mask: jnp.ndarray, adc_bits: int) -> jnp.ndarray:
    """Pruned flash ADC: each input lands on the highest kept level whose
    threshold i / 2^N it reaches (level 0 when none); returns level / 2^N."""
    n = 1 << adc_bits
    x = jnp.clip(x, 0.0, 1.0 - 0.5 / n)
    thr = jnp.arange(1, n, dtype=jnp.float32) / n                # (n-1,)
    ids = jnp.arange(1, n, dtype=jnp.float32)
    fired = (x[..., None] >= thr) & mask[:, 1:]                  # (..., C, n-1)
    return jnp.max(jnp.where(fired, ids, 0.0), axis=-1) / n


def make_qat_reference(cfg: dict, n_train: int, dtype=jnp.float32, batch_share: float = 1.0):
    """``fn(x_tr, y_tr, x_te, y_te, eval_seed, *rows) -> test accuracy per
    row``, jitted and vmapped over the rows (the data is shared).  One row
    is one training run: ``max_steps`` minibatch steps of SGD with momentum
    and a cosine learning rate, frozen past the row's own step budget, with
    the loss the mean over the row's batch size.  ``batch_share`` below 1
    plants a fault: the loss is the mean over that share of the batch."""
    t = cfg["trainer"]
    sizes = tuple(cfg["layer_sizes"])
    max_steps, max_batch = t["max_steps"], t["max_batch"]
    adc_bits = cfg["adc_bits"]

    def train_one(x_tr, y_tr, x_te, y_te, eval_seed, mask, wb, ab, bs, ep, lr, seed):
        key = jax.random.fold_in(jax.random.PRNGKey(eval_seed), seed)
        keys = jax.random.split(key, len(sizes) - 1)
        params = []
        for i, (fi, fo) in enumerate(zip(sizes[:-1], sizes[1:])):
            bound = 1.0 / jnp.sqrt(jnp.float32(fi))
            w = jax.random.uniform(keys[i], (fi, fo), jnp.float32, -bound, bound)
            params.append((w.astype(dtype), jnp.zeros((fo,), dtype)))
        xq_tr = adc_levels(x_tr, mask, adc_bits).astype(dtype)
        xq_te = adc_levels(x_te, mask, adc_bits).astype(dtype)
        wb, ab, lr = wb.astype(dtype), ab.astype(dtype), lr.astype(dtype)

        def forward(p, h):
            for i, (w, b) in enumerate(p):
                h = h @ _po2(w, wb) + b
                if i < len(p) - 1:
                    h = _uniform(jnp.clip(jax.nn.relu(h), 0.0, 1.0), ab)
            return h

        def loss(p, xb, yb, wt):
            logp = jax.nn.log_softmax(forward(p, xb), axis=-1)
            ce = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
            return jnp.sum(wt * ce) / jnp.maximum(jnp.sum(wt), 1.0)

        per_epoch = jnp.ceil(n_train / bs.astype(jnp.float32))
        budget = jnp.minimum(
            jnp.maximum(ep.astype(jnp.float32) * per_epoch * t["step_scale"], 1.0),
            float(max_steps),
        )
        kept = jnp.ceil(bs.astype(jnp.float32) * batch_share)
        wt = (jnp.arange(max_batch) < kept).astype(dtype)

        def step(carry, s):
            p, v = carry
            idx = jax.random.randint(jax.random.fold_in(key, s), (max_batch,), 0, n_train)
            g = jax.grad(loss)(p, xq_tr[idx], y_tr[idx], wt)
            frac = jnp.minimum(s.astype(jnp.float32) / budget, 1.0)
            lr_s = lr * 0.5 * (1.0 + jnp.cos(jnp.pi * frac).astype(dtype))
            on = (s.astype(jnp.float32) < budget).astype(dtype)
            v = jax.tree.map(lambda vi, gi: t["momentum"] * vi - lr_s * gi, v, g)
            p = jax.tree.map(lambda pi, vi: pi + on * vi, p, v)
            return (p, v), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (params, _), _ = jax.lax.scan(step, (params, zeros), jnp.arange(max_steps))
        pred = jnp.argmax(forward(params, xq_te), axis=-1)
        return jnp.mean((pred == y_te).astype(jnp.float32))

    batched = jax.jit(jax.vmap(train_one, in_axes=(None,) * 5 + (0,) * 7))

    def run(x_tr, y_tr, x_te, y_te, eval_seed, *rows):
        with jax.default_matmul_precision("highest"):
            return np.asarray(batched(x_tr, y_tr, x_te, y_te, np.int32(eval_seed), *rows))

    return run


# -- area -----------------------------------------------------------------


def area(masks: np.ndarray, cats: np.ndarray, cfg: dict) -> np.ndarray:
    """Area of each genome: its ADC bank's alone, whatever its other genes."""
    return adc_area(masks, cfg["adc_bits"], cfg["area_gates"])


def adc_area(masks: np.ndarray, adc_bits: int, gates: dict) -> np.ndarray:
    """Area of each pruned ADC bank, channel by channel: one comparator per
    kept level >= 1, one AND per kept level but the topmost, and per output
    bit an OR tree over the kept levels whose code sets that bit."""
    n = 1 << adc_bits
    masks = np.asarray(masks, bool)
    out = np.zeros(masks.shape[0])
    for g in range(masks.shape[0]):
        total = 0.0
        for ch in masks[g].reshape(-1, n):
            kept = [i for i in range(1, n) if ch[i]]
            n_or = sum(max(sum(1 for i in kept if (i >> b) & 1) - 1, 0)
                       for b in range(adc_bits))
            total += (len(kept) * gates["comparator"] + max(len(kept) - 1, 0) * gates["and"]
                      + n_or * gates["or"])
        out[g] = total
    return out
