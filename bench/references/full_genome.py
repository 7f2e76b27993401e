"""The plain reference of the three-axis genome (``genome_axes``
``["adc", "act", "wprec"]``), in the contract of ``adc_genome``.

The genome is the ADC-only genome's (per-input level masks, then the five
QAT genes) followed by one activation-circuit gene per hidden layer and
one weight-precision gene per layer.  The inputs, the pruned-ADC levels,
the genome's training seed and the ADC bank's gate count are the ADC-only
genome's, loaded from ``adc_genome``; nothing here imports the program.
Everything else is written from the papers and the configuration file:

* ``draw``: the five base genes and their masks as ``adc_genome`` draws
  them, then each activation and weight-precision gene uniform over its
  table (``act_choices``, ``wprec_bits``), as NSGA-II draws generation 0;
* ``decode``: the seven base rows (the training seed is the crc32 of the
  whole genome's bytes), then ``act_sel`` (P, hidden layers) int32 and
  ``wprec`` (P, layers) float32 bit widths, 0 for ternary, in the
  trainer's order;
* ``make_qat_reference``: one genome's QAT run in straightforward
  ``jax.numpy``, at ``highest`` in float32, or in bfloat16 throughout (the
  control);
* ``area``: the whole datapath's area as the search reports it, raw: the
  ADC bank gate by gate, then each layer's adders and output stage.

Departures from the papers, all as the configuration states them:

* Activation circuits (arXiv 2312.17612) are modelled as transfer
  functions, each in place of ReLU and followed by the [0, 1] clip and
  the ``act_bits`` re-digitisation of the hidden layer: exact ReLU, a
  saturating follower, a two-segment PWL bend, and a mid-rail comparator
  whose gradient is the saturating follower's (straight-through).  The
  papers approximate at the circuit level; here only the function is.
* Ternary weights (arXiv 2508.19660) follow the TWN rule per layer and
  per step: threshold 0.7 mean|w|, scale the mean of the live magnitudes,
  straight-through gradient; the paper evolves its ternary networks
  instead of training them.  Po2 weights at k bits keep exponents in
  [1 - 2^(k-1), 0], as the ADC-only genome's do.
* With the weight-precision axis on, the scalar ``weight_bits`` gene
  reaches neither the weights nor the area: each layer takes its own.
* Area is the repository's EGFET proxy, not a synthesized circuit: per
  layer fan_in x fan_out adders (fan_in - 1 and the bias) of act_bits +
  w // 2 accumulator bits (act_bits + 1 for ternary, a sign-add), and per
  neuron one output stage of the same bits, scaled in hidden layers by
  its activation circuit's ``act_area_scale``.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import window

adc = window.load_file(Path(__file__).resolve().parents[2], "references", "adc_genome")
load_dataset, split, adc_levels = adc.load_dataset, adc.split, adc.adc_levels

# the equations the ADC-only genome already writes: straight-through
# estimator, po2 weights at k bits, uniform activations at k bits
_ste, _po2, _uniform = adc._ste, adc._po2, adc._uniform


# -- genomes --------------------------------------------------------------


def _n_layers(cfg: dict) -> int:
    return len(cfg["layer_sizes"]) - 1


def draw(rng: np.random.Generator, n: int, cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """``n`` genomes: (mask genes, categorical genes (n, 5 + hidden layers +
    layers) int64)."""
    masks, cats = adc.draw(rng, n, cfg)
    act = rng.integers(0, len(cfg["act_choices"]), size=(n, _n_layers(cfg) - 1))
    wprec = rng.integers(0, len(cfg["wprec_bits"]), size=(n, _n_layers(cfg)))
    return masks, np.concatenate([cats, act, wprec], axis=1).astype(np.int64)


def decode(masks: np.ndarray, cats: np.ndarray, cfg: dict) -> tuple:
    """Genomes → the nine evaluator row arrays: level masks, weight bits,
    activation bits, batch size, epochs, lr, training seed, activation
    selectors, per-layer weight bits."""
    cats = np.asarray(cats, np.int64)
    hidden = _n_layers(cfg) - 1
    base = adc.decode(masks, cats[:, :5], cfg)[:6]
    return (
        *base,
        adc.genome_seeds(masks, cats),
        cats[:, 5:5 + hidden].astype(np.int32),
        np.asarray(cfg["wprec_bits"], np.float32)[cats[:, 5 + hidden:]],
    )


# -- QAT ------------------------------------------------------------------


def _step(h):
    sat = jnp.clip(h, 0.0, 1.0)
    return _ste(sat, (h > 0.5).astype(h.dtype))


CIRCUITS = {
    "relu": jax.nn.relu,
    "sat01": lambda h: jnp.clip(h, 0.0, 1.0),
    "pwl2": lambda h: jax.nn.relu(h) - 0.5 * jax.nn.relu(h - 0.5),
    "step": _step,
}


def _ternary(w):
    """TWN: {-s, 0, +s}, live where |w| > 0.7 mean|w|, s the mean live
    magnitude.  Straight-through."""
    mag = jnp.abs(w)
    live = mag > 0.7 * jnp.mean(mag)
    s = jnp.sum(jnp.where(live, mag, 0.0)) / jnp.maximum(jnp.sum(live), 1)
    return _ste(w, jnp.where(live, jnp.sign(w) * s, 0.0).astype(w.dtype))


def _weights(w, bits):
    """One layer's weights at ``bits``: po2, or ternary at 0."""
    return jnp.where(bits == 0, _ternary(w), _po2(w, jnp.maximum(bits, 1)))


def make_qat_reference(cfg: dict, n_train: int, dtype=jnp.float32, batch_share: float = 1.0):
    """``fn(x_tr, y_tr, x_te, y_te, eval_seed, *rows) -> test accuracy per
    row``, as ``adc_genome.make_qat_reference``, with each hidden layer's
    activation circuit and each layer's weight precision taken from the
    row's last two columns."""
    t = cfg["trainer"]
    sizes = tuple(cfg["layer_sizes"])
    max_steps, max_batch = t["max_steps"], t["max_batch"]
    adc_bits = cfg["adc_bits"]
    circuits = [CIRCUITS[c] for c in cfg["act_choices"]]

    def act(h, sel):
        return jnp.select([sel == k for k in range(len(circuits))], [c(h) for c in circuits])

    def train_one(x_tr, y_tr, x_te, y_te, eval_seed, mask, wb, ab, bs, ep, lr, seed,
                  act_sel, wprec):
        del wb  # each layer's weight precision is its own gene
        key = jax.random.fold_in(jax.random.PRNGKey(eval_seed), seed)
        keys = jax.random.split(key, len(sizes) - 1)
        params = []
        for i, (fi, fo) in enumerate(zip(sizes[:-1], sizes[1:])):
            bound = 1.0 / jnp.sqrt(jnp.float32(fi))
            w = jax.random.uniform(keys[i], (fi, fo), jnp.float32, -bound, bound)
            params.append((w.astype(dtype), jnp.zeros((fo,), dtype)))
        xq_tr = adc_levels(x_tr, mask, adc_bits).astype(dtype)
        xq_te = adc_levels(x_te, mask, adc_bits).astype(dtype)
        ab, lr, wprec = ab.astype(dtype), lr.astype(dtype), wprec.astype(dtype)

        def forward(p, h):
            for i, (w, b) in enumerate(p):
                h = h @ _weights(w, wprec[i]) + b
                if i < len(p) - 1:
                    h = _uniform(jnp.clip(act(h, act_sel[i]), 0.0, 1.0), ab)
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

    batched = jax.jit(jax.vmap(train_one, in_axes=(None,) * 5 + (0,) * 9))

    def run(x_tr, y_tr, x_te, y_te, eval_seed, *rows):
        with jax.default_matmul_precision("highest"):
            return np.asarray(batched(x_tr, y_tr, x_te, y_te, np.int32(eval_seed), *rows))

    return run


# -- area -----------------------------------------------------------------


def area(masks: np.ndarray, cats: np.ndarray, cfg: dict) -> np.ndarray:
    """Raw area of each genome's datapath: its ADC bank, counted gate by
    gate, then layer by layer the adders and the output stages."""
    _, _, ab, _, _, _, _, act_sel, wprec = decode(masks, cats, cfg)
    d = cfg["datapath_area"]
    sizes = cfg["layer_sizes"]
    out = adc.adc_area(masks, cfg["adc_bits"], cfg["area_gates"])
    for g in range(len(out)):
        for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
            w = float(wprec[g, i])
            acc = float(ab[g]) + (w // 2 if w > 0 else 1.0)
            scale = d["act_area_scale"][act_sel[g, i]] if i < len(sizes) - 2 else 1.0
            out[g] += (fan_in * fan_out * acc * d["adder_bit"]
                       + scale * fan_out * acc * d["output_stage_bit"])
    return out
