"""What decides ``correct``: the window's own answers against the reference.

Every row the window trained is recorded with the accuracy the program
answered (``window.Group``).  Once the window has closed, two samples of
those rows are drawn from ``--seed``: one over all rows, one among the
rows with the smallest learning-rate gene.  Both are trained again by the
plain float32 reference (``reference.make_qat_reference``) on the same data
with the same seeds, and each row's gap is the distance, in test samples,
between the program's accuracy and the reference's.

* ``gap_mean``: the mean gap over all sampled rows, whatever their genes,
  so every learning rate, batch size and epoch count the window trains.
  ``calibrate.py`` reads it; a cell holds it once its limits file has a
  limit for it, set from chip readings of the program and the faults.
* ``lowlr_gap_mean`` and ``lowlr_off2_share`` (the share of rows two test
  samples or more off): the same over the low-learning-rate rows.  A QAT
  run of 600 steps is chaotic under rounding.  The configuration states
  float32 at JAX's default matmul precision, which on the TPU is one
  bfloat16 pass, so a row trained with large steps can end a dozen test
  samples from the float32 reference, as a bfloat16 control does.  At the
  smallest learning rate the updates are smallest: a control that keeps
  its parameters in bfloat16 loses them, and so does a step that trains on
  part of its minibatch, while the program stays within about a sample of
  the reference.  The mean swings with the few rows that flip by ten
  samples or more; the share counts each such row once, so it is the
  steadier of the two from seed to seed.

Each cell's limits file says which numbers it holds.

For searches, the assembly of each Pareto front is checked exactly: every
front member's accuracy is one the program answered for that genome in that
search, its area is the gate-by-gate reference area, and no member
dominates another.

The limits of a cell are in ``limits/<cell>.json``, with the readings
they were set from.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import reference, work


def sample(groups, n: int, seed: int, lr: float | None = None, stream: int = 7) -> list:
    """``n`` (group, row) pairs drawn from ``seed`` over the answered rows,
    or over those with learning rate ``lr`` (all of them when fewer)."""
    index = [(g, r) for g, grp in enumerate(groups)
             for r in np.flatnonzero(np.concatenate([rows[5] for rows in grp.rows]) == lr
                                     if lr is not None else
                                     np.ones(sum(len(rows[5]) for rows in grp.rows), bool))]
    rng = np.random.default_rng([seed, stream])
    pick = rng.choice(len(index), size=min(n, len(index)), replace=False)
    return [index[i] for i in sorted(pick)]


def samples(cell, win: dict, seed: int) -> dict:
    """The two samples: ``all`` rows and ``lowlr`` rows."""
    n = cell.traffic["reference_rows"]
    lr = np.float32(min(cell.config["genes"]["lr"]))
    return {"all": sample(win["groups"], n, seed, stream=8),
            "lowlr": sample(win["groups"], n, seed, lr)}


def reference_answers(cfg: dict, groups, picks, block: int, dtype=jnp.float32,
                      batch_share: float = 1.0, rows_fn=None):
    """(program accuracy, reference accuracy, n_test) of each picked row.
    The reference runs once per group on a block of ``block`` rows (padded
    with the group's last row), so one compiled program serves every group.
    ``dtype``, ``batch_share`` and ``rows_fn`` (applied to the rows the
    reference trains) plant the control and the faults in its place."""
    n_train, n_test = work.split_sizes(cfg["dataset"])
    if not picks:
        return np.zeros(0), np.zeros(0), n_test
    fn = reference.make_qat_reference(cfg, n_train, dtype, batch_share)
    prog, ref = [], []
    for g in sorted({g for g, _ in picks}):
        rows, acc = groups[g].arrays()
        idx = np.asarray([r for gg, r in picks if gg == g])
        pad = np.concatenate([idx, np.repeat(idx[-1:], block - len(idx))])
        trained = tuple(a[pad] for a in rows)
        out = fn(*groups[g].data, groups[g].eval_seed,
                 *(rows_fn(trained) if rows_fn else trained))
        prog.append(acc[idx])
        ref.append(out[: len(idx)])
    return np.concatenate(prog), np.concatenate(ref), n_test


def gaps(prog: np.ndarray, ref: np.ndarray, n_test: int) -> np.ndarray:
    return np.round(np.abs(prog.astype(np.float64) - ref) * n_test)


# the numbers read from each sample's gaps; an empty sample cannot be
# checked and reads NaN, which fails every limit
NUMBERS = {
    "all": lambda g: {"gap_mean": float(g.mean()) if g.size else float("nan")},
    "lowlr": lambda g: {"lowlr_gap_mean": float(g.mean()) if g.size else float("nan"),
                        "lowlr_off2_share": float(np.mean(g >= 2)) if g.size else float("nan")},
}


def compare(cfg: dict, groups, picks: dict, block: int, answers=None, memo=None,
            **planted) -> dict:
    """The numbers of each sample in ``picks`` for the program's answers
    (or for ``answers(group)``, a group's answers altered, put in their
    place) against the reference, each sample in reference calls of its
    own; ``planted`` puts a planted reference in the program's place
    instead (see ``reference_answers``).  ``memo``, a dict, keeps the
    reference's answers for further comparisons of the same window."""
    memo = {} if memo is None else memo
    out = {}
    for name, sel in picks.items():
        if name not in memo:
            memo[name] = reference_answers(cfg, groups, sel, block)
        prog, ref, n_test = memo[name]
        if answers is not None and sel:
            prog = np.asarray([answers(groups[g])[r] for g, r in sel], np.float32)
        if planted:
            _, prog, _ = reference_answers(cfg, groups, sel, block, **planted)
        out.update(NUMBERS[name](gaps(prog, ref, n_test)))
    return out


def front_numbers(cfg: dict, groups, fronts) -> dict:
    """Exact checks of each search's front assembly."""
    by_seed = {g.eval_seed: g for g in groups}
    genes = cfg["genes"]
    unmatched = dominated = 0
    area_gap = 0.0
    for f in fronts:
        rows, acc = by_seed[f["seed"]].arrays()
        cats = np.asarray(f["cats"], np.int64)
        vals = (np.asarray(genes["weight_bits"], np.float32)[cats[:, 0]],
                np.asarray(genes["act_bits"], np.float32)[cats[:, 1]],
                np.asarray(genes["batch_size"], np.int32)[cats[:, 2]],
                np.asarray(genes["epochs"], np.int32)[cats[:, 3]],
                np.asarray(genes["lr"], np.float32)[cats[:, 4]])
        # the search reports 1 - (1 - acc) with the miss taken in float32
        reported = 1.0 - (np.float32(1.0) - acc).astype(np.float64)
        for m in range(len(f["acc"])):
            same = np.all(rows[0] == f["masks"][m], axis=(1, 2))
            for col, v in zip(rows[1:6], vals):
                same &= col == v[m]
            if not np.any(same & (reported == f["acc"][m])):
                unmatched += 1
        ref_area = reference.adc_area(f["masks"], cfg["adc_bits"], cfg["area_gates"])
        area_gap = max(area_gap, float(np.max(np.abs(f["area"] - ref_area) / ref_area)))
        obj = np.stack([1.0 - np.asarray(f["acc"]), np.asarray(f["area"])], axis=1)
        le = np.all(obj[:, None] <= obj[None], axis=-1)
        lt = np.any(obj[:, None] < obj[None], axis=-1)
        dominated += int(np.any(le & lt, axis=0).sum())
    return {"front_unmatched": float(unmatched), "front_dominated": float(dominated),
            "front_area_gap": area_gap}


def numbers(cell, win: dict, seed: int) -> dict:
    """Every number compared for one run: the samples whose numbers the
    cell's limits hold, and the front checks."""
    picks = {k: v for k, v in samples(cell, win, seed).items()
             if set(NUMBERS[k](np.zeros(0))) & set(cell.limits)}
    out = compare(cell.config, win["groups"], picks, cell.traffic["reference_rows"])
    if win["fronts"]:
        out.update(front_numbers(cell.config, win["groups"], win["fronts"]))
    return out
