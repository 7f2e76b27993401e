"""Useful work of a QAT row, counted from its genome and not from the program.

A row trains for its own step budget, ``epochs * ceil(n_train / batch) *
step_scale`` capped at ``max_steps``, on ``batch`` samples a step, and is
then scored once on the test set.  Steps past the budget and samples past
the batch size, which the program computes and masks, are not useful work.
So a program that skips them reads more useful work per second, and never
more than its roofline.

With ``W`` the weight count (``sum(fan_in * fan_out)``):

* FLOPs: ``steps * batch * 6 * W`` (forward 2W, backward 4W per sample)
  plus ``n_test * 2 * W``;
* bytes: ``steps * batch`` input rows of ``n_features`` float32 values,
  plus the test set once.
"""

from __future__ import annotations

import numpy as np


def weight_count(layer_sizes) -> int:
    return sum(a * b for a, b in zip(layer_sizes[:-1], layer_sizes[1:]))


def useful_steps(bs, ep, n_train: int, trainer: dict) -> np.ndarray:
    """Steps each row trains: the steps ``t`` with ``t < budget``."""
    bs = np.asarray(bs, np.float64)
    ep = np.asarray(ep, np.float64)
    budget = np.maximum(ep * np.ceil(n_train / bs) * trainer["step_scale"], 1.0)
    return np.minimum(np.ceil(np.minimum(budget, trainer["max_steps"])), trainer["max_steps"])


def row_work(bs, ep, cfg: dict, n_train: int, n_test: int) -> tuple[np.ndarray, np.ndarray]:
    """(flops, bytes) of each row with batch sizes ``bs`` and epochs ``ep``."""
    w = weight_count(cfg["layer_sizes"])
    samples = useful_steps(bs, ep, n_train, cfg["trainer"]) * np.asarray(bs, np.float64)
    row_bytes = 4.0 * cfg["layer_sizes"][0]
    flops = samples * 6.0 * w + n_test * 2.0 * w
    nbytes = samples * row_bytes + n_test * row_bytes
    return flops, nbytes


def useful_share(cfg: dict, n_train: int) -> float:
    """Useful row-steps over computed row-steps, averaged uniformly over
    the batch-size and epoch genes."""
    t = cfg["trainer"]
    bs, ep = np.meshgrid(cfg["genes"]["batch_size"], cfg["genes"]["epochs"])
    useful = useful_steps(bs.ravel(), ep.ravel(), n_train, t) * bs.ravel()
    return float(useful.mean() / (t["max_steps"] * t["max_batch"]))


def split_sizes(ds: dict) -> tuple[int, int]:
    """(n_train, n_test) of the stratified split of ``ds``."""
    per_class = [ds["n_samples"] // ds["n_classes"]] * ds["n_classes"]
    for i in range(ds["n_samples"] - sum(per_class)):
        per_class[i] += 1
    n_train = sum(int(round(ds["train_frac"] * n)) for n in per_class)
    return n_train, ds["n_samples"] - n_train


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take for the work, and what bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")

