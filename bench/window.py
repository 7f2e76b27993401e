"""What every traffic driver shares, and how a driver is found by name.

A traffic file names its driver (``"driver": "<name>"``); the driver is the
class ``Driver`` in ``drivers/<name>.py`` under the benchmark's tree, so a
later cell adds a traffic kind by adding one such file.  A driver has

* ``prepare()``: data, evaluators, and the warm-up of every program the
  window can call (set-up);
* ``draw(seed)``: the window's inputs, made from ``seed`` (set-up);
* ``window(seconds, prof)``: drives the program and returns the window's
  record (its rows and answers under ``groups``, which ``check`` compares
  with the reference, and whatever its metric readers read);
* ``release()``: drops the program's state before the reference runs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import time
from pathlib import Path

import jax
import numpy as np


@dataclasses.dataclass
class Group:
    """Rows one trainer configuration answered: its data and seed, the
    rows of each evaluator call, and the program's accuracy of each."""

    eval_seed: int
    data: tuple
    rows: list = dataclasses.field(default_factory=list)
    acc: list = dataclasses.field(default_factory=list)

    def add(self, rows, acc) -> None:
        self.rows.append(tuple(np.asarray(a) for a in rows))
        self.acc.append(np.asarray(acc, np.float32))

    def arrays(self):
        rows = tuple(np.concatenate(col) for col in zip(*self.rows))
        return rows, np.concatenate(self.acc)


class Profiler:
    """The profiler around the traced part of a window, and its cost."""

    def __init__(self, trace_dir: str | None):
        self.dir = trace_dir
        self.overhead_s = 0.0
        self._ann = None

    def start(self) -> None:
        t = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.raise_error_on_start_failure = True
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.overhead_s += time.perf_counter() - t

    def stop(self) -> None:
        t = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - t


def trainer_check(cfg: dict) -> None:
    """The program runs as the configuration states: its trainer defaults
    and JAX's matmul precision."""
    from repro.core import trainer

    d = trainer.EvalConfig()
    t = cfg["trainer"]
    if (d.max_batch, d.momentum) != (t["max_batch"], t["momentum"]):
        raise ValueError(f"configuration trainer {t} is not what the program runs: {d}")
    prec = jax.config.jax_default_matmul_precision or "default"
    if prec != t["matmul_precision"]:
        raise ValueError(f"JAX's matmul precision is {prec!r}, the configuration states "
                         f"{t['matmul_precision']!r}")


def program_data(cfg: dict, split_seed: int):
    """The program's own train/test split for ``split_seed``."""
    from repro.data import uci_synth

    x, y, _ = uci_synth.load(cfg["dataset"]["name"])
    return uci_synth.stratified_split(x, y, cfg["dataset"]["train_frac"], split_seed)


def mlp(cfg: dict):
    from repro.core import qat

    return qat.MLPConfig(tuple(cfg["layer_sizes"]), adc_bits=cfg["adc_bits"])


def load_driver(root: Path, name: str):
    """The class ``Driver`` of ``root/bench/drivers/<name>.py``."""
    path = Path(root) / "bench" / "drivers" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no traffic driver {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_driver_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Driver
