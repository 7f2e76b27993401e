"""Waves of fresh genomes through the population QAT program.

Blocking calls of ``core.trainer.make_population_evaluator``'s evaluator on
waves of ``wave_rows`` genomes (``genomes.draw``), host rows in and
accuracies to host, with the data split and trainer seed of the traffic.
No wave starts after ``seconds``; the window ends when the last returns.
"""

from __future__ import annotations

import time

import numpy as np

from bench import genomes, reference, window, work


class Driver:
    def __init__(self, cell):
        self.cfg, self.traffic = cell.config, cell.traffic
        self.ev = None

    def prepare(self) -> None:
        """Data, evaluator and its one program, warmed on a wave of its own."""
        from repro.core import trainer

        window.trainer_check(self.cfg)
        tr, cfg = self.traffic, self.cfg
        self.data = reference.split(*reference.load_dataset(cfg["dataset"]),
                                    cfg["dataset"]["train_frac"], tr["split_seed"])
        t = cfg["trainer"]
        self.ev = trainer.make_population_evaluator(
            *self.data, window.mlp(cfg),
            trainer.EvalConfig(max_steps=t["max_steps"], step_scale=t["step_scale"],
                               max_batch=t["max_batch"], momentum=t["momentum"],
                               seed=tr["eval_seed"], genome_axes=tuple(cfg["genome_axes"])),
        )
        rng = np.random.default_rng([2**32 - 1])
        np.asarray(self.ev(*reference.decode(*genomes.draw(rng, tr["wave_rows"], cfg), cfg)))

    def draw(self, seed: int) -> None:
        """The window's waves, made from ``seed``."""
        rng = np.random.default_rng(seed)
        self.pool = [reference.decode(*genomes.draw(rng, self.traffic["wave_rows"], self.cfg),
                                      self.cfg) for _ in range(self.traffic["pool_waves"])]

    def window(self, seconds: float, prof: window.Profiler | None) -> dict:
        group = window.Group(self.traffic["eval_seed"], self.data)
        n_traced = self.traffic["trace_waves"] if prof else 0
        t0 = time.perf_counter()
        i = 0
        while i == 0 or time.perf_counter() - t0 - (prof.overhead_s if prof else 0) < seconds:
            if i == 0 and n_traced:
                prof.start()
            rows = self.pool[i % len(self.pool)]
            group.add(rows, np.asarray(self.ev(*rows)))
            i += 1
            if i == n_traced:
                prof.stop()
        window_s = time.perf_counter() - t0 - (prof.overhead_s if prof else 0)
        n_train, n_test = len(self.data[1]), len(self.data[3])
        flops, nbytes = work.row_work(
            np.concatenate([r[3] for r in group.rows]),
            np.concatenate([r[4] for r in group.rows]), self.cfg, n_train, n_test)
        per_wave = len(group.rows[0][0])
        return {
            "window_s": window_s,
            "waves": i,
            "rows": i * per_wave,
            "work": {"flops": float(flops.sum()), "bytes": float(nbytes.sum())},
            "traced_work": {"flops": float(flops[: n_traced * per_wave].sum()),
                            "bytes": float(nbytes[: n_traced * per_wave].sum())},
            "groups": [group],
            "fronts": [],
            "program": "_evaluate_padded",
        }

    def release(self) -> None:
        self.ev = None
