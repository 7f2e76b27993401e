"""Back-to-back ``core.codesign.run_codesign`` searches.

Each search takes its seed in turn from the traffic's ``search_seeds``,
starting at ``--seed`` modulo their number.  Every run so does the same
searches in another order, and set-up compiles (first run) or fetches
every program they call before the window.  No search starts after
``seconds``; the window ends when the last returns.  The traffic's
``search`` group goes into ``CodesignConfig`` as it stands.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from bench import window


class Tap:
    """Records every batch the search's evaluators answer, by search.

    Installed around the window: ``trainer.make_population_evaluator`` and
    ``trainer.make_island_evaluator`` return the program's evaluators
    unchanged, wrapped so that each call also keeps its host rows and its
    result.  No call is added and nothing waits that did not wait before."""

    def __init__(self):
        self.groups: dict[int, window.Group] = {}
        self.search = None

    def _add(self, rows, out) -> None:
        seed = self.search
        if seed not in self.groups:
            self.groups[seed] = window.Group(seed, None)
        if len(rows[0]):
            self.groups[seed].add(rows, out)

    @contextlib.contextmanager
    def installed(self):
        from repro.core import trainer

        make_pop, make_isl = trainer.make_population_evaluator, trainer.make_island_evaluator

        def pop(*a, **k):
            ev = make_pop(*a, **k)

            def evaluate(*rows):
                out = ev(*rows)
                self._add(rows, out)
                return out

            def dispatch(*rows):
                resolve = ev.dispatch(*rows)

                def done():
                    out = resolve()
                    self._add(rows, out)
                    return out
                return done

            evaluate.__dict__.update(ev.__dict__)
            evaluate.dispatch = dispatch
            return evaluate

        def isl(*a, **k):
            ev = make_isl(*a, **k)

            def evaluate(batches):
                outs = ev(batches)
                for rows, out in zip(batches, outs):
                    self._add(rows, out)
                return outs

            evaluate.__dict__.update(ev.__dict__)
            return evaluate

        trainer.make_population_evaluator, trainer.make_island_evaluator = pop, isl
        try:
            yield
        finally:
            trainer.make_population_evaluator, trainer.make_island_evaluator = make_pop, make_isl


class Driver:
    def __init__(self, cell):
        self.cfg, self.traffic, self.ref = cell.config, cell.traffic, cell.ref

    def codesign_config(self, search_seed: int):
        from repro.core import codesign

        t = self.cfg["trainer"]
        return codesign.CodesignConfig(
            dataset=self.cfg["dataset"]["name"], adc_bits=self.cfg["adc_bits"],
            step_scale=t["step_scale"], max_steps=t["max_steps"], seed=search_seed,
            genome_axes=tuple(self.cfg["genome_axes"]), **self.traffic["search"],
        )

    def prepare(self) -> None:
        window.trainer_check(self.cfg)
        rng = np.random.default_rng([2**32 - 1])
        for s in self.traffic["search_seeds"]:
            self._warm(s, rng)

    def draw(self, seed: int) -> None:
        """The order of the window's searches, from ``seed``."""
        pool = list(self.traffic["search_seeds"])
        k = seed % len(pool)
        self.order = pool[k:] + pool[:k]

    def _warm(self, s: int, rng) -> None:
        """Compile every program a search with seed ``s`` can call: the
        population program at each bucket up to the population, the slices
        of its padded result, and for stacked islands the (islands, bucket)
        programs.  The window's searches build their own evaluators and
        fetch these from the persistent compilation cache."""
        from repro.core import trainer

        cc = self.codesign_config(s)
        data = window.program_data(self.cfg, s)
        ecfg = trainer.EvalConfig(max_steps=cc.max_steps, step_scale=cc.step_scale, seed=s,
                                  use_fused_kernel=cc.use_fused_kernel, genome_axes=cc.axes())
        pop = trainer.make_population_evaluator(*data, window.mlp(self.cfg), ecfg)
        n_dev = len(jax.devices())
        granule = -(-max(ecfg.pad_granule, 1) // n_dev) * n_dev
        top = -(-cc.pop_size // granule) * granule
        stacked = cc.num_islands > 1 and cc.stacked_islands
        for b in range(granule, top + 1, granule):
            if stacked and b > granule:
                break  # the population program only scores the 4 baseline rows
            rows = self.ref.decode(*self.ref.draw(rng, b, self.cfg), self.cfg)
            compiled = pop.program.lower(*(pop.shard_fn(a) for a in rows)).compile()
            out = jax.device_put(np.zeros(b, np.float32), compiled.output_shardings)
            for p in range(max(b - granule + 1, 1), b):
                out[:p].block_until_ready()
        if stacked:
            isl = trainer.make_island_evaluator(*data, window.mlp(self.cfg), ecfg,
                                                num_islands=cc.num_islands)
            for b in range(isl.granule, -(-cc.pop_size // isl.granule) * isl.granule + 1,
                           isl.granule):
                rows = self.ref.decode(*self.ref.draw(rng, b, self.cfg), self.cfg)
                stacked_rows = [isl.shard_fn(np.stack([a] * cc.num_islands)) for a in rows]
                isl.program.lower(*stacked_rows).compile()

    def window(self, seconds: float, prof: window.Profiler | None) -> dict:
        from repro.core import codesign

        tap = Tap()
        searches, fronts = [], []
        t0 = time.perf_counter()
        i = 0
        with tap.installed():
            while i == 0 or time.perf_counter() - t0 - (prof.overhead_s if prof else 0) < seconds:
                s = self.order[i % len(self.order)]
                tap.search = s
                if i == 0 and prof:
                    prof.start()
                ts = time.perf_counter()
                res = codesign.run_codesign(self.codesign_config(s))
                wall = time.perf_counter() - ts
                if i == 0 and prof:
                    prof.stop()
                searches.append({"seed": s, "wall_s": wall, "history": res.history})
                fronts.append({"seed": s, "masks": res.front_masks, "cats": res.front_cats,
                               "acc": res.front_acc, "area": res.front_area})
                i += 1
        ds = self.cfg["dataset"]
        for g in tap.groups.values():
            g.data = self.ref.split(*self.ref.load_dataset(ds), ds["train_frac"], g.eval_seed)
        return {
            "window_s": sum(r["wall_s"] for r in searches),
            "searches": searches,
            "rows": sum(sum(len(a) for a in g.acc) for g in tap.groups.values()),
            "groups": list(tap.groups.values()),
            "fronts": fronts,
            "program": "_evaluate_stacked" if self.traffic["search"].get("stacked_islands")
            else "_evaluate_padded",
        }

    def release(self) -> None:
        pass
