"""ADC-aware co-design: the paper's full training flow (Fig. 2).

Couples the NSGA-II search (``core.nsga2``) over {per-input ADC level
masks, QAT hyper-parameters} with the population-vmapped QAT inner loop
(``core.trainer``) and the area proxy (``core.area``).  Objectives, both
minimised, exactly as §II-C:

    obj0 = accuracy miss  (1 - test accuracy of the QAT-trained MLP)
    obj1 = total ADC area (proxy model, normalised to the conventional ADC)

Outputs the Pareto front plus a gains report in the paper's terms
(area× / power× vs the conventional ADC bank at a given accuracy-drop
budget).
"""

from __future__ import annotations

import dataclasses
import itertools
import zlib
from typing import Callable

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import area as area_model
from repro.core import chromosome, hybrid, memo_store, nsga2, qat, spans, surrogate, trainer
from repro.data import uci_synth
from repro.runtime import elastic as elastic_rt
from repro.runtime import failure as failure_rt

__all__ = [
    "CodesignConfig",
    "CodesignResult",
    "run_codesign",
    "make_service_backend",
    "gains_at_budget",
]


@dataclasses.dataclass(frozen=True)
class CodesignConfig:
    dataset: str = "seeds"
    adc_bits: int = 4
    pop_size: int = 24
    n_generations: int = 12
    step_scale: float = 1.0
    max_steps: int = 600
    seed: int = 0
    # memoize=True (default) caches QAT results by genome so survivors and
    # duplicate children are never re-trained; False selects the paper-style
    # naive engine that re-trains the full parent+child pool every
    # generation (the benchmark baseline, NOT the pre-memo engine)
    memoize: bool = True
    crossover_rate: float = 0.7
    mutation_rate: float = 0.02
    # run the QAT first layer through the fused pruned-ADC Pallas kernel
    # (kernels.fused_qat) instead of the pure-JAX quantize+matmul pair; the
    # search outcome is identical (same values, same STE gradient)
    use_fused_kernel: bool = False
    # checkpoint directory for the genome->objective memo: preloaded before
    # the search when present (fingerprint-verified), saved after.  One
    # path per (dataset, eval-config) — see core.memo_store.
    memo_path: str | None = None
    # island model (core.nsga2.IslandNSGA2): num_islands sub-populations of
    # pop_size chromosomes EACH (budgets are per island), sharing one
    # evaluation memo, with migration_size top-crowding Pareto members
    # migrating along migration_topology every migration_interval
    # generations.  num_islands=1 is exactly the single-population engine.
    num_islands: int = 1
    migration_interval: int = 3
    migration_size: int = 2
    migration_topology: str = "ring"
    # stacked_islands=True evaluates all islands' unseen genomes as ONE
    # cross-island SPMD program per generation (trainer.make_island_evaluator
    # over the (island, data) device-group mesh) instead of stepping the
    # islands sequentially — bit-for-bit identical search results; requires
    # memoize.  Ignored when num_islands == 1.
    stacked_islands: bool = False
    # async_pipeline=True (num_islands > 1) dispatches each island's unseen
    # batch as a non-blocking device program (trainer's evaluate.dispatch)
    # and blocks only at commit time, so the next island's planning runs
    # while earlier islands train (requires memoize, mutually exclusive
    # with stacked_islands).  With num_islands == 1 it changes nothing:
    # the blocking callback already runs the host-side area pass while the
    # accuracy program trains.  Bit-for-bit identical search results.
    async_pipeline: bool = False
    # fault tolerance: with checkpoint_dir set, GA state (per-island
    # populations, RNG streams, histories, migration log) plus the shared
    # memo is checkpointed via CheckpointManager every checkpoint_every
    # generations; resume=True restores the newest compatible checkpoint
    # (search_fingerprint-verified) and continues the interrupted
    # campaign.  drill (a runtime.elastic.DrillConfig) injects failures /
    # straggler slowdowns at evaluator-dispatch boundaries and records
    # row-level replay telemetry — the chaos-test hook.  Either field
    # routes the run through runtime.elastic.ElasticGARunner.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    resume: bool = False
    drill: "elastic_rt.DrillConfig | None" = None
    # generalized approximation genome (core.chromosome.AXES): which gene
    # groups the search evolves.  "adc" (mandatory) = per-input level masks
    # + QAT hyper-params; "act" adds a per-hidden-layer activation
    # approximation selector; "wprec" a per-layer weight-precision /
    # ternary gene.  The default is the paper's ADC-only space and is
    # bit-for-bit the pre-axes configuration: same genome bytes, same memo
    # keys, same fronts.  Accepts a tuple or "adc,act,wprec" string.
    genome_axes: tuple[str, ...] | str = ("adc",)
    # surrogate pre-screening (core.surrogate): gate each generation's
    # planned-unseen genomes through a memo-trained MLP ensemble and spend
    # QAT rows only on the predicted-undominated subset + an exploration
    # slice; the rest are deferred with flagged predictions and trained
    # the next time they are planned.  Requires memoize (the memo is the
    # training set).  The memo itself stays exact-rows-only, so
    # memo_fingerprint — and hence on-disk memo compatibility — is
    # unchanged by this flag.
    surrogate: bool = False
    surrogate_min_rows: int = 32     # exact fallback below this memo size
    surrogate_explore_frac: float = 0.15  # seeded always-train slice
    # gradient/GA hybrid (core.hybrid): hybrid_warm_frac > 0 seeds that
    # fraction of every island's initial population with argmax-hardened
    # states of short relaxed gradient descents (exactly re-scored through
    # the standard evaluation pipeline before they enter the population);
    # hybrid_refine_every = R > 0 additionally gradient-polishes the
    # top-crowding front-0 members every R generations and injects the
    # hardened results as extra children through the same plan/dedupe
    # path.  hybrid_grad_steps is the per-descent step budget.  Both
    # injection points need memoize; at the defaults (0 / 0) the search is
    # bit-for-bit the hybrid-less one.
    hybrid_warm_frac: float = 0.0
    hybrid_refine_every: int = 0
    hybrid_grad_steps: int = 30

    def validate(self) -> "CodesignConfig":
        """THE driver-flag validation matrix — every rejected combination.

        One place instead of three: ``examples/campaign.py`` argument
        checks, ``IslandConfig.__post_init__``, and the engine
        constructors each rejected their own slice of the flag space
        before PR 9.  The engine/IslandConfig guards remain as defense in
        depth, but every entry point (:func:`run_codesign`,
        :func:`make_service_backend`, ``CampaignConfig.validate``, the
        CLIs) routes through here first, so the full matrix is testable
        against one method.  Returns ``self`` so call sites can chain.
        """
        self.axes()  # raises on unknown/missing genome axes
        if self.pop_size < 2:
            raise ValueError(f"pop_size must be >= 2, got {self.pop_size}")
        if self.n_generations < 0:
            raise ValueError(
                f"n_generations must be >= 0, got {self.n_generations}"
            )
        if self.num_islands < 1:
            raise ValueError(f"num_islands must be >= 1, got {self.num_islands}")
        if self.migration_interval < 1:
            raise ValueError(
                f"migration_interval must be >= 1, got {self.migration_interval}"
            )
        if self.migration_size < 0:
            raise ValueError(
                f"migration_size must be >= 0, got {self.migration_size}"
            )
        if self.migration_topology not in nsga2.TOPOLOGIES:
            raise ValueError(
                f"unknown topology {self.migration_topology!r}; "
                f"choose from {nsga2.TOPOLOGIES}"
            )
        if self.stacked_islands and self.async_pipeline:
            raise ValueError(
                "stacked_islands and async_pipeline are mutually exclusive "
                "drivers (one cross-island wave vs in-flight per-island "
                "programs — pick one)"
            )
        if self.stacked_islands and not self.memoize:
            raise ValueError(
                "stacked_islands needs memoize=True (the cross-island wave "
                "is deduped through the shared memo)"
            )
        if self.async_pipeline and self.num_islands > 1 and not self.memoize:
            raise ValueError(
                "async_pipeline with num_islands > 1 needs memoize=True "
                "(the overlapped islands dedupe through the shared memo)"
            )
        if self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {self.checkpoint_every}"
            )
        if self.resume and self.checkpoint_dir is None:
            raise ValueError(
                "resume=True needs checkpoint_dir (where to resume from)"
            )
        if self.surrogate and not self.memoize:
            raise ValueError(
                "surrogate=True needs memoize=True (the memo is the "
                "surrogate's training set)"
            )
        if self.surrogate_min_rows < 1:
            raise ValueError(
                f"surrogate_min_rows must be >= 1, got {self.surrogate_min_rows}"
            )
        if not 0.0 <= self.surrogate_explore_frac <= 1.0:
            raise ValueError(
                "surrogate_explore_frac must be in [0, 1], got "
                f"{self.surrogate_explore_frac}"
            )
        if not 0.0 <= self.hybrid_warm_frac <= 1.0:
            raise ValueError(
                f"hybrid_warm_frac must be in [0, 1], got {self.hybrid_warm_frac}"
            )
        if self.hybrid_refine_every < 0:
            raise ValueError(
                f"hybrid_refine_every must be >= 0, got {self.hybrid_refine_every}"
            )
        if self.hybrid_grad_steps < 1:
            raise ValueError(
                f"hybrid_grad_steps must be >= 1, got {self.hybrid_grad_steps}"
            )
        if (
            self.hybrid_warm_frac > 0.0 or self.hybrid_refine_every > 0
        ) and not self.memoize:
            raise ValueError(
                "the gradient/GA hybrid needs memoize=True (warm/refined "
                "genomes are exact-scored through the memo pipeline so "
                "later generations see them as hits)"
            )
        return self

    def make_screen(self, n_mask_bits: int, cat_cardinalities) -> (
        "surrogate.SurrogateScreen | None"
    ):
        """The configured surrogate screen stage, or None (exact path)."""
        if not self.surrogate:
            return None
        return surrogate.SurrogateScreen(
            n_mask_bits, cat_cardinalities,
            surrogate.SurrogateConfig(
                min_rows=self.surrogate_min_rows,
                explore_frac=self.surrogate_explore_frac,
                seed=self.seed,
            ),
        )

    def axes(self) -> tuple[str, ...]:
        """The normalized genome-axes tuple (canonical order, validated)."""
        return chromosome.normalize_axes(self.genome_axes)

    def island_config(self) -> nsga2.IslandConfig:
        return nsga2.IslandConfig(
            num_islands=self.num_islands,
            migration_interval=self.migration_interval,
            migration_size=self.migration_size,
            topology=self.migration_topology,
            stacked=self.stacked_islands,
            async_pipeline=self.async_pipeline,
        )

    def memo_fingerprint(self) -> dict:
        """Config fields the cached objectives are a pure function of.

        The ``genome_axes`` key is only present when axes beyond "adc"
        are enabled: genome bytes from different axis sets must never
        alias, but every memo/checkpoint persisted before the axes
        existed (all ADC-only by construction) must keep validating.
        Likewise ``matmul_precision``, JAX's default matmul precision when
        the run sets one other than "default": the QAT dots, and so the
        accuracies, differ from one precision to another on the TPU.
        """
        fp = {
            "dataset": self.dataset,
            "adc_bits": self.adc_bits,
            "step_scale": self.step_scale,
            "max_steps": self.max_steps,
            "seed": self.seed,
        }
        axes = self.axes()
        if axes != ("adc",):
            fp["genome_axes"] = list(axes)
        precision = jax.config.jax_default_matmul_precision
        if precision not in (None, "default"):
            fp["matmul_precision"] = str(precision)
        return fp

    def search_fingerprint(self) -> dict:
        """Config fields a GA-state checkpoint is only valid for.

        Everything the objectives depend on (:meth:`memo_fingerprint`)
        plus the search-shape knobs that the RNG streams and population
        arrays encode.  ``n_generations`` is deliberately excluded: a
        resumed campaign may widen its budget (restore at generation g,
        run to a larger horizon) without invalidating the state.
        """
        fp = {
            **self.memo_fingerprint(),
            "pop_size": self.pop_size,
            "crossover_rate": self.crossover_rate,
            "mutation_rate": self.mutation_rate,
            "num_islands": self.num_islands,
            "migration_interval": self.migration_interval,
            "migration_size": self.migration_size,
            "migration_topology": self.migration_topology,
        }
        # screening changes which rows train each generation (the search
        # trajectory), so a surrogate checkpoint must not resume an exact
        # campaign or vice versa; key present only when enabled so every
        # pre-surrogate checkpoint keeps validating
        if self.surrogate:
            fp["surrogate"] = {
                "min_rows": self.surrogate_min_rows,
                "explore_frac": self.surrogate_explore_frac,
            }
        # warm-seeded populations / refinement waves change the search
        # trajectory the checkpoint arrays encode; knobs recorded only
        # when enabled so every pre-hybrid checkpoint keeps validating
        if self.hybrid_warm_frac > 0.0 or self.hybrid_refine_every > 0:
            fp["hybrid"] = {
                "warm_frac": self.hybrid_warm_frac,
                "refine_every": self.hybrid_refine_every,
                "grad_steps": self.hybrid_grad_steps,
            }
        return fp


@dataclasses.dataclass
class CodesignResult:
    dataset: str
    spec: uci_synth.DatasetSpec
    front_masks: np.ndarray        # (F, C, 2^N)
    front_cats: np.ndarray         # (F, n_cats) — 5 + the enabled axes'
    front_acc: np.ndarray          # (F,)
    front_area: np.ndarray         # (F,) absolute cm^2
    front_power: np.ndarray        # (F,) absolute mW
    conv_acc: float                # conventional-ADC QAT baseline accuracy
    conv_area: float
    conv_power: float
    history: list
    n_evaluations: int = 0         # QAT rows actually trained by the GA
    n_memo_hits: int = 0           # QAT rows answered from the genome memo
    n_deferred: int = 0            # rows answered by the surrogate instead
    # island-model telemetry (None for the single-population engine):
    island_history: list | None = None   # per-island NSGA2.history lists
    migrations: list | None = None       # per-wave acceptance counts
    # elastic-runner telemetry (None when the run was not checkpointed):
    recoveries: list | None = None       # re-mesh events (device loss etc.)
    # which genome gene groups the search evolved (core.chromosome.AXES)
    genome_axes: tuple[str, ...] = ("adc",)


def _genome_seeds(mask_genes: np.ndarray, cat_genes: np.ndarray) -> np.ndarray:
    """Deterministic per-genome training seeds (crc32 of the genome bytes).

    Seeding from the genome — not the row position in the batch — makes the
    objective a pure function of the chromosome, which is what lets the
    NSGA-II evaluation memo return cached results for repeated genomes
    without changing the search outcome.
    """
    keys = nsga2.genome_keys(mask_genes, cat_genes)
    return np.asarray([zlib.crc32(k) & 0x7FFFFFFF for k in keys], np.int32)


def _extra_rows(dec: dict) -> tuple:
    """The decoded extra row arrays for the enabled axes, canonical order.

    ``chromosome.decode_batch`` only emits these keys for enabled axes, so
    with ADC-only genomes this is empty and every evaluator call carries
    exactly the pre-axes seven arrays.
    """
    extra = []
    if "act_sel" in dec:
        extra.append(dec["act_sel"])
    if "wprec" in dec:
        extra.append(dec["wprec"])
    return tuple(extra)


def _make_cost_batch(axes: tuple[str, ...], adc_bits: int, layer_sizes):
    """(cost_batch, norm_area, norm_power) for the area objective.

    ADC-only keeps the paper's objective literally — pruned comparator
    bank normalised to the conventional bank.  With more axes the
    objective widens to the whole printed system (bank + weighted-sum
    precision + activation circuits), normalised to the conventional bank
    plus the default (po2-8 / exact ReLU) bespoke MLP, so area gains from
    any gene group trade against accuracy in one front.
    """
    layer_sizes = list(layer_sizes)
    conv_area, conv_power = area_model.conventional_cost(layer_sizes[0], adc_bits)
    if axes == ("adc",):
        def cost_batch(dec: dict) -> tuple[np.ndarray, np.ndarray]:
            return area_model.adc_cost_batch(dec["masks"], adc_bits)

        return cost_batch, conv_area, conv_power

    mlp_area, mlp_power = area_model.mlp_pow2_cost(layer_sizes)

    def cost_batch(dec: dict) -> tuple[np.ndarray, np.ndarray]:
        return area_model.genome_area_batch(
            dec["masks"], adc_bits, layer_sizes,
            dec["weight_bits"], dec["act_bits"],
            act_sel=dec.get("act_sel"), wprec=dec.get("wprec"),
        )

    return cost_batch, conv_area + mlp_area, conv_power + mlp_power


@dataclasses.dataclass(frozen=True)
class _Problem:
    """What a search and the service backend share for one config: data
    split, MLP and evaluator configs, genome shape, the evaluator-row
    format and the (1 - acc, area ratio) objectives."""

    cfg: CodesignConfig
    spec: uci_synth.DatasetSpec
    split: tuple  # (X_tr, y_tr, X_te, y_te)
    mlp_cfg: qat.MLPConfig
    eval_cfg: trainer.EvalConfig
    axes: tuple[str, ...]
    n_layers: int
    cost_batch: Callable[[dict], tuple[np.ndarray, np.ndarray]]
    norm_area: float
    conv_area: float
    conv_power: float

    @property
    def n_mask_bits(self) -> int:
        return chromosome.n_mask_bits(self.spec.n_features, self.cfg.adc_bits)

    @property
    def cat_cards(self) -> tuple[int, ...]:
        return tuple(chromosome.cat_cardinalities(self.axes, self.n_layers))

    def decode(self, masks: np.ndarray, cats: np.ndarray) -> dict:
        return chromosome.decode_batch(
            masks, cats, self.spec.n_features, self.cfg.adc_bits,
            axes=self.axes, n_layers=self.n_layers,
        )

    def rows(self, masks, cats, seeds=None) -> tuple[dict, tuple]:
        """``(dec, rows)``: the decoded genomes and their evaluator rows
        ``(masks, wb, ab, bs, ep, lr, seed, *extra)``; seeds default to
        the genome-derived ones (:func:`_genome_seeds`)."""
        dec = self.decode(masks, cats)
        if seeds is None:
            seeds = _genome_seeds(masks, cats)
        return dec, (
            dec["masks"], dec["weight_bits"], dec["act_bits"],
            dec["batch_size"], dec["epochs"], dec["lr"], seeds,
            *_extra_rows(dec),
        )

    def area(self, dec: dict) -> np.ndarray:
        """The area objective: cost normalised to the conventional design."""
        return self.cost_batch(dec)[0] / self.norm_area

    @staticmethod
    def objectives(accs, area: np.ndarray) -> np.ndarray:
        return np.stack([1.0 - np.asarray(accs), area], axis=1)

    def wave(self, batches, launch) -> list:
        """Objectives of a stacked wave: every batch's rows go to
        ``launch``, whose returned ``resolve()`` gives one accuracy array
        per batch; the area pass runs in between.  Empty batches get
        ``None``."""
        decs, rows = zip(*(self.rows(m, c) for m, c in batches))
        resolve = launch(list(rows))
        areas = [self.area(d) for d in decs]
        return [
            self.objectives(a, ar) if len(ar) else None
            for a, ar in zip(resolve(), areas)
        ]


def _problem(cfg: CodesignConfig) -> _Problem:
    """Load and split the data and build the configs and cost function."""
    X, y, spec = uci_synth.load(cfg.dataset)
    mlp_cfg = qat.MLPConfig(
        layer_sizes=(spec.n_features, spec.hidden, spec.n_classes),
        adc_bits=cfg.adc_bits,
    )
    axes = cfg.axes()
    conv_area, conv_power = area_model.conventional_cost(spec.n_features, cfg.adc_bits)
    cost_batch, norm_area, _ = _make_cost_batch(axes, cfg.adc_bits, mlp_cfg.layer_sizes)
    return _Problem(
        cfg=cfg,
        spec=spec,
        split=tuple(uci_synth.stratified_split(X, y, 0.7, cfg.seed)),
        mlp_cfg=mlp_cfg,
        eval_cfg=trainer.EvalConfig(
            max_steps=cfg.max_steps, step_scale=cfg.step_scale, seed=cfg.seed,
            use_fused_kernel=cfg.use_fused_kernel, genome_axes=axes,
        ),
        axes=axes,
        n_layers=len(mlp_cfg.layer_sizes) - 1,
        cost_batch=cost_batch,
        norm_area=norm_area,
        conv_area=conv_area,
        conv_power=conv_power,
    )


def run_codesign(cfg: CodesignConfig) -> CodesignResult:
    with spans.span("codesign.search", seed=cfg.seed, dataset=cfg.dataset):
        return _run_codesign(cfg)


def _run_codesign(cfg: CodesignConfig) -> CodesignResult:
    cfg.validate()
    with spans.span("codesign.setup"):
        prob = _problem(cfg)
        # evaluators live in a mutable dict so the elastic-recovery path can
        # swap in re-meshed replacements mid-campaign: every objective callback
        # below reads the dict at call time, not at closure-capture time
        evaluators: dict = {
            "pop": trainer.make_population_evaluator(
                *prob.split, prob.mlp_cfg, prob.eval_cfg,
            )
        }
        if cfg.num_islands > 1 and cfg.stacked_islands:
            evaluators["islands"] = trainer.make_island_evaluator(
                *prob.split, prob.mlp_cfg, prob.eval_cfg,
                num_islands=cfg.num_islands,
            )

        def rebuild_evaluators(n_devices: int | None = None) -> None:
            """Re-lower every evaluator onto the first ``n_devices`` devices."""
            for name in list(evaluators):
                evaluators[name] = evaluators[name].rebuild(n_devices)

    # chaos-drill tap: every batch actually sent to an evaluator passes
    # through here (one ordinal per non-empty batch, row count accumulated)
    # BEFORE dispatch — an injected failure therefore interrupts the
    # generation with the batch's rows already counted, which is what lets
    # the chaos tests account for replayed rows exactly
    drill = cfg.drill
    _batch_ordinal = itertools.count()

    def _observe_batch(n_rows: int) -> None:
        if drill is None:
            return
        step = next(_batch_ordinal)
        drill.rows_dispatched += int(n_rows)
        if drill.injector is not None:
            drill.injector.maybe_slow(step)
            drill.injector.maybe_fail(step)

    def dispatch_evaluate(mask_genes: np.ndarray, cat_genes: np.ndarray):
        """Launch one batch's QAT program now; objectives on resolve().

        Resolved at once it is the blocking callback (``evaluate``
        below).  The accuracy program is only *dispatched*; the
        vectorized area pass then runs on the host WHILE the device
        trains, and the returned closure blocks and assembles the
        objectives.
        """
        with spans.span("codesign.decode"):
            dec, rows = prob.rows(mask_genes, cat_genes)
        _observe_batch(mask_genes.shape[0])
        resolve_acc = evaluators["pop"].dispatch(*rows)
        with spans.span("codesign.area"):
            area = prob.area(dec)

        def resolve() -> np.ndarray:
            with spans.span("codesign.wait"):
                accs = np.asarray(resolve_acc())
            return prob.objectives(accs, area)

        return resolve

    def evaluate(mask_genes: np.ndarray, cat_genes: np.ndarray) -> np.ndarray:
        """Blocking objective callback: dispatch, then resolve at once."""
        return dispatch_evaluate(mask_genes, cat_genes)()

    def evaluate_stacked(batches):
        """One ``trainer.make_island_evaluator`` program trains every
        island's unseen batch of a generation (called, not dispatched)."""
        def launch(rows):
            for m, _ in batches:
                if m.shape[0]:
                    _observe_batch(m.shape[0])
            accs = evaluators["islands"](rows)
            return lambda: accs

        return prob.wave(batches, launch)

    preload = None
    if cfg.memo_path and cfg.memoize and memo_store.memo_path_exists(cfg.memo_path):
        preload = memo_store.load_memo(cfg.memo_path, cfg.memo_fingerprint())
    ga_cfg = nsga2.NSGA2Config(
        pop_size=cfg.pop_size, n_generations=cfg.n_generations, seed=cfg.seed,
        memoize=cfg.memoize, crossover_rate=cfg.crossover_rate,
        mutation_rate=cfg.mutation_rate,
    )
    ga_kwargs = dict(
        n_mask_bits=prob.n_mask_bits,
        cat_cardinalities=prob.cat_cards,
        evaluate=evaluate,
        cfg=ga_cfg,
        memo=preload,
        screen=cfg.make_screen(prob.n_mask_bits, prob.cat_cards),
    )
    if cfg.num_islands > 1:
        ga = nsga2.IslandNSGA2(
            island_cfg=cfg.island_config(),
            stacked_evaluate=evaluate_stacked if cfg.stacked_islands else None,
            dispatch_evaluate=dispatch_evaluate if cfg.async_pipeline else None,
            **ga_kwargs,
        )
    else:
        ga = nsga2.NSGA2(**ga_kwargs)

    def run_ga(hook):
        return ga.run(checkpoint_hook=hook)

    if cfg.hybrid_warm_frac > 0.0 or cfg.hybrid_refine_every > 0:
        engines = ga.islands if cfg.num_islands > 1 else [ga]
        k_warm = int(cfg.hybrid_warm_frac * cfg.pop_size)  # per island
        hcfg = hybrid.HybridConfig(
            grad_steps=cfg.hybrid_grad_steps,
            # enough restarts that (after snapshot dedupe) every island can
            # usually be dealt its full warm share
            n_restarts=max(4, -(-k_warm * len(engines) // 4)),
            seed=cfg.seed,
        )
        if cfg.hybrid_refine_every > 0:
            refiner = hybrid.make_refiner(
                *prob.split[:2], prob.mlp_cfg.layer_sizes, cfg.adc_bits, prob.axes, hcfg
            )
            for eng in engines:
                eng.set_refiner(refiner, cfg.hybrid_refine_every)

        def _seed_warm_populations() -> None:
            """Descend, exact-score, and deal warm genomes across islands.

            Scoring goes through ``score_pool`` on island 0 — the shared
            memo's standard plan/commit contract, so the rows land in memo
            insertion order ahead of generation 0 and count as island-0
            evaluations (honest equal-rows accounting vs a pure GA).
            """
            wm, wc = hybrid.warm_start_genomes(
                *prob.split[:2], prob.mlp_cfg.layer_sizes, cfg.adc_bits, prob.axes, hcfg
            )
            if not wm.shape[0] or k_warm <= 0:
                return
            objs = engines[0].score_pool(wm, wc)
            # deal in Pareto order (rank asc, crowding desc within front),
            # round-robin so every island gets an even slice of the front
            fronts = nsga2.fast_non_dominated_sort(objs)
            order: list[int] = []
            for front in fronts:
                crowd = nsga2.crowding_distance(objs[front])
                order.extend(front[np.argsort(-crowd, kind="stable")].tolist())
            take = np.asarray(order[: k_warm * len(engines)], np.int64)
            for i, eng in enumerate(engines):
                sel = take[i :: len(engines)][:k_warm]
                if sel.size:
                    eng.seed_warm(wm[sel], wc[sel])

        inner_run_ga = run_ga

        def run_ga(hook):
            # fresh campaigns only: a restored engine (resume / in-process
            # rollback) already has its population — warm genomes only
            # shape generation 0
            if cfg.hybrid_warm_frac > 0.0 and engines[0].pop is None:
                _seed_warm_populations()
            return inner_run_ga(hook)

    recoveries = None
    if cfg.checkpoint_dir is not None or drill is not None:
        out, recoveries = _run_elastic(cfg, ga, run_ga, rebuild_evaluators)
    else:
        out = run_ga(None)
    if cfg.memo_path and cfg.memoize:
        memo_store.save_memo(cfg.memo_path, ga.memo, cfg.memo_fingerprint())

    # conventional-ADC baseline accuracy = full mask + default hyper-params,
    # evaluated explicitly over several inits (the [7] baseline is a tuned
    # bespoke circuit — take the best-trained replicate, not a lucky/unlucky
    # single seed).  Goes straight to the trainer with explicit replicate
    # seeds: the GA-facing ``evaluate`` derives seeds from the genome, which
    # would collapse identical replicates onto one init.
    n_seeds = 4
    with spans.span("codesign.baseline"):
        # all-zero categorical genes decode to the default/exact choice of
        # every gene group (po2-8 weights, exact ReLU), so the baseline stays
        # the [7] bespoke circuit whatever axes the search evolves
        _, base_rows = prob.rows(
            np.ones((n_seeds, prob.n_mask_bits), bool),
            np.zeros((n_seeds, len(prob.cat_cards)), np.int64),
            seeds=np.arange(n_seeds, dtype=np.int32),
        )
        conv_acc = float(np.asarray(evaluators["pop"](*base_rows)).max())

    with spans.span("codesign.result"):
        dec = prob.decode(out["masks"], out["cats"])
        front_area, front_power = prob.cost_batch(dec)
        front_acc = 1.0 - out["objs"][:, 0]
        return CodesignResult(
            dataset=cfg.dataset,
            spec=prob.spec,
            front_masks=dec["masks"],
            front_cats=out["cats"],
            front_acc=front_acc,
            front_area=front_area,
            front_power=front_power,
            conv_acc=conv_acc,
            conv_area=prob.conv_area,
            conv_power=prob.conv_power,
            history=out["history"],
            n_evaluations=int(out["n_evaluations"]),
            n_memo_hits=int(out["n_memo_hits"]),
            n_deferred=int(out.get("n_deferred", 0)),
            island_history=out.get("island_history"),
            migrations=out.get("migrations"),
            recoveries=recoveries,
            genome_axes=prob.axes,
        )


def make_service_backend(cfg: CodesignConfig, wave_slots: int = 4) -> dict:
    """Build the real-QAT wave backend for ``core.eval_service.EvalService``.

    The service's wave scheduler speaks the island-evaluator contract —
    ``wave_slots`` per-request ``(masks, cats)`` batches in, one
    objective array per slot out — so the backend is the stacked-islands
    objective of :func:`run_codesign` rebuilt for a fixed slot count:
    the same ``_problem`` (genome decode, crc32 genome seeds, area pass)
    and the same ``trainer.make_island_evaluator`` program.  A genome therefore gets
    the exact objective vector here that any campaign with the same
    :meth:`CodesignConfig.memo_fingerprint` computes, which is what makes
    the service's shared memo interchangeable with campaign memos on
    disk.

    Returns a dict with ``stacked_evaluate``, the genome shape
    (``n_mask_bits``, ``cat_cardinalities``), the memo ``fingerprint``,
    a ``screen_factory`` (``None`` unless ``cfg.surrogate`` — the service
    builds one fresh surrogate screen per request, mirroring its
    engine-local memo snapshots), and the dataset ``spec`` /
    ``conv_area`` for reporting.  The stacked
    program is *dispatched* (``island_evaluator.dispatch``) so the
    per-wave area pass runs on the host while the QAT wave trains on
    device — the same overlap a search's blocking callback has.
    """
    cfg.validate()
    prob = _problem(cfg)
    island_eval = trainer.make_island_evaluator(
        *prob.split, prob.mlp_cfg, prob.eval_cfg, num_islands=wave_slots,
    )
    screen_factory = (
        (lambda: cfg.make_screen(prob.n_mask_bits, prob.cat_cards))
        if cfg.surrogate
        else None
    )
    return {
        "stacked_evaluate": lambda batches: prob.wave(batches, island_eval.dispatch),
        "fingerprint": cfg.memo_fingerprint(),
        "n_mask_bits": prob.n_mask_bits,
        "cat_cardinalities": prob.cat_cards,
        "spec": prob.spec,
        "conv_area": prob.conv_area,
        "screen_factory": screen_factory,
    }


def _run_elastic(cfg: CodesignConfig, ga, run_ga, rebuild_evaluators):
    """Run the GA under the elastic runner: checkpoints, resume, recovery.

    Wires ``runtime.elastic.ElasticGARunner`` around the already-built
    engine: optional resume from the newest fingerprint-compatible
    checkpoint, a save callback firing every ``cfg.checkpoint_every``
    generation boundaries (plus straggler-urgent boundaries and the final
    one), a device probe honoring the drill's ``lose_devices``, and the
    evaluator rebuild hook for re-meshing onto survivors.  The manager is
    closed in a ``finally`` so a crashing campaign (e.g. an injected
    ``HostFailure``) still drains its queued async writes — that last
    durable boundary is exactly what the restarted process resumes from.
    """
    drill = cfg.drill
    mgr = (
        CheckpointManager(cfg.checkpoint_dir)
        if cfg.checkpoint_dir is not None
        else None
    )
    fp = cfg.search_fingerprint()
    if mgr is not None and cfg.resume:
        step = mgr.latest_step()
        if step is not None:
            tree, manifest = mgr.restore(step)
            stored = manifest.get("extra", {}).get("fingerprint", {})
            if memo_store._canonical(stored) != memo_store._canonical(fp):
                raise ValueError(
                    f"checkpoint at {cfg.checkpoint_dir} was written by a "
                    f"search configured {stored}, not {fp}; refusing to "
                    "resume an incompatible campaign"
                )
            ga.set_state({"arrays": tree, "meta": manifest["extra"]["meta"]})

    every = max(int(cfg.checkpoint_every), 1)

    def save_cb(driver, gens_done: int, urgent: bool) -> None:
        if mgr is None:
            return
        if urgent or gens_done % every == 0 or gens_done >= cfg.n_generations:
            st = driver.state_dict()
            mgr.save(
                gens_done,
                st["arrays"],
                extra={"meta": st["meta"], "fingerprint": fp},
            )

    if drill is not None and drill.lose_devices:
        def probe():
            return max(jax.device_count() - drill.lose_devices, 1)
    else:
        probe = None

    runner = elastic_rt.ElasticGARunner(
        driver=ga,
        run_fn=run_ga,
        rebuild=rebuild_evaluators,
        probe=probe,
        watchdog=(drill.watchdog if drill is not None else None),
        checkpoint_cb=save_cb,
        recover_on=(failure_rt.DeviceLossError,),
    )
    try:
        out = runner.run()
    finally:
        if mgr is not None:
            mgr.close()
    return out, runner.recoveries


def gains_at_budget(res: CodesignResult, acc_drop_budget: float = 0.05) -> dict:
    """Paper-style gains: best area/power reduction within an accuracy budget."""
    ok = res.front_acc >= (res.conv_acc - acc_drop_budget)
    if not ok.any():
        ok = res.front_acc >= res.front_acc.max() - 1e-9  # fall back to best acc
    idx = np.where(ok)[0]
    best = idx[np.argmin(res.front_area[idx])]
    return {
        "dataset": res.dataset,
        "budget": acc_drop_budget,
        "conv_acc": res.conv_acc,
        "acc": float(res.front_acc[best]),
        "area_gain": float(res.conv_area / max(res.front_area[best], 1e-12)),
        "power_gain": float(res.conv_power / max(res.front_power[best], 1e-12)),
        "kept_levels_mean": float(res.front_masks[best][:, 1:].sum(-1).mean()),
        "mask": res.front_masks[best],
        "cats": res.front_cats[best],
    }
