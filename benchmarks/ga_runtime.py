"""Paper §III-B runtime note: GA cost vs hardware-unaware training.

The paper reports ~120 min on a 64-core EPYC for the full search and
stresses the overhead over conventional training is minimal.  Two
beyond-paper engine measurements:

* ``run``: the population-vmapped evaluator collapses a whole generation
  into ONE compiled program — per-generation wall time vs an equivalent
  serial per-chromosome loop.
* ``run_memo``: the NSGA-II evaluation memo (results keyed on genome
  bytes) vs the paper-style naive engine that re-trains every chromosome
  in the selection pool each generation — QAT rows trained and
  per-generation wall-clock at EQUAL pop/generations.
* ``run_fused``: the fused pruned-ADC QAT kernel (``kernels/fused_qat``)
  vs the unfused quantize+matmul pair inside the SAME population-vmapped
  evaluator — per-generation wall clock plus the HBM traffic the fusion
  removes (``benchmarks/fused_qat.py`` has the op-level detail).
* ``run_islands``: island-model NSGA-II (``core.nsga2.IslandNSGA2``) vs
  the single-population engine at EQUAL total evaluation budget (K islands
  of P/K chromosomes vs one population of P, same generations) —
  per-generation wall clock, memo-hit rate, and the hypervolume of the
  merged cross-island Pareto front vs the single front; the island engine
  is additionally timed under the stacked (K, P) SPMD driver
  (``stacked_islands=True``, one cross-island program per generation)
  against the sequential island loop at bit-identical search results.
* ``run_hybrid``: the gradient/GA hybrid (``core.hybrid`` — relaxed
  warm-start + front-0 gradient refinement) vs the pure GA at EQUAL
  device budget: the pure baseline is granted extra generations until it
  has trained at least as many QAT rows as the hybrid search spent, and
  ``hybrid_hv_ratio`` compares the final front hypervolumes (gated
  >= 1.0 in ``benchmarks/baselines.json`` — the gradient injections must
  pay for the rows they consume).
* ``run_pipelined``: async generation pipelining (``async_pipeline=True``
  — non-blocking device dispatch, host variation/planning overlapped
  with in-flight QAT, block only at commit time) vs the synchronous
  driver at bit-identical search results, for both the single-population
  engine and the island engine — per-generation and blocked-time
  (``eval_s``) medians, the pipelined-vs-synchronous speedups, and
  identity flags.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import codesign, nsga2, qat, trainer
from repro.data import uci_synth


def run(pop: int = 12, steps: int = 150) -> dict:
    """Vmapped-vs-serial per-generation wall clock (one SPMD program)."""
    X, y, spec = uci_synth.load("seeds")
    Xtr, ytr, Xte, yte = uci_synth.stratified_split(X, y)
    cfg = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes))
    ev = trainer.make_population_evaluator(
        Xtr, ytr, Xte, yte, cfg, trainer.EvalConfig(max_steps=steps)
    )
    # serial path gets granule 1 so it trains exactly one chromosome per call
    ev1 = trainer.make_population_evaluator(
        Xtr, ytr, Xte, yte, cfg, trainer.EvalConfig(max_steps=steps, pad_granule=1)
    )
    rng = np.random.default_rng(0)
    masks = rng.uniform(size=(pop, spec.n_features, 16)) < 0.7
    wb = np.full(pop, 8.0, np.float32)
    ab = np.full(pop, 4.0, np.float32)
    bs = np.full(pop, 64, np.int32)
    ep = np.full(pop, 120, np.int32)
    lr = np.full(pop, 0.05, np.float32)
    seeds = np.arange(pop, dtype=np.int32)

    # warm up (compile once)
    np.asarray(ev(masks, wb, ab, bs, ep, lr, seeds))
    t0 = time.time()
    np.asarray(ev(masks, wb, ab, bs, ep, lr, seeds))
    t_vmapped = time.time() - t0

    # serial: one chromosome at a time through the same compiled program
    def one(i):
        return ev1(
            masks[i : i + 1], wb[:1], ab[:1], bs[:1], ep[:1], lr[:1], seeds[i : i + 1]
        )
    np.asarray(one(0))  # warm up the P=1 shape
    t0 = time.time()
    for i in range(pop):
        np.asarray(one(i))
    t_serial = time.time() - t0

    return {
        "pop": pop,
        "steps": steps,
        "vmapped_s_per_gen": round(t_vmapped, 3),
        "serial_s_per_gen": round(t_serial, 3),
        "speedup": round(t_serial / max(t_vmapped, 1e-9), 2),
    }


def run_memo(
    pop: int = 12, gens: int = 20, steps: int = 60, mutation_rate: float = 0.01
) -> dict:
    """Memoized vs naive re-evaluating engine at EQUAL pop/generations.

    Both runs use identical search settings on the same dataset; the only
    difference is ``CodesignConfig.memoize``.  The naive engine trains the
    full parent+child pool (2P rows) every generation — the paper's flow;
    the memo engine trains only genomes it has never seen (survivors are
    free, and as the search converges duplicate children add further
    savings).  ``mutation_rate=0.01`` per gene sits between the paper's
    0.2% operator and the engine default 2%.
    """
    out = {}
    for label, memo in (("memo", True), ("naive", False)):
        cfg = codesign.CodesignConfig(
            dataset="seeds", pop_size=pop, n_generations=gens,
            step_scale=0.2, max_steps=steps, memoize=memo,
            mutation_rate=mutation_rate,
        )
        t0 = time.time()
        res = codesign.run_codesign(cfg)
        gen_s = [h["gen_s"] for h in res.history]
        out[label] = {
            "qat_rows_trained": res.n_evaluations,
            "memo_hits": res.n_memo_hits,
            "wall_s": round(time.time() - t0, 2),
            # median, not mean: generations that first hit a new population
            # bucket pay a one-off JIT compile that would otherwise swamp
            # the steady-state per-generation number
            "gen_s_median": round(float(np.median(gen_s)), 3),
            "gen_s_mean": round(float(np.mean(gen_s)), 3),
            "gen_s": gen_s,
        }
    out["pop"] = pop
    out["gens"] = gens
    out["eval_reduction"] = round(
        out["naive"]["qat_rows_trained"] / max(out["memo"]["qat_rows_trained"], 1), 2
    )
    # honest split of where the memo savings come from: survivor reuse is
    # structural (P cached parents resubmitted per generation); anything
    # beyond that is genuine duplicate-child dedup across the run
    out["survivor_reuse_rows"] = pop * gens
    out["duplicate_dedup_rows"] = pop * (1 + gens) - out["memo"]["qat_rows_trained"]
    return out


def run_surrogate(
    pop: int = 12,
    gens: int = 24,
    steps: int = 60,
    min_rows: int = 24,
    explore_frac: float = 0.1,
    dataset: str = "seeds",
) -> dict:
    """Surrogate pre-screening vs the exact path at EQUAL search budget.

    Two otherwise identical memoized searches (the ``run_memo`` budget
    class): the exact engine trains every planned-unseen genome; the
    screened engine (``CodesignConfig.surrogate``) trains only the
    memo-trained MLP ensemble's predicted-undominated subset plus the
    seeded exploration slice, deferring the rest with flagged
    predictions.  Reported: QAT rows trained on each side,
    ``rows_saved_ratio`` (exact rows / surrogate rows — the headline,
    gated at >= 2x in ``benchmarks/baselines.json``), the deferred-row
    count, and ``hv_ratio`` — the screened front's hypervolume over the
    exact front's at the shared ``HV_REF`` reference (gated >= 0.98:
    the saved rows must not cost front quality).  Both fronts are built
    from exact objectives only (the screen's final-generation rule), so
    the hv comparison is honest.
    """
    out: dict = {
        "pop": pop, "gens": gens, "min_rows": min_rows,
        "explore_frac": explore_frac,
    }
    base = dict(
        dataset=dataset, pop_size=pop, n_generations=gens,
        step_scale=0.2, max_steps=steps,
    )
    configs = {
        "exact": codesign.CodesignConfig(**base),
        "surrogate": codesign.CodesignConfig(
            surrogate=True, surrogate_min_rows=min_rows,
            surrogate_explore_frac=explore_frac, **base,
        ),
    }
    for label, cfg in configs.items():
        t0 = time.time()
        res = codesign.run_codesign(cfg)
        gen_s = [h["gen_s"] for h in res.history]
        out[label] = {
            "qat_rows_trained": res.n_evaluations,
            "memo_hits": res.n_memo_hits,
            "deferred": res.n_deferred,
            "front_size": int(res.front_acc.size),
            "gen_s_median": round(float(np.median(gen_s)), 3),
            "wall_s": round(time.time() - t0, 2),
            "hypervolume": round(
                nsga2.hypervolume_2d(_front_objectives(res), HV_REF), 4
            ),
        }
    out["rows_saved_ratio"] = round(
        out["exact"]["qat_rows_trained"]
        / max(out["surrogate"]["qat_rows_trained"], 1),
        2,
    )
    out["hv_ratio"] = round(
        out["surrogate"]["hypervolume"] / max(out["exact"]["hypervolume"], 1e-12),
        3,
    )
    out["wall_speedup"] = round(
        out["exact"]["wall_s"] / max(out["surrogate"]["wall_s"], 1e-9), 2
    )
    return out


def run_hybrid(
    pop: int = 12,
    gens: int = 8,
    steps: int = 60,
    warm_frac: float = 0.5,
    refine_every: int = 3,
    grad_steps: int = 40,
    dataset: str = "seeds",
    max_extra_gens: int = 24,
) -> dict:
    """Gradient/GA hybrid vs pure GA at EQUAL device budget.

    The hybrid search (``hybrid_warm_frac`` + ``hybrid_refine_every``)
    spends QAT rows on exactly re-scoring its hardened descent states and
    refinement children on top of the normal generation rows.  To keep
    the comparison honest, the pure-GA baseline is re-run with its
    generation count raised until it has trained AT LEAST as many QAT
    rows as the hybrid run spent — the pure side never gets less device
    budget than the hybrid side.  ``hybrid_hv_ratio`` is then the hybrid
    front's hypervolume over the budget-matched pure front's at the
    shared ``HV_REF`` reference; the gate (>= 1.0, gated as
    ``hybrid_hv_ratio`` in ``benchmarks/baselines.json``) asserts the
    gradient injections at least pay for the rows they consume.
    """
    base = dict(
        dataset=dataset, pop_size=pop, step_scale=0.2, max_steps=steps
    )
    out: dict = {
        "pop": pop, "gens": gens, "warm_frac": warm_frac,
        "refine_every": refine_every, "grad_steps": grad_steps,
    }
    t0 = time.time()
    res_h = codesign.run_codesign(
        codesign.CodesignConfig(
            n_generations=gens, hybrid_warm_frac=warm_frac,
            hybrid_refine_every=refine_every, hybrid_grad_steps=grad_steps,
            **base,
        )
    )
    out["hybrid"] = {
        "qat_rows_trained": res_h.n_evaluations,
        "memo_hits": res_h.n_memo_hits,
        "front_size": int(res_h.front_acc.size),
        "wall_s": round(time.time() - t0, 2),
        "hypervolume": round(
            nsga2.hypervolume_2d(_front_objectives(res_h), HV_REF), 4
        ),
    }
    # budget-match: give the pure GA more generations until it has trained
    # at least as many rows as the hybrid spent (never fewer)
    pure_gens = gens
    while True:
        t0 = time.time()
        res_p = codesign.run_codesign(
            codesign.CodesignConfig(n_generations=pure_gens, **base)
        )
        if (
            res_p.n_evaluations >= res_h.n_evaluations
            or pure_gens >= gens + max_extra_gens
        ):
            break
        # scale the remaining row deficit by the observed per-generation rate
        rate = max(res_p.n_evaluations / max(pure_gens, 1), 1.0)
        deficit = res_h.n_evaluations - res_p.n_evaluations
        pure_gens += max(1, int(np.ceil(deficit / rate)))
    out["pure"] = {
        "gens": pure_gens,
        "qat_rows_trained": res_p.n_evaluations,
        "memo_hits": res_p.n_memo_hits,
        "front_size": int(res_p.front_acc.size),
        "wall_s": round(time.time() - t0, 2),
        "hypervolume": round(
            nsga2.hypervolume_2d(_front_objectives(res_p), HV_REF), 4
        ),
    }
    out["hybrid_hv_ratio"] = round(
        out["hybrid"]["hypervolume"] / max(out["pure"]["hypervolume"], 1e-12),
        3,
    )
    return out


def run_fused(pop: int = 12, steps: int = 150) -> dict:
    """Fused-vs-unfused per-generation wall clock at the ``run`` shapes."""
    try:
        from benchmarks import fused_qat as fused_bench
    except ModuleNotFoundError:
        # script invocation (python benchmarks/ga_runtime.py): sys.path[0]
        # is benchmarks/ itself, so the sibling imports flat
        import fused_qat as fused_bench

    return fused_bench.run_generation(pop=pop, steps=steps)


# reference point for front hypervolumes in (1 - acc, area / conv_area)
# space: obj0 is bounded by 1 (zero accuracy) and obj1 by 1 (the full
# conventional mask); 1.1 on the area axis keeps the unpruned anchor point
# contributing instead of sitting exactly on the reference boundary.
HV_REF = (1.0, 1.1)


def _front_objectives(res: codesign.CodesignResult) -> np.ndarray:
    """A CodesignResult front in minimisation space: (1-acc, area ratio)."""
    return np.stack(
        [1.0 - res.front_acc, res.front_area / res.conv_area], axis=1
    )


def run_islands(
    pop: int = 24,
    islands: int = 2,
    gens: int = 8,
    steps: int = 60,
    migration_interval: int = 2,
    dataset: str = "seeds",
) -> dict:
    """Island-model vs single-population engine at EQUAL evaluation budget.

    The single engine runs one population of ``pop``; the island engine
    runs ``islands`` sub-populations of ``pop // islands`` for the same
    generation count, so both sides draw the same number of candidate
    rows per generation.  Reported per engine: QAT rows actually trained,
    memo-hit rate, per-generation wall clock, and the hypervolume of the
    final (merged) Pareto front in (1-acc, normalised-area) space at the
    shared reference point ``HV_REF``.

    The island engine is measured twice: the sequential reference driver
    and the stacked driver (``stacked_islands=True``) that evaluates all
    K islands' unseen genomes as ONE cross-island SPMD program per
    generation.  Both produce identical searches (same rows trained, same
    merged front — asserted in ``stacked_matches_sequential``), so the
    comparison isolates the per-generation wall-clock effect of stacking:
    ``stacked_gen_speedup`` is sequential-islands median gen_s over
    stacked median gen_s (≈1 on one device where the stack adds nothing;
    > 1 on a multi-device host where the sequential loop leaves K-1
    device groups idle per island step).

    Default split: 2 islands of 12.  Measured on this workload, NSGA-II's
    front maintenance degrades once a sub-population drops below ~12
    chromosomes (the front no longer fits), so prefer island counts that
    keep ``pop // islands`` >= 12; at that size the merged front matches
    or beats the single population across seeds while each island stays
    an independent device-group-sized work unit.
    """
    if pop % islands:
        raise ValueError(f"pop={pop} must divide evenly into {islands} islands")
    base = dict(
        dataset=dataset, n_generations=gens, step_scale=0.2, max_steps=steps
    )
    island_kw = dict(
        pop_size=pop // islands, num_islands=islands,
        migration_interval=migration_interval,
    )
    configs = {
        "single": codesign.CodesignConfig(pop_size=pop, **base),
        "islands": codesign.CodesignConfig(**island_kw, **base),
        "islands_stacked": codesign.CodesignConfig(
            stacked_islands=True, **island_kw, **base
        ),
    }
    out: dict = {"pop_total": pop, "n_islands": islands, "gens": gens}
    for label, cfg in configs.items():
        t0 = time.time()
        res = codesign.run_codesign(cfg)
        gen_s = [h["gen_s"] for h in res.history]
        submitted = res.n_evaluations + res.n_memo_hits
        out[label] = {
            "front_size": int(res.front_acc.size),
            "qat_rows_trained": res.n_evaluations,
            "memo_hits": res.n_memo_hits,
            "memo_hit_rate": round(res.n_memo_hits / max(submitted, 1), 3),
            "gen_s_median": round(float(np.median(gen_s)), 3),
            "wall_s": round(time.time() - t0, 2),
            "hypervolume": round(
                nsga2.hypervolume_2d(_front_objectives(res), HV_REF), 4
            ),
        }
        if label.startswith("islands"):
            out[label]["migration_waves"] = len(res.migrations or [])
            out[label]["migrants_accepted"] = sum(
                sum(w["accepted"]) for w in (res.migrations or [])
            )
    out["hv_ratio"] = round(
        out["islands"]["hypervolume"] / max(out["single"]["hypervolume"], 1e-12),
        3,
    )
    # stacked is the SAME search in fewer programs: identical rows trained
    # and merged front, so the gen_s delta below is pure driver overhead
    out["stacked_matches_sequential"] = bool(
        out["islands_stacked"]["qat_rows_trained"]
        == out["islands"]["qat_rows_trained"]
        and out["islands_stacked"]["hypervolume"] == out["islands"]["hypervolume"]
    )
    out["stacked_gen_speedup"] = round(
        out["islands"]["gen_s_median"]
        / max(out["islands_stacked"]["gen_s_median"], 1e-9),
        2,
    )
    return out


def run_pipelined(
    pop: int = 16,
    islands: int = 2,
    gens: int = 6,
    steps: int = 60,
    migration_interval: int = 2,
    dataset: str = "seeds",
) -> dict:
    """Async-pipelined vs synchronous driver at bit-identical searches.

    Four searches on the same dataset: the single-population engine and
    the K-island engine, each with ``async_pipeline`` off and on.  The
    async driver computes exactly what the synchronous one does — same
    RNG order, same memo insertion order, so ``*_matches_sync`` asserts
    identical rows trained and identical front hypervolume — it only
    moves *when the host blocks*: island batches are dispatched as
    non-blocking device programs and the host plans the next island's
    pool while they train.  On one population the flag changes nothing
    (the blocking callback already overlaps the area pass), so the
    single pair times one code path twice.

    Reported per engine: per-generation wall-clock median (``gen_s``) and
    the blocked-time median (``eval_s`` — for the async island driver
    this is the time commits actually spent waiting on in-flight
    programs, the quantity pipelining shrinks).  ``*_pipeline_speedup``
    is the synchronous over async per-generation median.  Expect ≈1 on a
    host where QAT dominates wall clock and the GA's host side is cheap;
    the win grows with host-side variation cost (large populations /
    many islands) and with device count, where the hidden host latency
    would otherwise serialise against every wave.
    """
    if pop % islands:
        raise ValueError(f"pop={pop} must divide evenly into {islands} islands")
    base = dict(
        dataset=dataset, n_generations=gens, step_scale=0.2, max_steps=steps
    )
    island_kw = dict(
        pop_size=pop // islands, num_islands=islands,
        migration_interval=migration_interval,
    )
    configs = {
        "single_sync": codesign.CodesignConfig(pop_size=pop, **base),
        "single_async": codesign.CodesignConfig(
            pop_size=pop, async_pipeline=True, **base
        ),
        "islands_sync": codesign.CodesignConfig(**island_kw, **base),
        "islands_async": codesign.CodesignConfig(
            async_pipeline=True, **island_kw, **base
        ),
    }
    out: dict = {"pop_total": pop, "n_islands": islands, "gens": gens}
    for label, cfg in configs.items():
        t0 = time.time()
        res = codesign.run_codesign(cfg)
        gen_s = [h["gen_s"] for h in res.history]
        eval_s = [h["eval_s"] for h in res.history]
        out[label] = {
            "qat_rows_trained": res.n_evaluations,
            "memo_hits": res.n_memo_hits,
            "gen_s_median": round(float(np.median(gen_s)), 3),
            "eval_s_median": round(float(np.median(eval_s)), 3),
            "wall_s": round(time.time() - t0, 2),
            "hypervolume": round(
                nsga2.hypervolume_2d(_front_objectives(res), HV_REF), 4
            ),
        }
    for side in ("single", "islands"):
        sync, asyn = out[f"{side}_sync"], out[f"{side}_async"]
        # the async driver is the SAME search: identical rows trained and
        # identical front, so the gen_s delta is pure dispatch overlap
        out[f"{side}_async_matches_sync"] = bool(
            sync["qat_rows_trained"] == asyn["qat_rows_trained"]
            and sync["hypervolume"] == asyn["hypervolume"]
        )
        out[f"{side}_pipeline_speedup"] = round(
            sync["gen_s_median"] / max(asyn["gen_s_median"], 1e-9), 2
        )
    return out


if __name__ == "__main__":
    r = run()
    print(f"vmapped generation: {r['vmapped_s_per_gen']}s  "
          f"serial: {r['serial_s_per_gen']}s  speedup x{r['speedup']}")
    m = run_memo()
    print(f"QAT rows trained at equal pop/gens (P={m['pop']}, G={m['gens']}): "
          f"naive={m['naive']['qat_rows_trained']} memo={m['memo']['qat_rows_trained']} "
          f"-> x{m['eval_reduction']} fewer evaluations")
    print(f"per-generation wall-clock median: naive={m['naive']['gen_s_median']}s "
          f"memo={m['memo']['gen_s_median']}s (memo hits: {m['memo']['memo_hits']})")
    print(f"memo savings split: survivor reuse {m['survivor_reuse_rows']} rows "
          f"(structural), duplicate-child dedup {m['duplicate_dedup_rows']} rows")
    f = run_fused()
    print(f"fused kernel per-generation: fused={f['fused_s_per_gen']}s "
          f"unfused={f['unfused_s_per_gen']}s x{f['speedup']} "
          f"({f['bytes_saved_per_gen']}B intermediate HBM traffic saved/gen)")
    i = run_islands()
    print(f"islands (K={i['n_islands']}, equal budget P={i['pop_total']}): "
          f"hypervolume merged={i['islands']['hypervolume']} "
          f"single={i['single']['hypervolume']} (x{i['hv_ratio']})")
    print(f"islands memo-hit rate {i['islands']['memo_hit_rate']} vs "
          f"single {i['single']['memo_hit_rate']}; "
          f"{i['islands']['migrants_accepted']} migrants accepted over "
          f"{i['islands']['migration_waves']} waves; per-gen median "
          f"{i['islands']['gen_s_median']}s vs {i['single']['gen_s_median']}s")
    print(f"stacked islands: per-gen median {i['islands_stacked']['gen_s_median']}s "
          f"vs sequential {i['islands']['gen_s_median']}s "
          f"(x{i['stacked_gen_speedup']}, "
          f"identical search: {i['stacked_matches_sequential']})")
    p = run_pipelined()
    print(f"async pipeline (single): per-gen median "
          f"{p['single_async']['gen_s_median']}s vs sync "
          f"{p['single_sync']['gen_s_median']}s "
          f"(x{p['single_pipeline_speedup']}, "
          f"identical search: {p['single_async_matches_sync']})")
    print(f"async pipeline (K={p['n_islands']} islands): per-gen median "
          f"{p['islands_async']['gen_s_median']}s vs sync "
          f"{p['islands_sync']['gen_s_median']}s "
          f"(x{p['islands_pipeline_speedup']}, blocked-time median "
          f"{p['islands_async']['eval_s_median']}s vs "
          f"{p['islands_sync']['eval_s_median']}s, "
          f"identical search: {p['islands_async_matches_sync']})")
    s = run_surrogate()
    print(f"surrogate screening (P={s['pop']}, G={s['gens']}): "
          f"QAT rows exact={s['exact']['qat_rows_trained']} "
          f"screened={s['surrogate']['qat_rows_trained']} "
          f"(x{s['rows_saved_ratio']} fewer, "
          f"{s['surrogate']['deferred']} deferred) at "
          f"hypervolume ratio {s['hv_ratio']} "
          f"({s['surrogate']['hypervolume']} vs {s['exact']['hypervolume']})")
    h = run_hybrid()
    print(f"gradient/GA hybrid (P={h['pop']}, G={h['gens']}): "
          f"QAT rows hybrid={h['hybrid']['qat_rows_trained']} "
          f"pure={h['pure']['qat_rows_trained']} "
          f"(pure granted {h['pure']['gens']} gens) at "
          f"hypervolume ratio {h['hybrid_hv_ratio']} "
          f"({h['hybrid']['hypervolume']} vs {h['pure']['hypervolume']})")
