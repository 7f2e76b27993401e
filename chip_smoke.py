"""Smoke run of the co-design search on a TPU: the quickest proof it starts.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # stacked vs sequential islands, 4 chips

One process, phases in order; any failure exits non-zero.  Without a TPU
the script exits non-zero before any search runs: there is no CPU
fallback.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One chip:

* ``main``: ``run_codesign`` on cardio (21x5x3, the widest paper topology)
  at the paper budget of ``configs.printed_mlp`` (pop 24, 600 steps,
  batch 128), cut from 16 generations to 3.
* ``fused``: the same search with the fused pruned-ADC Pallas kernel.
  The population program must hold a compiled ``tpu_custom_call``, and
  its per-row accuracies are compared with the XLA program's on the same
  front genomes.
* ``reference``: front genomes re-scored one row at a time by the plain
  float32 program (no vmap, no mesh, ``default_matmul_precision
  ("highest")``) against the evaluator's accuracies.  On the TPU a
  default-precision f32 dot runs in bf16 passes, so training trajectories
  drift apart and a few test samples flip: the check is a tolerance.  The
  same rows re-scored by the evaluator in another batch must match the
  search bit for bit.

Four chips (``--four-chips``, only this phase): four stacked islands over
``island_mesh`` against the sequential island loop with the same seed.
Fronts and memo insertion order must be equal, and the stacked arrays
must span all four devices.

Each phase prints its wall time and its compile seconds and counts (from
``jax.monitoring``) before the last line.  The compile cache lives where
``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import printed_mlp  # noqa: E402
from repro.core import codesign, memo_store, nsga2, trainer  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

DATASET = "cardio"
N_GENERATIONS = 3   # the only cut: printed_mlp's full budget runs 16
N_ROWS = 8          # front genomes re-scored by the fused and reference checks
# Per-row accuracy tolerances (fractions of cardio's 638 test samples).
# On a v5e the fused kernel matched the XLA pair bit for bit on every row.
FUSED_ATOL = 0.0
# The f32 reference at "highest" vs the evaluator's default-precision dots
# (bf16 passes) differed by at most 6 test samples (0.0094) over 8 front
# genomes after 600 steps; the bound is about twice that.
REF_ATOL = 0.02

_durations: dict[str, list[float]] = collections.defaultdict(list)
_events: collections.Counter = collections.Counter()


def _listen() -> None:
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: _durations[name].append(secs)
    )
    jax.monitoring.register_event_listener(lambda name, **kw: _events.update([name]))


def _report(phase: str, t0: float, **extra) -> None:
    """One line per phase: wall time, compile time and counts, extras."""
    backend = _durations.pop("/jax/core/compile/backend_compile_duration", [])
    lower = _durations.pop("/jax/core/compile/jaxpr_to_mlir_module_duration", [])
    line = {
        "phase": phase,
        "wall_s": time.perf_counter() - t0,
        "backend_compile_s": sum(backend),
        "n_backend_compiles": len(backend),
        "lower_s": sum(lower),
        "cache_hits": _events.pop("/jax/compilation_cache/cache_hits", 0),
        "cache_misses": _events.pop("/jax/compilation_cache/cache_misses", 0),
        **extra,
    }
    _durations.clear()
    _events.clear()
    print(json.dumps(line), flush=True)


def phase_device(n_chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"jax {jax.__version__}, device_kind {info['kind']!r}, "
          f"{info['count']} device(s)", flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"no TPU: JAX's first device is on {info['platform']!r}")
    if info["count"] < n_chips:
        sys.exit(f"needs {n_chips} chips, JAX sees {info['count']}")
    return info


def run_search(cfg: codesign.CodesignConfig):
    """``run_codesign`` with its memo saved; returns (result, memo)."""
    with tempfile.TemporaryDirectory() as d:
        cfg = dataclasses.replace(cfg, memo_path=os.path.join(d, "memo"))
        res = codesign.run_codesign(cfg)
        memo = memo_store.load_memo(cfg.memo_path, cfg.memo_fingerprint())
    return res, memo


def _check_result(res) -> None:
    assert res.front_acc.size > 0, "empty Pareto front"
    assert np.isfinite(res.front_acc).all(), res.front_acc
    assert np.isfinite(res.conv_acc), res.conv_acc


def _search_summary(res) -> dict:
    return {
        "gen_s": [h["gen_s"] for h in res.history],
        "qat_rows_trained": res.n_evaluations,
        "memo_hits": res.n_memo_hits,
        "front_size": int(res.front_acc.size),
        "front_acc_max": float(res.front_acc.max()),
        "conv_acc": res.conv_acc,
    }


def phase_main(cfg: codesign.CodesignConfig):
    t0 = time.perf_counter()
    res, memo = run_search(cfg)
    _check_result(res)
    _report("main", t0, **_search_summary(res))
    return res, memo


def _evaluator(prob, fused: bool = False):
    """A population evaluator as ``run_codesign`` builds it for ``prob``."""
    ecfg = dataclasses.replace(prob.eval_cfg, use_fused_kernel=fused)
    return trainer.make_population_evaluator(*prob.split, prob.mlp_cfg, ecfg)


def front_rows(prob, memo: dict, n: int):
    """Evaluator rows of ``n`` genomes of the memo's first front.

    Returns ``(rows, miss, n_front)``: the evaluator rows ``prob.rows``
    builds (cycled when the front has fewer than ``n`` members), each
    row's accuracy miss as the search recorded it, and the front's size.  The
    miss is ``1 - acc`` as ``run_codesign`` computes it from the
    evaluator's float32 accuracy, so ``1.0 - acc`` of a re-scored float32
    row compares to it exactly.
    """
    keys = list(memo)
    objs = np.stack([memo[k] for k in keys])
    front = nsga2.fast_non_dominated_sort(objs)[0]
    pick = np.resize(front, n)
    n_mask = prob.n_mask_bits
    masks = np.stack([np.frombuffer(keys[i][:n_mask], np.uint8) for i in pick])
    cats = np.stack([np.frombuffer(keys[i][n_mask:], np.int64) for i in pick])
    _, rows = prob.rows(masks.astype(bool), cats)
    return rows, objs[pick, 0], len(front)


def assert_kernel_compiled(ev, rows) -> None:
    """The population program holds the Pallas kernel, not its interpreter."""
    lowered = ev.program.lower(*(ev.shard_fn(a) for a in rows))
    assert "tpu_custom_call" in lowered.as_text(), "fused kernel not compiled"


def phase_fused(cfg: codesign.CodesignConfig, main_res, main_memo) -> None:
    t0 = time.perf_counter()
    res, _ = run_search(dataclasses.replace(cfg, use_fused_kernel=True))
    _check_result(res)
    prob = codesign._problem(cfg)
    rows, _, n_front = front_rows(prob, main_memo, N_ROWS)
    ev_xla, ev_fused = (_evaluator(prob, fused) for fused in (False, True))
    assert_kernel_compiled(ev_fused, rows)
    acc_xla = np.asarray(ev_xla(*rows))
    acc_fused = np.asarray(ev_fused(*rows))
    diff = np.abs(acc_fused - acc_xla)
    same_front = (
        res.front_acc.shape == main_res.front_acc.shape
        and np.array_equal(res.front_acc, main_res.front_acc)
        and np.array_equal(res.front_cats, main_res.front_cats)
        and np.array_equal(res.front_masks, main_res.front_masks)
    )
    _report(
        "fused", t0, **_search_summary(res),
        tpu_custom_call=True, front_equals_xla_search=bool(same_front),
        rows=N_ROWS, distinct_front_genomes=min(n_front, N_ROWS),
        rows_bit_identical=int((diff == 0).sum()),
        max_abs_diff=float(diff.max()), atol=FUSED_ATOL,
        acc_xla=acc_xla.tolist(), acc_fused=acc_fused.tolist(),
    )
    assert diff.max() <= FUSED_ATOL, (acc_fused, acc_xla)


def phase_reference(cfg: codesign.CodesignConfig, main_memo) -> None:
    t0 = time.perf_counter()
    prob = codesign._problem(cfg)
    rows, miss, _ = front_rows(prob, main_memo, N_ROWS)
    acc_batch = np.asarray(_evaluator(prob)(*rows))
    with jax.default_matmul_precision("highest"):
        train_one = jax.jit(trainer._make_train_one(prob.mlp_cfg, prob.eval_cfg))
        data = trainer._split_args(*prob.split, prob.eval_cfg.seed)
        ref = np.asarray([
            train_one(*(np.asarray(a)[i] for a in rows), data) for i in range(N_ROWS)
        ])
    diff = np.abs((1.0 - ref) - miss)
    n_test = prob.split[3].shape[0]
    _report(
        "reference", t0, rows=N_ROWS, n_test=n_test,
        max_abs_diff=float(diff.max()), mean_abs_diff=float(diff.mean()),
        max_diff_test_samples=float(diff.max() * n_test), atol=REF_ATOL,
        rescored_equals_search=bool(np.array_equal(1.0 - acc_batch, miss)),
        acc_search=(1.0 - miss).tolist(), acc_ref=ref.tolist(),
    )
    assert np.isfinite(ref).all(), ref
    assert diff.max() <= REF_ATOL, (ref, 1.0 - miss)
    # the memo's premise: a row's result does not depend on its batch
    assert np.array_equal(1.0 - acc_batch, miss), (acc_batch, 1.0 - miss)


def phase_four_chips(cfg: codesign.CodesignConfig) -> None:
    """Stacked islands on four chips against the sequential island loop."""
    t0 = time.perf_counter()
    # interval 2 puts one migration wave inside the 3 generations
    icfg = dataclasses.replace(cfg, num_islands=4, migration_interval=2)
    seq, seq_memo = run_search(icfg)
    stk, stk_memo = run_search(dataclasses.replace(icfg, stacked_islands=True))
    _check_result(stk)
    for field in ("front_masks", "front_cats", "front_acc", "front_area"):
        np.testing.assert_array_equal(getattr(stk, field), getattr(seq, field))
    assert list(stk_memo) == list(seq_memo), "memo insertion order differs"
    for k in seq_memo:
        np.testing.assert_array_equal(stk_memo[k], seq_memo[k])
    assert stk.n_evaluations == seq.n_evaluations

    # the stacked evaluator run_codesign builds: inputs and outputs must
    # span all four chips, not land on the first
    prob = codesign._problem(cfg)
    ev = trainer.make_island_evaluator(*prob.split, prob.mlp_cfg, prob.eval_cfg,
                                       num_islands=4)
    rows, _, _ = front_rows(prob, stk_memo, 4 * ev.granule)
    stacked = [ev.shard_fn(np.reshape(a, (4, ev.granule) + a.shape[1:]))
               for a in rows]
    out = ev.program(*stacked)
    in_devs = {len(a.sharding.device_set) for a in stacked}
    assert in_devs == {4}, in_devs
    assert len(out.sharding.device_set) == 4, out.sharding
    _report(
        "four_chips", t0, mesh=dict(ev.mesh.shape),
        stacked_devices=len(out.sharding.device_set),
        fronts_equal=True, memo_order_equal=True, memo_entries=len(stk_memo),
        migration_waves=len(stk.migrations or []),
        seq=_search_summary(seq), stacked=_search_summary(stk),
    )


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip stacked-island phase")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1

    t0 = time.perf_counter()
    info = phase_device(n_chips)
    cache_dir = enable_compile_cache()
    _listen()
    _report("device", t0, jax=jax.__version__, cache_dir=cache_dir)

    cfg = dataclasses.replace(
        printed_mlp.codesign_config(DATASET, full=True), n_generations=N_GENERATIONS
    )
    if args.four_chips:
        phase_four_chips(cfg)
    else:
        main_res, main_memo = phase_main(cfg)
        phase_fused(cfg, main_res, main_memo)
        phase_reference(cfg, main_memo)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
