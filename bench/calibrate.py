"""Readings the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3
        [--control] [--faults] [--fresh 901,902] [--trace-fixture PATH]

One process sets the cell up once, then for each seed runs a window at the
cell's own size and load and prints the numbers ``check`` compares (the
program against the float32 reference).  ``--control`` adds the same
numbers for the bfloat16 reference in the program's place; ``--faults``
for each planted fault: the float32 reference in the program's place with
its state left unchanged (``frozen``: learning rate 0) or with each step's
loss the mean over half of its minibatch (``half_batch``), and the
program's answers each given to another row of the same call
(``altered``).  The benchmark's own runs never run these.  ``--fresh``
(search cells) then times one search for each of these seeds, which no
run has compiled programs for, with JAX's tracing, lowering and compile
time in it.  ``--trace-fixture`` also records one traced window and writes
a trimmed copy of its trace, in ``trace_reduce``'s form, to PATH.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def trimmed(trace: dict, span: tuple[int, int], keep_ns: int) -> dict:
    """The events ``trace_reduce`` reads in the first ``keep_ns`` of
    ``span``, with the reduction they give under ``expected``."""
    from bench import trace_reduce

    lo, hi = span[0], min(span[1], span[0] + keep_ns)
    planes = []
    for p in trace["planes"]:
        device = p["name"].startswith("/device:")
        lines = []
        for line in p["lines"]:
            if device and line["name"] not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
                continue
            ev = [e for e in line["events"] if e[1] < hi and e[1] + e[2] > lo
                  and (device or e[2] >= 10_000 or e[0].startswith("bench."))]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    small = {"planes": planes, "window": [lo, hi]}
    small["expected"] = trace_reduce.reduce(small, (lo, hi))
    return small


def _other_row(acc: np.ndarray) -> np.ndarray:
    return np.roll(acc, 1) if len(acc) > 1 else 1.0 - acc


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax.numpy as jnp

    from bench import check, harness, trace_reduce, window

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--fresh", default="")
    ap.add_argument("--trace-fixture")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    harness.enable_compile_cache()
    log = harness.CompileLog()
    t = time.perf_counter()
    driver = window.load_driver(cell.root, cell.traffic["driver"])(cell)
    driver.prepare()
    print(json.dumps({"prepare_s": time.perf_counter() - t}), flush=True)
    block = cell.traffic["reference_rows"]
    variants = {}
    if args.control:
        variants["control"] = {"dtype": jnp.bfloat16}
    if args.faults:
        variants["frozen"] = {"rows_fn": lambda r: r[:5] + (r[5] * 0,) + r[6:]}
        variants["half_batch"] = {"batch_share": 0.5}
    for seed in (int(s) for s in args.seeds.split(",")):
        driver.draw(seed)
        win = driver.window(args.seconds, None)
        picks = check.samples(cell, win, seed)
        memo = {}
        line = {"seed": seed, "window_s": win["window_s"], "rows": win["rows"],
                "sampled": {k: len(v) for k, v in picks.items()},
                "program": check.compare(cell.config, win["groups"], picks, block, memo=memo)}
        if win["fronts"]:
            line["program"].update(check.front_numbers(cell.config, win["groups"], win["fronts"]))
        for name, planted in variants.items():
            line[name] = check.compare(cell.config, win["groups"], picks, block, memo=memo,
                                       **planted)
        if args.faults:
            line["altered"] = check.compare(
                cell.config, win["groups"], picks, block, memo=memo,
                answers=lambda g: np.concatenate([_other_row(a) for a in g.acc]))
        print(json.dumps(line), flush=True)
    for seed in (int(s) for s in args.fresh.split(",") if s):
        from repro.core import codesign

        t0 = time.perf_counter()
        codesign.run_codesign(driver.codesign_config(seed))
        t1 = time.perf_counter()
        print(json.dumps({"fresh_seed": seed, "search_s": t1 - t0, **log.between(t0, t1)}),
              flush=True)
    if args.trace_fixture:
        with tempfile.TemporaryDirectory() as tdir:
            driver.window(args.seconds, window.Profiler(tdir))
            files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
            tr = trace_reduce.load_xplane(files[0])
        span = trace_reduce.window_of(tr, "bench.traced")
        red = trace_reduce.reduce(tr, span)
        print(json.dumps({"span": span, "reduced": red})[:4000])
        small = trimmed(tr, span, 300_000_000)
        Path(args.trace_fixture).parent.mkdir(parents=True, exist_ok=True)
        Path(args.trace_fixture).write_text(json.dumps(small))
        print(json.dumps({"fixture": args.trace_fixture, "reduced": small["expected"]})[:4000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
