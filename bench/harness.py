"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names its configuration (``configs/<config>.json``) and traffic
mix (``traffic/<traffic>.json``), which names its driver
(``drivers/<driver>.py``); each metric, end-to-end or per-layer, is read by
``metrics/<metric>.py`` and its limits are in ``limits/<cell>.json``.  A
run loads, warms up every program the window can call, measures for
``--seconds`` (``--trace 1``: with a profiler trace of the first search or
waves, and reports the per-layer metrics instead of the end-to-end ones),
checks the window's answers against the reference (``check``), and prints
one JSON line last.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic, limits and metrics, each found by name under ``root``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((root / "bench" / "limits" / f"{name}.json").read_text())["limits"],
        end_to_end=e2e, per_layer=per_layer, root=root,
    )


def device_info(chips: int) -> dict:
    """The devices JAX sees; exits when they are not TPUs or too few."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"device: {info}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is on {info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {info['count']}")
    return info


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table or kind.startswith("_"):
        raise ValueError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache`` (the program's own default); every program is
    kept, however fast it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileLog:
    """JAX's tracing, lowering and compile spans and cache events, on the
    host clock (each span ends when JAX reports it)."""

    SPANS = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.spans: list[tuple[float, float]] = []
        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, name, secs, **kw):
        if name in self.SPANS:
            t = time.perf_counter()
            self.spans.append((t - secs, t))

    def _event(self, name, **kw):
        self.events.append((time.perf_counter(), name))

    def between(self, t0: float, t1: float) -> dict:
        """Wall seconds covered by JAX's spans in [t0, t1], and its cache
        hits and misses (a miss is a compile)."""
        from bench.trace_reduce import _union

        ns = [(int(s * 1e9), int(e * 1e9)) for s, e in self.spans]
        covered = sum(e - s for s, e in _union(ns, int(t0 * 1e9), int(t1 * 1e9))) / 1e9
        ev = [n for t, n in self.events if t0 <= t <= t1]
        return {"jit_s": covered,
                "cache_hits": ev.count("/jax/compilation_cache/cache_hits"),
                "cache_misses": ev.count("/jax/compilation_cache/cache_misses")}


def metric_reader(root: Path, name: str):
    """The module of ``metrics/<name>.py``: ``read(rec)`` gives the number
    or None when the run has nothing to read; a roofline reader also gives
    ``bound(rec)``, what bounds the least time."""
    if not (root / "bench" / "metrics" / f"{name}.py").is_file():
        raise ValueError(f"no reader for metric {name!r} in bench/metrics")
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(cell: Cell, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float) -> dict:
    """Set-up, window, check; returns the result line's object."""
    import jax

    from bench import check, trace_reduce, window

    enable_compile_cache()
    log = CompileLog()
    peak = peaks(device["kind"]) if device["platform"] == "tpu" else None
    driver = window.load_driver(cell.root, cell.traffic["driver"])(cell)
    driver.prepare()
    driver.draw(seed)
    setup_s = time.perf_counter() - t_start
    with tempfile.TemporaryDirectory() as tdir:
        prof = window.Profiler(tdir) if trace else None
        t0 = time.perf_counter()
        win = driver.window(seconds, prof)
        t1 = time.perf_counter()
        stats = [d.memory_stats() or {} for d in jax.devices()]
        mem = max(s.get("peak_bytes_in_use", 0) for s in stats)
        driver.release()
        gc.collect()
        red = None
        if trace:
            files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
            tr = trace_reduce.load_xplane(files[0])
            span = trace_reduce.window_of(tr, "bench.traced")
            red = trace_reduce.reduce(tr, span) if span else None
            del tr
    rec = {**win, "setup_s": setup_s, "jit": log.between(t0, t1), "trace": red,
           "peaks": peak, "chips": cell.chips}
    nums = check.numbers(cell, win, seed)
    lims = cell.limits
    correct = all(nums[k] <= lims[k] for k in lims)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = metric_reader(cell.root, m["name"])
        v = reader.read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            if hasattr(reader, "bound"):
                metrics[m["name"]]["bound"] = reader.bound(rec)
    device = {**device, "memory_peak_bytes": int(mem)}
    out = {"correct": correct, "attempted": win["rows"], "failed": 0,
           "metrics": metrics, "device": device}
    if trace and red:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    out["window"] = {"seconds": win["window_s"], "rows": win["rows"],
                     "searches": len(win.get("searches", [])), "waves": win.get("waves"),
                     **rec["jit"]}
    # the numbers compared come last in the line
    out["checks"] = {k: {"value": nums[k], "limit": lims[k]} for k in lims}
    return out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    device = device_info(cell.chips)
    out = run(cell, args.seed, args.seconds, bool(args.trace), device, t_start)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
