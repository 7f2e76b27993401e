"""From a profiler trace to the numbers the per-layer metrics read.

A trace is held as plain data, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, dur_ns], ...]}]}]}``, so the reduction can be
checked on a small recorded trace (``fixtures/``) without a chip.
``load_xplane`` turns the profiler's ``.xplane.pb`` into that form.

Device planes are named ``/device:<kind>:<n>``.  On each, the events of
the ``XLA Ops`` line are the operations that ran, and those of the ``XLA
Modules`` line the compiled programs.  Host planes (``/host:...``) carry
the harness's own annotations (``bench.*``) and JAX's dispatch, lowering
and compile events, which is what the idle gaps are attributed to.
"""

from __future__ import annotations

import collections
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# gaps shorter than this lie between the operations of one program; they
# are summed under one label instead of being attributed to the host
SHORT_GAP_NS = 50_000
SHORT_GAP = "device: gaps under 50 us"


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [[_short(e.name), int(e.start_ns), int(e.duration_ns)]
                           for e in line.events],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _short(name: str) -> str:
    """An HLO operation's name without its shapes and operands."""
    return name.split(" = ")[0]


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged, clipped intervals."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith("/device:") and not p["name"].startswith("/device:CPU")
            and _line(p, OPS_LINE)]


def host_events(trace: dict) -> list:
    return [ev for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]]


def window_of(trace: dict, annotation: str) -> tuple[int, int] | None:
    """[start, end) of the first host event named ``annotation``."""
    for name, s, d in host_events(trace):
        if name == annotation:
            return s, s + d
    return None


def _label(name: str) -> str:
    """Host event name without arguments or numeric suffixes."""
    return re.sub(r"\.\d+$", "", name)


def reduce(trace: dict, window: tuple[int, int], top: int = 10) -> dict:
    """Busy union, idle share, program time and idle gaps over ``window``.

    Returns ``{"window_s", "devices": [{"name", "busy_s"}], "busy_s" (mean
    over devices), "idle_share" (1 - busy / window, mean over devices),
    "modules_s" {program name: device seconds summed over devices},
    "device_ops" [[op, seconds]] (top, summed over devices), "idle_gaps"
    [[host activity, seconds]] (top, idle time summed over devices)}``, or
    ``None`` when the trace holds no device operations.
    """
    lo, hi = window
    devs = device_planes(trace)
    if not devs or hi <= lo:
        return None
    hosts = [(s, s + d, n) for n, s, d in host_events(trace) if d > 0]
    per_dev, modules, ops = [], collections.Counter(), collections.Counter()
    gaps: collections.Counter = collections.Counter()
    for plane in devs:
        busy = _union(((s, s + d) for _, s, d in _line(plane, OPS_LINE)), lo, hi)
        per_dev.append({"name": plane["name"], "busy_s": sum(e - s for s, e in busy) / 1e9})
        for name, s, d in _line(plane, OPS_LINE):
            if s < hi and s + d > lo:
                ops[name] += (min(s + d, hi) - max(s, lo)) / 1e9
        for name, s, d in _line(plane, MODULES_LINE):
            if s < hi and s + d > lo:
                modules[name] += (min(s + d, hi) - max(s, lo)) / 1e9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 >= SHORT_GAP_NS:
                gaps[attribute((g0 + g1) // 2, hosts)] += (g1 - g0) / 1e9
            elif g1 > g0:
                gaps[SHORT_GAP] += (g1 - g0) / 1e9
    window_s = (hi - lo) / 1e9
    busy_s = sum(d["busy_s"] for d in per_dev) / len(per_dev)
    return {
        "window_s": window_s,
        "devices": per_dev,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "modules_s": dict(modules),
        "device_ops": [[n, s] for n, s in ops.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gaps.most_common(top)],
    }


def attribute(t: int, hosts: list) -> str:
    """What the host was doing at ``t``: the shortest host event spanning
    it that is not the harness's outer window annotation."""
    best = None
    for s, e, name in hosts:
        if s <= t < e and name != "bench.traced" and (best is None or e - s < best[0]):
            best = (e - s, name)
    return _label(best[1]) if best else "host: no event"


def program_seconds(modules_s: dict, program: str) -> float:
    """Device seconds of the compiled programs whose name holds ``program``."""
    return sum(s for name, s in modules_s.items() if program in name)
