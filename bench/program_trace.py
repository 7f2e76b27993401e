"""Reductions of a profiler trace by the program's own spans and named scopes.

Where the program records its own spans (``repro.core.spans``), they are
host events of the trace, on the device's clock: ``idle_by_span`` gives
each idle gap of the device to the innermost of them, and
``scope_seconds`` puts the device time of a program's operations on the
``jax.named_scope`` in their ``op_name`` metadata.  A v5e trace's ``XLA
Ops`` events carry no op_name (their stats are offset, duration and time
scale), so that metadata is read from the compiled program's text
(``op_names_from_hlo``, ``wave_program_text``), by operation name, one
program at a time: two programs may name different operations alike
(``%fusion.305``).

A traced run of the harness records the program's spans and counters and
puts ``idle_by_span`` under ``idle_spans`` of its trace reduction, and,
for a driver that names its program's operations (``op_names``),
``scope_seconds`` under ``scope_s``; the per-layer metrics read them there.
"""

from __future__ import annotations

import bisect
import collections
import re

from bench.trace_reduce import (MODULES_LINE, OPS_LINE, SHORT_GAP_NS, _line, _union,
                                device_planes, host_events)

NO_SPAN = "host: no span"
NO_SCOPE = "other"


def idle_by_span(trace: dict, window: tuple[int, int], names) -> list | None:
    """Idle seconds of the device in gaps of 50 us or more, by the innermost
    host event named in ``names`` (the program's spans) over each gap's
    midpoint, ``NO_SPAN`` where none is; summed over devices, largest
    first.  None when the trace holds no device operations."""
    lo, hi = window
    devs = device_planes(trace)
    if not devs or hi <= lo:
        return None
    names = set(names)
    hosts = [(s, s + d, n) for n, s, d in host_events(trace) if d > 0 and n in names]
    idle: collections.Counter = collections.Counter()
    for plane in devs:
        busy = _union(((s, s + d) for _, s, d in _line(plane, OPS_LINE)), lo, hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 >= SHORT_GAP_NS:
                t = (g0 + g1) // 2
                inner = min(((e - s, n) for s, e, n in hosts if s <= t < e), default=None)
                idle[inner[1] if inner else NO_SPAN] += (g1 - g0) / 1e9
    return [[n, s] for n, s in idle.most_common()]


def _self_ns(events: list) -> list[int]:
    """Each event's duration less that of the events nested in it (an
    ``XLA Ops`` line nests a loop's body operations in the loop)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [e - s for s, e in events]
    stack: list[int] = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def _scope(op_name: str, scopes: set) -> str:
    """The innermost named scope of an op_name: its path's last component,
    the operation's own name aside, whose innermost name (``adc`` of
    ``transpose(jvp(adc))``) is in ``scopes``."""
    path = op_name.split(";")[0].split("/")[:-1]
    for part in reversed(path):
        name = re.sub(r"^(?:[^()]*\()*([^()]*)\)*$", r"\1", part)
        if name in scopes:
            return name
    return NO_SCOPE


def op_names_from_hlo(text: str) -> dict[str, str]:
    """Each operation of a compiled program's text (``compiled.as_text()``)
    with the op_name of its metadata, by the name its trace events carry
    (``%fusion.246``)."""
    return dict(re.findall(r'^\s*(?:ROOT )?(%\S+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
                           text, re.M))


def wave_program_text(driver) -> str:
    """The compiled text of the program an ``eval_wave`` driver's waves ran
    (``bench/drivers/wave.py``, after ``draw``): each operation's name with
    its op_name metadata."""
    rows = driver.pool[0]
    return driver.ev.program.lower(*(driver.ev.shard_fn(a) for a in rows)).compile().as_text()


def _module_at(modules: list, t: int) -> str:
    """The name of the last program (``(start, name)``, sorted) to start at
    or before ``t``: the one an operation starting at ``t`` runs in."""
    i = bisect.bisect_right(modules, (t, "\uffff")) - 1
    return modules[i][1] if i >= 0 else ""


def scope_seconds(trace: dict, window: tuple[int, int], scopes, program: str,
                  op_names: dict) -> dict | None:
    """Device seconds of the operations of the programs whose name holds
    ``program``, by the named scope in their op_name (``op_names``, one
    compiled program's, see ``op_names_from_hlo``): each operation's own
    time, nested operations taken out, clipped to ``window`` and summed
    over devices; ``NO_SCOPE`` for operations in none of ``scopes``.  None
    without op_names or device operations."""
    devs = device_planes(trace)
    lo, hi = window
    if not op_names or not devs or hi <= lo:
        return None
    scopes = set(scopes)
    out: collections.Counter = collections.Counter()
    for plane in devs:
        modules = sorted((s, n) for n, s, _ in _line(plane, MODULES_LINE))
        evs = [(n, s, max(s, lo), min(s + d, hi)) for n, s, d in _line(plane, OPS_LINE)
               if s < hi and s + d > lo]
        for (name, start, _, _), ns in zip(evs, _self_ns([(a, b) for *_, a, b in evs])):
            if program in _module_at(modules, start):
                out[_scope(op_names.get(name, ""), scopes)] += ns / 1e9
    return dict(out)


def span_self_ms(log: dict | None, name: str) -> float | None:
    """Mean self time, in milliseconds, of the spans named ``name`` in a
    recording (``spans.Log.as_dict()``); None without a recording or such
    a span."""
    own = [s["self"] for s in (log or {}).get("spans", ())
           if s["name"] == name and s["self"] is not None]
    return 1e3 * sum(own) / len(own) if own else None


def useful_share(log: dict | None) -> float | None:
    """Useful row-steps over the row-steps the QAT scan computed, in percent,
    from a recording's counters; None without a recording or a scanned
    row-step."""
    c = (log or {}).get("counters", {})
    scanned = c.get("trainer.scanned_row_steps", 0)
    return 100.0 * c["trainer.useful_row_steps"] / scanned if scanned else None
