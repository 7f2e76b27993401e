"""Program spans and counters: where a search and its evaluator spend time.

    with spans.span("nsga2.generation", gen=3) as s:
        ...
    s.dur                                    # seconds, always measured
    spans.count("trainer.rows", 24)

    with spans.recording() as log:           # off by default
        codesign.run_codesign(cfg)
    log.spans, log.counters, log.self_times(), log.dump("spans.jsonl")

Every span enters ``jax.profiler.TraceAnnotation(name)``, so under a
running profiler it is a host event on the same clock as the device's
operations, and a trace reduction can say which program phase the device
sat idle in.  With no profiler running that costs well under a
microsecond.  A span always reads the clock at its ends (``NSGA2.history``
reports ``gen_s`` and ``eval_s`` from its spans); only while
:func:`recording` is on does it also keep its name, parent, thread and
attributes in memory, and only then does :func:`count` add anything.

Parents are kept per thread, so a span opened on a service's worker
thread never nests under one of its caller's.  Every name is declared
once, below; a trace reduction reads these tuples to know which host
events are the program's.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

import jax

__all__ = ["SPANS", "COUNTERS", "SCOPES", "Span", "Log", "span", "count", "scope",
           "recording", "is_recording"]

# span names: the layer boundaries of a search and of an evaluator call
SPANS = (
    "codesign.search",     # one run_codesign, root (seed, dataset)
    "codesign.setup",      # data, split, evaluator and cost-function construction
    "nsga2.setup",         # generation 0: draw and evaluate the seed pool
    "nsga2.generation",    # one generation, step_begin to step_commit (gen)
    "nsga2.variation",     # tournament, crossover, mutation, refinement
    "nsga2.evaluate",      # the pool's evaluation, what eval_s reports
    "nsga2.plan",          # memo dedupe walk and surrogate screen
    "nsga2.select",        # non-dominated sort, crowding, survivors, telemetry
    "codesign.decode",     # genome decode and per-genome training seeds
    "codesign.area",       # host area pass of a batch
    "trainer.call",        # one evaluator call (rows, bucket)
    "trainer.input",       # padding and placement of the rows on the mesh
    "trainer.dispatch",    # the jitted program's call: trace and lower on a miss
    "codesign.wait",       # blocked on the device's answer
    "codesign.baseline",   # conventional-ADC baseline replicates
    "codesign.result",     # front decode, area, result assembly
)

COUNTERS = (
    "trainer.rows",               # real rows sent to an evaluator
    "trainer.padded_rows",        # bucket padding rows computed and dropped
    "trainer.program_builds",     # traces of an evaluator's jitted program
    "trainer.program_reuses",     # evaluator builds that found their program built
    "trainer.useful_row_steps",   # sum over real rows of steps x batch trained
    "trainer.scanned_row_steps",  # rows x max_steps x max_batch the scan computes
)

# jax.named_scope names inside the QAT program (op_name metadata); "act"
# and "wprec" only in programs whose genome has those axes
SCOPES = ("adc", "layer", "gather", "loss", "sgd", "test", "act", "wprec")

_SPAN_SET = frozenset(SPANS)
_SCOPE_SET = frozenset(SCOPES)
_log: "Log | None" = None
_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """One timed interval; a context manager, or entered and left by hand
    where a phase opens in one method and closes in another."""

    __slots__ = ("name", "attrs", "start", "end", "parent", "thread", "_ann", "_log")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.start = self.end = 0.0
        self.parent = self.thread = self._log = None

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        log = _log
        if log is not None:
            st = _stack()
            self.parent = st[-1] if st else None
            self.thread = threading.get_ident()
            self._log = log
            st.append(self)
            log._add(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self._log is not None:
            st = _stack()
            # by identity: the stacked island driver closes its islands'
            # generations in another order than it opened them
            for i in range(len(st) - 1, -1, -1):
                if st[i] is self:
                    del st[i]
                    break
        self._ann.__exit__(None, None, None)

    @property
    def dur(self) -> float:
        """Seconds from enter to exit."""
        return self.end - self.start


def span(name: str, **attrs) -> Span:
    """A span named ``name`` (one of :data:`SPANS`); enter it with ``with``."""
    if name not in _SPAN_SET:
        raise ValueError(f"undeclared span {name!r}; declare it in spans.SPANS")
    return Span(name, attrs)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` (one of :data:`COUNTERS`) while recording."""
    log = _log
    if log is None:
        return
    log._count(name, n)


def scope(name: str):
    """``jax.named_scope(name)`` for a name declared in :data:`SCOPES`."""
    if name not in _SCOPE_SET:
        raise ValueError(f"undeclared scope {name!r}; declare it in spans.SCOPES")
    return jax.named_scope(name)


def is_recording() -> bool:
    """Whether spans and counters are being kept (work done only to feed a
    counter can be skipped otherwise)."""
    return _log is not None


class Log:
    """The spans and counters of one :func:`recording`."""

    def __init__(self):
        self._spans: list[Span] = []
        self.counters: dict[str, int] = dict.fromkeys(COUNTERS, 0)
        self._lock = threading.Lock()

    def _add(self, s: Span) -> None:
        with self._lock:
            self._spans.append(s)

    def _count(self, name: str, n: int) -> None:
        if name not in self.counters:
            raise ValueError(f"undeclared counter {name!r}; declare it in spans.COUNTERS")
        with self._lock:
            self.counters[name] += int(n)

    @property
    def spans(self) -> list[dict]:
        """Every span entered while recording, in the order entered:
        ``{"name", "start", "end", "dur", "parent", "thread", "attrs"}``,
        times in seconds of ``time.perf_counter``, ``parent`` the index of
        the enclosing span on the same thread or None, ``end`` None while
        the span is open."""
        with self._lock:
            spans = list(self._spans)
        index = {id(s): i for i, s in enumerate(spans)}
        return [{"name": s.name, "start": s.start, "end": s.end or None,
                 "dur": s.dur if s.end else None,
                 "parent": index.get(id(s.parent)) if s.parent is not None else None,
                 "thread": s.thread, "attrs": s.attrs} for s in spans]

    def self_times(self, spans: list[dict] | None = None) -> list[float | None]:
        """Each span's duration less the part of it its children cover."""
        spans = self.spans if spans is None else spans
        kids: dict[int, list[tuple[float, float]]] = {}
        for s in spans:
            if s["parent"] is not None and s["end"] is not None:
                kids.setdefault(s["parent"], []).append((s["start"], s["end"]))
        out = []
        for i, s in enumerate(spans):
            if s["end"] is None:
                out.append(None)
                continue
            covered, reach = 0.0, s["start"]
            for a, b in sorted(kids.get(i, ())):
                a, b = max(a, reach), min(b, s["end"])
                if b > a:
                    covered += b - a
                    reach = b
            out.append(s["dur"] - covered)
        return out

    def as_dict(self) -> dict:
        """Plain data: ``{"spans": [... with "self"], "counters"}``."""
        spans = self.spans
        for s, st in zip(spans, self.self_times(spans)):
            s["self"] = st
        with self._lock:
            counters = dict(self.counters)
        return {"spans": spans, "counters": counters}

    def dump(self, path) -> None:
        """One JSON line per span, then one line of counters."""
        d = self.as_dict()
        with open(path, "w") as f:
            for s in d["spans"]:
                f.write(json.dumps(s, default=str) + "\n")
            f.write(json.dumps({"counters": d["counters"]}) + "\n")


@contextlib.contextmanager
def recording():
    """Keep every span and counter until the block ends; yields the
    :class:`Log`.  Recordings nest: the inner one sees only its block."""
    global _log
    prev, log = _log, Log()
    _log = log
    try:
        yield log
    finally:
        _log = prev
