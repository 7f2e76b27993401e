"""Share of the population QAT program's device time spent in the
three-axis genome's own stages: the own time of the operations under the
named scopes ``act`` (the activation circuits) and ``wprec`` (the
per-layer weight lowering) over the device time of the program's events,
traced waves, summed over devices (profiler trace and the compiled
program's op_names).  Nothing where neither scope holds an operation: a
program without the scopes, or a genome without those axes."""

from bench import trace_reduce

SCOPES = ("act", "wprec")


def read(rec):
    red = rec.get("trace")
    if not rec.get("waves") or not red or not red.get("scope_s"):
        return None
    if not set(SCOPES) & set(red["scope_s"]):
        return None
    dev_s = trace_reduce.program_seconds(red["modules_s"], rec["program"])
    if dev_s <= 0:
        return None
    return 100.0 * sum(red["scope_s"].get(s, 0.0) for s in SCOPES) / dev_s
