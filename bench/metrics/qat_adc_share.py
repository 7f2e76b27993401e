"""Share of the population QAT program's device time spent in the ADC
stage: the own time of the operations under the named scope ``adc`` over
the device time of the program's events, traced waves, summed over
devices (profiler trace and the compiled program's op_names)."""

from bench import trace_reduce


def read(rec):
    red = rec.get("trace")
    if not rec.get("waves") or not red or not red.get("scope_s"):
        return None
    dev_s = trace_reduce.program_seconds(red["modules_s"], rec["program"])
    if dev_s <= 0:
        return None
    return 100.0 * red["scope_s"].get("adc", 0.0) / dev_s
