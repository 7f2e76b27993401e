"""The population QAT program's share of its roofline: the least time the
traced waves' useful work could take (``work.least_time``, the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s) over the device time of
the program's events in the trace, summed over devices."""

from bench import trace_reduce, work


def _parts(rec):
    red, peaks = rec.get("trace"), rec.get("peaks")
    if not rec.get("waves") or not red or not peaks:
        return None
    dev_s = trace_reduce.program_seconds(red["modules_s"], rec["program"])
    if dev_s <= 0:
        return None
    tw = rec["traced_work"]
    return work.least_time(tw["flops"], tw["bytes"], peaks), dev_s


def read(rec):
    parts = _parts(rec)
    if parts is None:
        return None
    (least, _), dev_s = parts
    return 100.0 * least / dev_s


def bound(rec):
    parts = _parts(rec)
    return parts[0][1] if parts else None
