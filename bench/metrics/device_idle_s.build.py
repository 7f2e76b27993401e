"""Seconds the device sat idle, in gaps of 50 us or more, while the
innermost program span was ``trainer.dispatch`` (the jitted program's
call: tracing and lowering on a miss), over the traced search, averaged
over the cell's devices (profiler trace and the program's spans)."""


def read(rec):
    red = rec.get("trace")
    if not rec.get("searches") or not red or red.get("idle_spans") is None:
        return None
    idle = dict(red["idle_spans"])
    return idle.get("trainer.dispatch", 0.0) / len(red["devices"])
