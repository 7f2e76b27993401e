"""Share of the traced search in which no operation ran on the device,
averaged over the cell's devices (profiler trace)."""


def read(rec):
    if not rec.get("searches") or not rec.get("trace"):
        return None
    return 100.0 * rec["trace"]["idle_share"]
