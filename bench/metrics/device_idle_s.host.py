"""Seconds the device sat idle, in gaps of 50 us or more, while the
innermost program span was one of the host GA's (variation, memo plan,
selection, genome decode, area pass), over the traced search, averaged
over the cell's devices (profiler trace and the program's spans)."""

HOST_GA = ("nsga2.variation", "nsga2.plan", "nsga2.select", "codesign.decode", "codesign.area")


def read(rec):
    red = rec.get("trace")
    if not rec.get("searches") or not red or red.get("idle_spans") is None:
        return None
    idle = dict(red["idle_spans"])
    return sum(idle.get(n, 0.0) for n in HOST_GA) / len(red["devices"])
