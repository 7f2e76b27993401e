"""Genomes the population QAT program trained in the window, per second of
the window (host clock)."""


def read(rec):
    if not rec.get("waves"):
        return None
    return rec["rows"] / rec["window_s"]
