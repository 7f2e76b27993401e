"""Seconds per search: the window over the searches it completed (host
clock)."""


def read(rec):
    s = rec.get("searches")
    if not s:
        return None
    return rec["window_s"] / len(s)
