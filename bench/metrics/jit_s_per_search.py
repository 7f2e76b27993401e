"""Seconds per search that JAX spent tracing, lowering, compiling or
fetching compiled programs from the persistent cache in the window
(``jax.monitoring`` spans, their union on the host clock)."""


def read(rec):
    s = rec.get("searches")
    if not s:
        return None
    return rec["jit"]["jit_s"] / len(s)
