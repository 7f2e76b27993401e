"""Host GA milliseconds per generation: the generation's wall time less its
evaluation, from each search's history (aggregated over islands)."""


def read(rec):
    hist = [g for r in rec.get("searches") or [] for g in r["history"]]
    if not hist:
        return None
    return 1e3 * sum(g["gen_s"] - g["eval_s"] for g in hist) / len(hist)
