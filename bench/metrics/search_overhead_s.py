"""Seconds a search spends outside its generations: data, evaluator build,
generation 0, the conventional baseline and the result (host clock)."""


def read(rec):
    s = rec.get("searches")
    if not s:
        return None
    gens = sum(g["gen_s"] for r in s for g in r["history"])
    return (rec["window_s"] - gens) / len(s)
