"""Traces of an evaluator's jitted program per search in the window: the
program's counter ``trainer.program_builds`` over the searches."""


def read(rec):
    if not rec.get("searches") or not rec.get("spans"):
        return None
    return rec["spans"]["counters"]["trainer.program_builds"] / len(rec["searches"])
