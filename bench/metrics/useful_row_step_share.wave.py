"""Share of the row-steps the QAT scan computed that train a real row within
its step budget and batch: the program's counters
``trainer.useful_row_steps`` over ``trainer.scanned_row_steps``, in the
waves of the window."""

from bench import program_trace


def read(rec):
    if not rec.get("waves"):
        return None
    return program_trace.useful_share(rec.get("spans"))
