"""Milliseconds per evaluator call of its input path (padding and placement
of the rows on the mesh): the self time of the program's ``trainer.input``
spans over their number, in the searches of the window."""

from bench import program_trace


def read(rec):
    if not rec.get("searches"):
        return None
    return program_trace.span_self_ms(rec.get("spans"), "trainer.input")
