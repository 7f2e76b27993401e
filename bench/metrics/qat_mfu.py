"""Useful QAT model FLOPs per second in the window, counted from the
genomes (``work.row_work``), over the chips' bf16 peak."""


def read(rec):
    if not rec.get("waves") or not rec.get("peaks"):
        return None
    rate = rec["work"]["flops"] / rec["window_s"]
    return 100.0 * rate / (rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
