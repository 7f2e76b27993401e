"""Seconds from process start to the start of the window: TPU start, data,
evaluator build and the warm-up of every program the window can call
(host clock)."""


def read(rec):
    return rec["setup_s"]
