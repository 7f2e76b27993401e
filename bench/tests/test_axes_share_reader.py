"""The ``qat_axes_share`` reader: the own device time of the three-axis
genome's scopes over the traced program's time, and nothing where the
program has neither scope."""

import pytest

from bench import window
from tiny import ROOT


def _wave(scope_s):
    """A traced wave record, as ``test_readers`` makes one, with ``scope_s``."""
    return {"waves": 3, "program": "_evaluate_padded",
            "trace": {"devices": [{}],
                      "modules_s": {"jit__evaluate_padded(1)": 2.0, "jit_slice(2)": 0.5},
                      "scope_s": scope_s}}


def _read(name, rec):
    return window.load_file(ROOT, "metrics", name).read(rec)


def test_reader_reads_the_axes_share():
    rec = _wave({"adc": 0.25, "gather": 1.5, "act": 0.1, "wprec": 0.15, "other": 0.1})
    assert _read("qat_axes_share", rec) == pytest.approx(12.5, rel=1e-12)
    assert _read("qat_adc_share", rec) == pytest.approx(12.5, rel=1e-12)


@pytest.mark.parametrize("path", [("trace", "scope_s"), ("trace",), ("waves",)])
def test_reader_reads_nothing_without_its_record(path):
    rec = _wave({"act": 0.1, "wprec": 0.15})
    d = rec
    for k in path[:-1]:
        d = d[k]
    del d[path[-1]]
    assert _read("qat_axes_share", rec) is None


def test_a_program_without_the_three_axis_scopes_reads_no_axes_share():
    # the ADC-only genome's waves, or a program that declares no such scope
    rec = _wave({"adc": 0.25, "gather": 1.5, "other": 0.1})
    assert _read("qat_axes_share", rec) is None
    assert _read("qat_adc_share", rec) == pytest.approx(12.5, rel=1e-12)
    rec["trace"]["scope_s"]["wprec"] = 0.5
    assert _read("qat_axes_share", rec) == pytest.approx(25.0, rel=1e-12)
