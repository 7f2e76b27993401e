import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / "trace_wave.json"


def _trace():
    """Two devices over a 100 ns window; host: the window, a compile inside it."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.traced", 0, 100_000_000],
            ["PjitFunction(_evaluate_padded)", 10_000_000, 80_000_000],
            ["backend_compile_and_load", 20_000_000, 30_000_000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit__evaluate_padded(1)", 60_000_000, 30_000_000],
                                               ["jit_slice", 95_000_000, 1_000_000]]},
            {"name": "XLA Ops", "events": [["fusion.1", 60_000_000, 20_000_000],
                                           ["fusion.2", 70_000_000, 20_000_000],
                                           ["slice", 95_000_000, 1_000_000],
                                           ["tiny", 96_010_000, 1_000_000],
                                           ["late", 99_990_000, 20_000]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Modules", "events": [["jit__evaluate_padded(1)", 0, 50_000_000]]},
            {"name": "XLA Ops", "events": [["fusion.1", 0, 50_000_000]]}]},
    ]}


def test_busy_union_idle_share_program_time_and_gaps():
    t = _trace()
    span = tr.window_of(t, "bench.traced")
    assert span == (0, 100_000_000)
    red = tr.reduce(t, span)
    # device 0: [60, 90), [95, 96), [96.01, 97.01), [99.99, 100) -> 32.01 ms
    busy0, busy1 = 0.03201, 0.05
    assert [d["busy_s"] for d in red["devices"]] == pytest.approx([busy0, busy1])
    assert red["busy_s"] == pytest.approx((busy0 + busy1) / 2)
    assert red["idle_share"] == pytest.approx(1 - (busy0 + busy1) / 2 / 0.1)
    assert tr.program_seconds(red["modules_s"], "_evaluate_padded") == pytest.approx(0.08)
    gaps = dict(red["idle_gaps"])
    # device 0 [0, 60): midpoint 30 ms in the compile; [90, 95) and
    # [97.01, 99.99) after the dispatch returned; [96, 96.01) is short.
    # Device 1 [50, 100): midpoint 75 ms in the dispatch.
    assert gaps["backend_compile_and_load"] == pytest.approx(0.06)
    assert gaps["host: no event"] == pytest.approx(0.005 + 0.00298)
    assert gaps[tr.SHORT_GAP] == pytest.approx(0.00001)
    assert gaps["PjitFunction(_evaluate_padded)"] == pytest.approx(0.05)
    assert sum(gaps.values()) == pytest.approx(0.2 - busy0 - busy1)
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(0.07)


def test_clipped_to_the_window_and_empty_without_devices():
    t = _trace()
    red = tr.reduce(t, (60_000_000, 80_000_000))
    assert red["devices"][0]["busy_s"] == pytest.approx(0.02)
    assert red["devices"][1]["busy_s"] == 0.0
    host_only = {"planes": [t["planes"][0]]}
    assert tr.reduce(host_only, (0, 100_000_000)) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    """A trimmed trace of traced waves recorded on one v5e chip."""
    t = json.loads(FIXTURE.read_text())
    red = tr.reduce(t, tuple(t["window"]))
    assert len(red["devices"]) == 1
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["idle_share"] == pytest.approx(1 - red["busy_s"] / red["window_s"])
    prog = tr.program_seconds(red["modules_s"], "_evaluate_padded")
    assert 0.0 < prog <= red["window_s"]
    assert sum(s for _, s in red["idle_gaps"]) <= red["window_s"] - red["busy_s"] + 1e-9
    assert red == json.loads(json.dumps(t["expected"]))
