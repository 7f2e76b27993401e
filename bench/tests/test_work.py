import json

import numpy as np
import pytest

from bench import work
from tiny import ROOT


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"printed-mlp-{name}.json").read_text())


@pytest.mark.parametrize("name, n_train, n_test, share", [
    ("cardio", 1488, 638, 0.469), ("seeds", 147, 63, 0.234)])
def test_useful_row_step_share(name, n_train, n_test, share):
    cfg = _cfg(name)
    assert work.split_sizes(cfg["dataset"]) == (n_train, n_test)
    assert work.useful_share(cfg, n_train) == pytest.approx(share, abs=5e-4)


def test_one_genome_by_hand():
    # seeds, batch 64, 120 epochs: ceil(147 / 64) = 3 steps an epoch, 360 steps
    cfg = _cfg("seeds")
    flops, nbytes = work.row_work([64], [120], cfg, 147, 63)
    w = 7 * 3 + 3 * 3
    assert flops[0] == 360 * 64 * 6 * w + 63 * 2 * w
    assert nbytes[0] == 360 * 64 * 7 * 4 + 63 * 7 * 4
    # cardio, batch 16, 60 epochs: 93 steps an epoch, capped at 600
    flops, nbytes = work.row_work([16], [60], _cfg("cardio"), 1488, 638)
    assert flops[0] == 600 * 16 * 6 * 120 + 638 * 2 * 120
    assert nbytes[0] == 600 * 16 * 21 * 4 + 638 * 21 * 4


def test_least_time_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peaks) == (10.0, "flops")
    assert work.least_time(100.0, 50.0, peaks) == (5.0, "bytes")
    assert np.isclose(work.least_time(0.0, 0.0, peaks)[0], 0.0)
