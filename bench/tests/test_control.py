"""The control comes out as not correct: the float32 reference, computed in
bfloat16 throughout, in the program's place, at a size a CPU test holds.

On the chip the same control is run at the cell's own size by
``bench/calibrate.py --control`` (readings in PERF.md)."""

import json

import jax
import jax.numpy as jnp

from bench import check, harness, window
from tiny import tree


def test_bfloat16_control_fails_the_limit(tmp_path):
    root = tree(tmp_path)
    p = root / "bench/traffic/eval_wave.json"
    traffic = json.loads(p.read_text())
    traffic.update(wave_rows=256, reference_rows=48)
    p.write_text(json.dumps(traffic))
    cell = harness.load_cell("seeds.wave1024", root)
    driver = window.load_driver(root, "wave")(cell)
    driver.prepare()
    driver.draw(2**31 + 11)
    win = driver.window(0.0, None)
    picks = check.samples(cell, win, 2**31 + 11)
    assert len(picks["lowlr"]) == 48 and len(picks["all"]) == 48
    prog = check.compare(cell, win["groups"], picks, 48)
    ctl = check.compare(cell, win["groups"], picks, 48, dtype=jnp.bfloat16)
    # on the CPU the program's dots are true float32: it reads the reference
    assert jax.default_backend() == "cpu"
    assert all(prog[k] == 0 for k in prog)
    assert any(ctl[k] > limit for k, limit in cell.limits.items())
