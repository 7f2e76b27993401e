"""Waves of fresh genomes (``wave``) with JAX's matmul precision set from
the configuration.

The configuration states the precision of the program's dots
(``trainer.matmul_precision``); the program sets none, so this driver
sets ``jax_default_matmul_precision`` to it before set-up and puts the
previous value back in ``release``, before the reference runs.
"""

from __future__ import annotations

from pathlib import Path

import jax

from bench import window

Wave = window.load_driver(Path(__file__).resolve().parents[2], "wave")


class Driver(Wave):
    def prepare(self) -> None:
        self.prev_precision = jax.config.jax_default_matmul_precision
        jax.config.update("jax_default_matmul_precision",
                          self.cfg["trainer"]["matmul_precision"])
        super().prepare()

    def release(self) -> None:
        super().release()
        jax.config.update("jax_default_matmul_precision", self.prev_precision)
