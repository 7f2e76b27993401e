"""Entry point of the benchmark: one run of one cell (see ``harness``)."""

import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness

    sys.exit(harness.main(t_start=T_START))
