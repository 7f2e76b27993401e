import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import os
import tempfile

# the harness keeps JAX's compile cache where this says; a test run keeps
# its CPU programs out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", tempfile.mkdtemp(prefix="bench-tests-jax-"))
