"""The benchmark's own tests run on the CPU, with the harness and the
program on the path:

    python -m pytest bench/tests
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
