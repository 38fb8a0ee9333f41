import os
import sys
from pathlib import Path

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke
# tests and benches must see 1 device.
SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
# tests/ itself, for the hypothesis fallback shim (_propshim)
TESTS = Path(__file__).resolve().parent
if str(TESTS) not in sys.path:
    sys.path.insert(0, str(TESTS))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Continuous invariant validation: every LSMTree.drain_jobs() in the test
# suite runs the mechanism + policy invariant sweep (LSMConfig reads this
# env at construction; benchmarks leave it unset => off).
os.environ.setdefault("REPRO_PARANOID_CHECKS", "1")
