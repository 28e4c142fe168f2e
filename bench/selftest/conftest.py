"""The benchmark's own tests: ``python -m pytest bench/selftest`` from the
root of the repository, on the CPU (``JAX_PLATFORMS=cpu``).  Four virtual
CPU devices stand in for the four-chip cell."""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
