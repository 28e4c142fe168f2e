"""Benchmark harness — one module per paper table/figure + system reports.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig8 eq1   # substring filter
"""

from __future__ import annotations

import os
import sys
import traceback
from pathlib import Path

import jax

from benchmarks.common import emit

MODULES = [
    "benchmarks.fig7_transfer",
    "benchmarks.fig8_curvefit",
    "benchmarks.fig9_tradeoffs",
    "benchmarks.eq1_cycles",
    "benchmarks.kernel_bench",
    "benchmarks.stream_bench",
    "benchmarks.model_bench",
    "benchmarks.fleet_bench",
    "benchmarks.roofline_report",
]


def main() -> None:
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; without it the cache sits
    # at one fixed path in the checkout, so later runs find it again
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parent.parent
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    failures = 0
    print("name,us_per_call,derived")
    for modname in MODULES:
        if filters and not any(f in modname for f in filters):
            continue
        try:
            mod = __import__(modname, fromlist=["run"])
            emit(mod.run())
        except Exception:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"{modname},0.0,ERROR", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
