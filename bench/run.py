#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets it up (compiles included),
serves its traffic for ``--seconds`` seconds and checks a sample of what it
served against the plain reference.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, then ``checks``); the numbers compared
are also the last lines of standard error.  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
from a profiled window.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.  The persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` where that is set, otherwise ``.jax_cache`` at
the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if wl is None:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax

    if jax.default_backend() != "tpu":
        print(f"run.py: no TPU here (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < int(wl["chips"]):
        print(f"run.py: {args.workload} needs {wl['chips']} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
