#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same cell at several camera counts.

    python3 bench/sweep.py --workload cnn_live_sparse --config fpca_cnn \
        --traffic live_sparse --cameras 4,8,16,32 --seed 7 --seconds 10

A cell not yet in ``BENCHMARK.json`` is named by ``--config`` and
``--traffic``.  For each count it runs the cell once in this process and
prints one JSON line with the tick latency (p50, p95) and how late the load
generator ran in the second half of the window.  A count is sustained when
that lateness stays under one tick period: the backlog does not grow.  The
cell's camera count is then set to four fifths of the highest sustained
count, once, by hand.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--cameras", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("sweep.py: no TPU here", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness

    for n in (int(x) for x in args.cameras.split(",")):
        entry = ({"name": args.workload, "config": args.config, "traffic": args.traffic,
                  "chips": 1} if args.config else None)
        cell = harness.load_cell(args.workload, {"traffic": {"cameras": n}}, entry=entry)
        captured = {}
        res = harness.run_cell(cell, args.seed, args.seconds, False,
                               t_start=time.perf_counter(), window=captured)
        late = np.asarray(captured["late"][len(captured["late"]) // 2:]) * 1e3
        lat = captured["latency"]
        print(json.dumps({
            "cameras": n, "correct": res["correct"],
            "latency_p50_ms": 1e3 * harness._quantile(lat, 50),
            "latency_p95_ms": 1e3 * harness._quantile(lat, 95),
            "late_median_ms": float(np.median(late)), "late_max_ms": float(late.max()),
            "ticks": captured["ticks"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
