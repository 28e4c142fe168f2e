#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python3 bench/control.py --workload cnn_backlog_busy --seconds 15 \
        --program-seeds 1,2,3 --control-seeds 11,12,13 --ticks 16 \
        --fault stale --fault-seeds 21,22,23

* ``--program-seeds``: the cell run as the benchmark runs it (set-up, a
  window of ``--seconds``, the check), once per seed: the lower readings.
* ``--control-seeds``: the control, the reference in the program's place
  computed in bfloat16, one precision below the float32 the configuration
  states.  For each seed it draws the cell's fleet, weights and sampled
  cameras as a run with that seed does, takes ``--ticks`` ticks per camera
  (about what a run serves in set-up and window together) and a sample of
  the last half of them.
* ``--fault`` with ``--fault-seeds``: the cell run with a fault of
  ``bench/faults.py`` planted under the timed path.

Each reading is one JSON line with the compared numbers beside the limits.
The benchmark's own runs do not run this.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_readings(cell, seed: int, ticks: int) -> dict:
    import numpy as np

    from bench import harness, reference, scenes

    s, tr = cell.cfg["spec"], cell.traffic
    fleet = scenes.Fleet(s["image_h"], s["image_w"], tr["cameras"], tr["scene"], seed)
    weights = harness.make_weights(cell, seed)
    rec = harness.Recorder(fleet, seed, tr["check"], cell.cfg)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    pick = rng.choice(np.arange(ticks // 2, ticks - 1), rec.k, replace=False)
    sample = sorted(int(t) for t in pick) + [ticks - 1]
    ref = reference.Reference(cell.cfg, cell.head, fleet, weights)
    readings = reference.Readings()
    for cam in rec.cams.values():
        ref.control(cam, sample, ticks, readings)
    return readings.numbers()


def _seeds(text):
    return [int(x) for x in text.split(",")] if text else []


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--ticks", type=int, default=16)
    ap.add_argument("--fault", default="stale")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("control.py: no TPU here", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import faults, harness

    cell = harness.load_cell(args.workload)

    def run(seed):
        out = {}
        res = harness.run_cell(cell, seed, args.seconds, False, window=out)
        return dict(out["numbers"], correct=res["correct"])

    def emit(kind, seed, numbers):
        line = {"workload": args.workload, "kind": kind, "seed": seed, "numbers": numbers,
                "limits": cell.limits}
        print(json.dumps(line), flush=True)

    for seed in _seeds(args.program_seeds):
        emit("program", seed, run(seed))
    for seed in _seeds(args.control_seeds):
        emit("control", seed, control_readings(cell, seed, args.ticks))
    for seed in _seeds(args.fault_seeds):
        with faults.plant(args.fault, cell.traffic["entry"]):
            numbers = run(seed)
        emit(f"fault:{args.fault}", seed, numbers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
