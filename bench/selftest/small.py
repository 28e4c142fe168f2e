"""The benchmark's cells cut to a size the CPU runs in seconds."""

import json

from bench import harness

SEED = 2**31 + 4099          # more than 32 signed bits hold

# Cells whose files are here but which are not yet in BENCHMARK.json (not
# proven on the chip); rehearsed with the others.
CANDIDATES = [
    {"name": "cnn_live_sparse", "config": "fpca_cnn", "traffic": "live_sparse",
     "chips": 1},
    {"name": "detect_vga_backlog", "config": "fpca_detect_vga",
     "traffic": "segments_sparse", "chips": 1},
    {"name": "cnn_fleet4_backlog_skewed", "config": "fpca_cnn",
     "traffic": "fleet4_backlog_skewed", "chips": 4},
]


def _entries():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    names = {w["name"] for w in bench["workloads"]}
    return bench["workloads"] + [c for c in CANDIDATES if c["name"] not in names]


def _entry(name: str) -> dict:
    return next(w for w in _entries() if w["name"] == name)


def workloads():
    return [w["name"] for w in _entries()]


def traffic(name: str) -> dict:
    """The cell's traffic file as it stands."""
    wl = _entry(name)
    return json.loads((harness.BENCH / "traffic" / f"{wl['traffic']}.json").read_text())


def entry(name: str) -> str:
    """The serving entry the cell's traffic drives: ``tick`` or ``segments``."""
    return traffic(name)["entry"]


def small_cell(name: str, **over):
    wl = _entry(name)
    seg = traffic(name)["entry"] == "segments"
    over = {"spec": {"image_h": 40, "image_w": 80 if seg else 40},
            "traffic": {"cameras": 2 if seg else 8, "segment_length": 4 if seg else 0,
                        "check": {"cameras": 2 if seg else 4, "ticks": 3},
                        **over}}
    cell = harness.load_cell(name, over, entry=wl)
    cell.traffic["scene"] = dict(cell.traffic["scene"], radius=3.0, clips=2,
                                 clip_frames=12)
    return cell
