"""A checkout of the benchmark at a size the CPU holds, for the tests.

``tiny_root(tmp)`` copies ``benchmarks/chip`` under ``tmp`` and adds a
12x12 configuration of the paper's layer pattern and one cell per
driver, written as a later change would write them: data files only.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = {
    "name": "csnn-tiny", "source": "test size of the paper's layer pattern",
    "reference": "csnn", "input_hw": [12, 12],
    "layers": [{"conv": 8, "kernel": 3}, {"conv": 8, "kernel": 3, "pool": 3},
               {"conv": 4, "kernel": 3}, {"fc": 10}],
    "t_steps": 4, "v_t": 1.0, "relu_clamp": 1.0,
    "precision": "float32", "matmul_precision": "highest",
    "conversion": {"percentile": 99.9, "calibration_inputs": 32},
    "plan": {"capacity": "lossless", "channel_block": 8}, "reduced": [],
}


def limits(cell: str) -> dict:
    """The correctness limits of a benchmark cell, as its file states."""
    path = REPO / "benchmarks" / "chip" / "workloads" / f"{cell}.json"
    return json.loads(path.read_text())["check"]["limits"]


# each tiny cell is held to the limits of the cell whose path it drives
CELLS = {
    "tiny-stream": ({"driver": "open_loop_stream", "input": "events",
                     "params": {"band": 2, "noise_rate": 0.01}, "pool": 64,
                     "rate_rps": 40},
                    {"slots": 8, "check": {
                        "limits": limits("paper-stream-steady")}}),
    "tiny-offline": ({"driver": "offline_batch", "input": "images",
                      "params": {}, "batch": 16, "batches": 2},
                     {"check": {"limits": limits("paper-offline")}}),
}


def tiny_root(tmp: Path, cells=tuple(CELLS)) -> Path:
    """A checkout under ``tmp`` with the benchmark and ``cells`` added."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(REPO / "benchmarks" / "chip", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "configs" / "csnn-tiny.json").write_text(json.dumps(TINY))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "csnn-tiny", "source": TINY["source"],
                            "file": "benchmarks/chip/configs/csnn-tiny.json",
                            "reduced": [], "why": "tests"})
    for name in cells:
        mix, own = CELLS[name]
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        (bench / "workloads" / f"{name}.json").write_text(json.dumps(own))
        spec["workloads"].append({"name": name, "config": "csnn-tiny",
                                  "traffic": name, "chips": 1,
                                  "why": "tests"})
        stream = mix["driver"] == "open_loop_stream"
        for m in spec["end_to_end"] + spec["per_layer"]:
            serve = m["name"].startswith("latency") or m["name"].endswith(
                ".serve")
            offline = m["name"] == "samples_per_s" or m["name"].endswith(
                ".offline")
            if "workloads" in m and (serve if stream else offline):
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
