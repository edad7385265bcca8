"""The harness finds a cell by its name alone, and refuses the CPU.

A cell added as a later change adds it (a ``BENCHMARK.json`` entry, a
configuration, a traffic mix and a workload file: data only) is found
and runs end to end, its check reading ``correct: true``; nothing else
is edited.  Every cell of the committed ``BENCHMARK.json`` resolves to
its files, metric readers and driver.
"""
from __future__ import annotations

import json
import time

import pytest

import bench_fixture
from bench_fixture import REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny-stream", "tiny-offline"])
def test_dropped_in_cell_runs(root, cell):
    from benchmarks.chip import run
    r = run.run(cell, 2**32 + 5, 1, False, root=root, require_tpu=False,
                t_start=time.perf_counter())
    assert r["correct"] is True, r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "check"


def test_committed_cells_resolve():
    from benchmarks.chip.cell import load_cell, load_module
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = load_cell(REPO, w["name"])
        load_module(cell.bench_dir / "drivers" / f"{cell.mix['driver']}.py")
        load_module(cell.bench_dir / "reference"
                    / f"{cell.cfg['reference']}.py")
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
        assert set(cell.own["check"]["limits"]) == {
            "logit_err_p90", "unexplained_share", "unanswered"}


def test_no_tpu_is_refused(root):
    from benchmarks.chip import run
    with pytest.raises(run.NoChip):
        run.run("tiny-offline", 1, 1, False, root=root)


def test_unknown_device_kind_is_refused():
    from benchmarks.chip.peaks import peaks
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError):
        peaks("cpu")
