"""A run with the timed path broken underneath reads ``correct: false``.

Each test skips the harness's look for a chip (``require_tpu=False``),
drives the rest of a run of a test-size cell on the CPU, and plants one
fault (``benchmarks/chip/faults.py``) in the program the cell's window
drives:

* a step that returns its state unchanged;
* half of the batch left out (its rows answered with the other half's,
  or, served, never answered);
* an answer altered where it is produced: every answer moved by 1e-5,
  ten times and more the percentile's limit; or the answers of one slot
  of the serving table, or of one row of each offline batch, moved by
  1e-3, which only the share of unexplained answers sees.

The same cells unbroken read ``correct: true`` (test_bench_discovery).
"""
from __future__ import annotations

import time

import pytest

import bench_fixture

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.tiny_root(tmp_path_factory.mktemp("bench"))


def run_cell(root, cell):
    from benchmarks.chip import run
    return run.run(cell, SEED, 1, False, root=root, require_tpu=False,
                   t_start=time.perf_counter())


def run_planted(root, cell, fault, slot=0):
    from benchmarks.chip.faults import planted
    with planted(fault, slot):
        return run_cell(root, cell)


def over(r, number):
    row = r["check"][number]
    return r["correct"] is False and row["value"] > row["limit"]


@pytest.mark.parametrize("cell", ["tiny-stream", "tiny-offline"])
def test_state_unchanged(root, cell):
    assert over(run_planted(root, cell, "state_unchanged"), "logit_err_p90")


@pytest.mark.parametrize("cell", ["tiny-stream", "tiny-offline"])
def test_answer_altered(root, cell):
    assert over(run_planted(root, cell, "answer_altered"), "logit_err_p90")


@pytest.mark.parametrize("cell,fault", [("tiny-stream", "one_slot"),
                                        ("tiny-offline", "one_row")])
def test_one_answer_source_altered(root, cell, fault):
    assert over(run_planted(root, cell, fault), "unexplained_share")


def test_half_batch_left_out_offline(root, monkeypatch):
    import jax.numpy as jnp

    import repro.core.csnn as csnn
    real = csnn.snn_apply_batched

    def half(params, spikes, *a, **kw):
        h = spikes.shape[0] // 2
        out = real(params, spikes[:h], *a, **kw)
        return jnp.concatenate([out, out])
    monkeypatch.setattr(csnn, "snn_apply_batched", half)
    assert over(run_cell(root, "tiny-offline"), "logit_err_p90")


def test_half_batch_left_out_stream(root, monkeypatch):
    import asyncio

    import repro.serve.csnn_engine as engine
    real = engine.CSNNEngine.submit_nowait
    count = {"n": 0}

    warm = 2 * bench_fixture.CELLS["tiny-stream"][1]["slots"]

    def half(self, image):
        count["n"] += 1
        if count["n"] <= warm or count["n"] % 2:
            return real(self, image)
        return asyncio.get_running_loop().create_future()  # never answered
    monkeypatch.setattr(engine.CSNNEngine, "submit_nowait", half)
    from benchmarks.chip.cell import load_module
    driver = load_module(root / "benchmarks/chip/drivers/open_loop_stream.py")
    monkeypatch.setattr(driver, "ANSWER_WAIT_S", 1.0)
    assert over(run_cell(root, "tiny-stream"), "unanswered")
