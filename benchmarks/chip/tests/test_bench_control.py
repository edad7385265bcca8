"""The control fails each cell's check: the plain reference computed one
precision step below the configuration's (``high``, three bf16 passes,
for float32 at ``highest``), put in the program's place, on inputs of
the cell's traffic at the configuration's full widths.

On the chip the same comparison runs at each cell's own size through
``benchmarks/chip/control.py``; here it runs on 128 inputs, which a CPU
test holds.
"""
from __future__ import annotations

import pytest

from bench_fixture import REPO

CELLS = ["paper-stream-steady", "paper-offline"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    from benchmarks.chip import check, inputs
    from benchmarks.chip.cell import load_cell, load_module, prng_key

    c = load_cell(REPO, cell)
    ref = load_module(c.bench_dir / "reference" / f"{c.cfg['reference']}.py")
    kind = c.mix["input"]
    payload = inputs.make(c.mix, c.cfg, 128, 7)
    params = ref.make_params(prng_key(7, "weights"),
                             inputs.ann_input(kind, payload, c.cfg), c.cfg,
                             inputs.CHANNELS[kind])
    spikes = inputs.spikes(kind, payload, c.cfg, ref)
    good, _ = ref.reference_logits(params, spikes, c.cfg, block=128)
    ctrl, _ = ref.reference_logits(params, spikes, c.cfg, block=128,
                                   precision="high")
    robust = check.threshold_robust(ref, params, spikes, c.cfg, good)
    nums = check.numbers(check.answer_errors(ctrl, good), robust, 0)
    correct, table = check.judge(nums, c.own["check"]["limits"])
    assert not correct, table
