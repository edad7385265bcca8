"""Faults planted in the program under a cell's timed path.

    python benchmarks/chip/faults.py --workload paper-stream-steady \
        --fault one_slot --seeds 11 12 13 --seconds 20

The tests (``tests/test_bench_faults.py``) plant each fault in a
test-size cell on the CPU and see ``correct`` come out false.  On the
chip this script runs a cell at its own size with one fault planted, a
full run per seed in one process, and prints per seed the numbers the
check compares: the fault's readings, beside the sound runs', set the
limits.  Not part of a cell's run.

* ``none``: nothing planted: the sound program's readings;
* ``state_unchanged``: the chunk step returns its state unchanged;
* ``answer_altered``: every answer moved by 1e-5 where the head makes it;
* ``one_slot``: the answers of one slot of the serving table (``--slot``)
  moved by 1e-3 where the head makes them;
* ``one_row``: the answer of one row of each offline batch (``--slot``)
  moved by 1e-3.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SMALL, LARGE = 1e-5, 1e-3


def _same_state(params, state, spikes, cfg, plan, **kw):
    return (state, []) if kw.get("collect_stats") else state


def _readout_moved(real, delta, row=None):
    def readout(*a, **kw):
        logits = real(*a, **kw)
        if row is None:
            return logits + delta
        return logits.at[row].add(delta)
    return readout


def _apply_moved(real, row):
    def apply(*a, **kw):
        return real(*a, **kw).at[row].add(LARGE)
    return apply


def patches(fault: str, slot: int = 0) -> list:
    """[(module, attribute, replacement)] that plant ``fault``."""
    import repro.core.csnn as csnn
    import repro.serve.csnn_engine as engine
    if fault == "none":
        return []
    if fault == "state_unchanged":
        return [(csnn, "snn_step_chunk", _same_state),
                (engine, "snn_step_chunk", _same_state)]
    if fault == "answer_altered":
        return [(m, "snn_readout", _readout_moved(m.snn_readout, SMALL))
                for m in (csnn, engine)]
    if fault == "one_slot":
        return [(engine, "snn_readout",
                 _readout_moved(engine.snn_readout, LARGE, slot))]
    if fault == "one_row":
        return [(csnn, "snn_apply_batched",
                 _apply_moved(csnn.snn_apply_batched, slot))]
    raise ValueError(f"unknown fault {fault!r}")


@contextlib.contextmanager
def planted(fault: str, slot: int = 0):
    """The program with ``fault`` planted, restored on exit."""
    todo = patches(fault, slot)
    saved = [(m, a, getattr(m, a)) for m, a, _ in todo]
    try:
        for m, a, new in todo:
            setattr(m, a, new)
        yield
    finally:
        for m, a, old in saved:
            setattr(m, a, old)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--slot", type=int, default=0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    args = ap.parse_args(argv)
    from benchmarks.chip import run
    for seed in args.seeds:
        with planted(args.fault, args.slot):
            r = run.run(args.workload, seed, args.seconds, False,
                        t_start=time.perf_counter())
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "slot": args.slot, "seed": seed,
                          "correct": r["correct"],
                          "attempted": r["attempted"],
                          "check": {k: v["value"]
                                    for k, v in r["check"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
