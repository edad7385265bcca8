"""The control of a cell's check, on the chip, at the cell's own size.

    python benchmarks/chip/control.py --workload paper-offline \
        --seeds 11 12 13

Per seed: the cell's inputs and weights as a run with that seed makes
them (an offline cell's distinct batches; a serving cell's whole pool,
of which a run's window sends most traces), then the plain reference
at the configuration's precision and the control, the same reference one
step below (``precision="high"``), in the program's place.  Prints the
numbers the check compares, per seed, as JSON lines: the control has to
read above the cell's limits.  Not part of a cell's run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import check, inputs
    from benchmarks.chip.cell import derive_seed, load_cell, load_module
    from benchmarks.chip.cell import prng_key

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    cell = load_cell(ROOT, args.workload)
    cfg, mix = cell.cfg, cell.mix
    ref = load_module(cell.bench_dir / "reference" / f"{cfg['reference']}.py")
    kind = mix["input"]
    n_cal = cfg["conversion"]["calibration_inputs"]
    for seed in args.seeds:
        t = time.perf_counter()
        if mix["driver"] == "open_loop_stream":
            pool = inputs.make(mix, cfg, mix["pool"],
                               derive_seed(seed, "pool"))
            calib = pool[:n_cal]
            payload = pool
        else:
            b = mix["batch"]
            payload = inputs.make(mix, cfg, mix["batches"] * b,
                                  derive_seed(seed, "inputs"))
            calib = payload[:n_cal]
        params = ref.make_params(prng_key(seed, "weights"),
                                 inputs.ann_input(kind, calib, cfg), cfg,
                                 inputs.CHANNELS[kind])
        spikes = inputs.spikes(kind, payload, cfg, ref)
        good, _ = ref.reference_logits(params, spikes, cfg)
        ctrl, _ = ref.reference_logits(params, spikes, cfg, precision="high")
        errors = check.answer_errors(ctrl, good)
        nums = check.numbers(errors, check.threshold_robust(
            ref, params, spikes, cfg, good), 0)
        correct, _ = check.judge(nums, cell.own["check"]["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "answers": len(errors),
                          "control_logit_err_p90": nums["logit_err_p90"],
                          "control_unexplained_share":
                              nums["unexplained_share"],
                          "control_logit_err_max": float(errors.max()),
                          "control_correct": correct,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
