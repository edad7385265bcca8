"""Sweeps that fix a cell's load, on the chip, in one process.

    python benchmarks/chip/sweep.py --workload paper-stream-steady \
        --rates 300 500 700 --seconds 8 --seed 5
    python benchmarks/chip/sweep.py --workload paper-offline \
        --batches 256 512 1024 --seconds 4 --repeat 3 --seed 5

Serving: one set-up, then one window per offered rate.  Per rate it
prints the latency median and 95th percentile, the 95th percentile of the
first and of the second half of the window's requests (a backlog that
grows shows as the second above the first), and the share of requests
answered before the window closed.  The knee is the highest rate at which
the engine keeps up; the cell offers about four fifths of it.

Offline: per batch size one set-up and ``--repeat`` windows, each
printing ``samples_per_s``.

Not part of a cell's run: its numbers fix the rate and the batch written
into the traffic mix.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=())
    ap.add_argument("--batches", type=int, nargs="*", default=())
    ap.add_argument("--repeat", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.chip.cell import load_cell, load_module
    from benchmarks.chip.run import Ctx

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 2
    cell = load_cell(ROOT, args.workload)
    reference = load_module(cell.bench_dir / "reference"
                            / f"{cell.cfg['reference']}.py")
    driver = load_module(cell.bench_dir / "drivers"
                         / f"{cell.mix['driver']}.py")

    def ctx():
        return Ctx(cell=cell, seed=args.seed, devices=devices[:cell.chips],
                   reference=reference, t_start=time.perf_counter())

    if args.rates:
        state = driver.prepare(ctx())
        for rate in args.rates:
            m = driver.measure(state, ctx(), args.seconds, rate=rate)
            lat, due = m["latency_ms"], m["due_s"]
            half = due < args.seconds / 2
            in_window = np.mean(lat / 1e3 + due <= args.seconds)
            print(json.dumps({
                "rate_rps": rate, "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p95_first_half_ms": float(np.percentile(lat[half], 95)),
                "p95_second_half_ms": float(np.percentile(lat[~half], 95)),
                "answered_in_window": float(in_window),
                "unanswered": m["unanswered"]}), flush=True)
    for b in args.batches:
        cell.mix = dict(cell.mix, batch=b)
        state = driver.prepare(ctx())
        for _ in range(args.repeat):
            m = driver.measure(state, ctx(), args.seconds)
            print(json.dumps({"batch": b,
                              "samples_per_s": m["e2e"]["samples_per_s"]}),
                  flush=True)
        driver.release(state)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
