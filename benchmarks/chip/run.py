"""Chip benchmark of the event-driven CSNN server: one cell, one run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout, in one process, on the chips of the
machine it is started on.  It exits non-zero, and prints no result, where
JAX finds no TPU, fewer chips than the cell asks for, or a device kind
missing from ``peaks.py``; it never falls back to the CPU.

A run makes its weights and traffic from ``--seed``, warms every shape the
cell uses (set-up, ``setup_s``: from process start to the first timed
request), measures for ``--seconds``, and then checks what the timed path
produced against the configuration's plain reference (``check.py``).
With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it traces a 2-second window in the middle of the measured
one and reports the cell's per-layer metrics, each read by
``metrics/<name>.py`` from that trace and the counters of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each compared number beside its
limit; the same numbers are the last lines of standard error.
JAX's persistent compilation cache is kept at ``.jax_cache`` in the
checkout, so only a checkout's first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import numpy as np  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
TRACE_SECONDS = 2.0
# paper, Table III: input sparsity of the three conv layers, trained net
PAPER_SPARSITY = "93/98/98"


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device kind without published peaks."""


@dataclass
class Ctx:
    """What a driver may use of the run."""

    cell: object
    seed: int
    devices: list
    reference: object
    t_start: float
    setup_s: float = math.nan

    def seed_for(self, what: str) -> int:
        from benchmarks.chip.cell import derive_seed
        return derive_seed(self.seed, what)

    def key_for(self, what: str):
        from benchmarks.chip.cell import prng_key
        return prng_key(self.seed, what)

    def mark_setup_done(self) -> None:
        """End of set-up.  Everything set-up made (JAX's traces and
        executables, the traffic pool) is collected once and frozen out
        of the collector's reach, so that a full collection inside the
        window scans only what the window makes; without this, such a
        collection stalled the serving loop for 0.1-1.4 s."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t_start

    @staticmethod
    def trace_span(seconds: float) -> tuple[float, float]:
        """The traced part of the window: 2 s in its middle."""
        start = max(0.0, (seconds - TRACE_SECONDS) / 2)
        return start, min(float(seconds), start + TRACE_SECONDS)

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_tpu: bool = True,
        t_start: float = T_START) -> dict:
    """One run of ``workload``; returns the result object."""
    from benchmarks.chip import check, inputs
    from benchmarks.chip.cell import load_cell, load_module
    from benchmarks.chip.peaks import peaks

    cell = load_cell(root, workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peak = None
    if require_tpu:
        if platform != "tpu":
            raise NoChip(f"no TPU: JAX reports platform {platform!r}")
        try:
            peak = peaks(kind)["bf16_flops"]
        except ValueError as e:
            raise NoChip(str(e)) from None
    if len(devices) < cell.chips:
        raise NoChip(f"{workload} needs {cell.chips} chips, JAX reports "
                     f"{len(devices)}")
    used = devices[:cell.chips]
    Ctx.log(f"{workload}: {platform} {kind!r} x{len(devices)}, using "
            f"{cell.chips}; seed {seed}; {seconds} s; trace {int(trace)}")
    reference = load_module(
        cell.bench_dir / "reference" / f"{cell.cfg['reference']}.py")
    driver = load_module(
        cell.bench_dir / "drivers" / f"{cell.mix['driver']}.py")
    ctx = Ctx(cell=cell, seed=seed, devices=used, reference=reference,
              t_start=t_start)

    state = driver.prepare(ctx)
    tracer = None
    if trace:
        from benchmarks.chip.trace import Tracer
        tracer = Tracer()
    m = driver.measure(state, ctx, seconds, tracer)
    stats = [d.memory_stats() or {} for d in used]
    mem = max(s.get("peak_bytes_in_use", 0) for s in stats)
    params = state.params
    driver.release(state)
    del state
    gc.collect()

    t = time.perf_counter()
    spikes = inputs.spikes(m["kind"], m["distinct"], cell.cfg, reference)
    ref_logits, active = reference.reference_logits(params, spikes, cell.cfg)
    which = m["answer_input"]
    errors = check.answer_errors(m["answers"], ref_logits[which])
    robust = check.threshold_robust(reference, params, spikes, cell.cfg,
                                    ref_logits)
    nums = check.numbers(errors, robust[which], m["unanswered"])
    correct, table = check.judge(nums, cell.own["check"]["limits"])
    tight = check.threshold_robust(reference, params, spikes, cell.cfg,
                                   ref_logits, check.MARGIN / 10)
    flips = errors > check.FLIP
    Ctx.log(f"check: {len(errors)} answers against the reference on "
            f"{spikes.shape[0]} distinct inputs in "
            f"{time.perf_counter() - t:.2f} s; largest error "
            f"{float(errors.max())!r}; share above {check.FLIP}: "
            f"{float(flips.mean())!r}; inputs that move with the "
            f"threshold: {float(1 - robust.mean())!r} at "
            f"{check.MARGIN}, {float(1 - tight.mean())!r} at "
            f"{check.MARGIN / 10}, where the unexplained share reads "
            f"{float(np.mean(flips & tight[which]))!r}; conv layer input "
            f"sparsity {'/'.join(f'{100 * (1 - a):.2f}' for a in active)} "
            f"% (paper, Table III: {PAPER_SPARSITY})")

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(mem)}
    result = {"correct": bool(correct), "attempted": int(m["attempted"]),
              "failed": int(m["unanswered"])}
    if not trace:
        values = dict(m["e2e"], setup_s=ctx.setup_s)
        metrics = {mt["name"]: {"value": values[mt["name"]],
                                "unit": mt["unit"]}
                   for mt in cell.end_to_end}
    else:
        from benchmarks.chip.flops import sample_flops
        red = tracer.reduce(devices=[d.id for d in used])
        busy = sum(red["busy_s"].values()) / len(red["busy_s"])
        rec = {"window_s": red["window_s"], "busy_s": red["busy_s"],
               "busy_mean_s": busy,
               "counters": m["counters"], "chips": cell.chips,
               "flops_per_sample": sample_flops(
                   cell.cfg, inputs.CHANNELS[m["kind"]]),
               "peak_flops": peak}
        Ctx.log(f"trace: window {red['window_s']!r} s, busy {red['busy_s']}"
                f", counters {m['counters']}")
        metrics = {}
        for mt in cell.per_layer:
            reader = load_module(cell.bench_dir / "metrics"
                                 / f"{mt['name']}.py")
            value = reader.read(rec)
            if value is not None:
                metrics[mt["name"]] = {"value": value, "unit": mt["unit"]}
        device.update(busy_s=busy, window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    result["check"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.chip import check
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for k, v in result["metrics"].items():
        Ctx.log(f"{k}: {v['value']!r} {v['unit']}")
    check.report(result["check"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
