"""Where a cell's time goes, by the program's own spans and scopes.

    python benchmarks/chip/phases.py --workload <cell> --seed <n> \
        --seconds <s> [--whole]

Runs the cell's driver as ``run.py --trace 1`` does (same set-up, same
2-second traced window in the middle, or the whole window with
``--whole``), but reads the trace with ``spans.py`` as well as with
``trace.py``.  It prints to standard error the table of engine phases
(host time on the loop thread, device idle under it) and device scopes
(device time per unit), and as the last line of standard output one JSON
object:

* ``e2e``: the driver's end-to-end numbers for the whole window (with
  the profiler on over the traced part) and ``setup_s``;
* ``readings``: what the span readings give in this cell:
  ``host_idle_share`` (% of the window with the device idle and the loop
  thread in engine host work: submit, admit, encode, pack, dispatch,
  backlog, readout), ``round_host_ms`` (the loop thread's time in that
  host work per chunk), ``idle_under_span_share`` (% of device idle time
  under some engine span), ``admit_wait_p95_ms`` (p95 of the window's
  admission-wait histogram), ``conv_unit_device_ms`` and
  ``compact_device_ms`` (device time under those scopes per offline
  call) and ``scoped_busy_share`` (% of device busy time under a named
  scope), each where the cell has what it reads;
* ``spans``: ``spans.read``'s result, and ``counters``: the driver's.

It checks nothing against the reference: ``run.py`` does that.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def readings(red: dict, counters: dict, waits) -> dict:
    """The span readings of one traced window (see the module docstring);
    ``waits``: the window's admission-wait histogram, or None."""
    from benchmarks.chip import spans
    from repro.serve.csnn_engine import wait_quantile_ms

    out, phases, scopes = {}, red["phases"], red["scopes"]
    if phases and red["rounds"]:
        host = [p for n, p in phases.items() if n in spans.HOST_PHASES]
        idle = red["window_s"] - red["busy_s"]
        out["host_idle_share"] = (100 * sum(p["idle_s"] for p in host)
                                  / red["window_s"])
        out["round_host_ms"] = (1e3 * sum(p["host_s"] for p in host)
                                / red["rounds"])
        if idle > 0:
            out["idle_under_span_share"] = 100 * (
                1 - phases.get(spans.NO_SPAN, {}).get("idle_s", 0) / idle)
    if waits is not None and sum(waits):
        out["admit_wait_p95_ms"] = wait_quantile_ms(waits, 0.95)
    if scopes and counters.get("calls"):
        for unit in ("conv_unit", "compact"):
            out[f"{unit}_device_ms"] = (1e3 * scopes.get(unit, 0.0)
                                        / counters["calls"])
    if scopes and red["busy_s"]:
        out["scoped_busy_share"] = (100 * scopes.get(spans.SCOPED, 0.0)
                                    / red["busy_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--whole", action="store_true",
                    help="trace the whole window, not 2 s in its middle")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import spans, trace
    from benchmarks.chip.cell import load_cell, load_module
    from benchmarks.chip.run import Ctx

    cell = load_cell(ROOT, args.workload)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"phases.py: no TPU: JAX reports platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    reference = load_module(
        cell.bench_dir / "reference" / f"{cell.cfg['reference']}.py")
    driver = load_module(
        cell.bench_dir / "drivers" / f"{cell.mix['driver']}.py")
    ctx = Ctx(cell=cell, seed=args.seed, devices=used, reference=reference,
              t_start=T_START)
    if args.whole:
        ctx.trace_span = lambda seconds: (0.0, float(seconds))
    # the serve driver keeps its window's engine.stats snapshots in a
    # dict it hands to each tick; keep a reference to read them after
    seen = {}
    tick = getattr(driver, "_trace_tick", None)
    if tick is not None:
        def kept_tick(tracer, engine, window, *rest):
            seen["window"] = window
            return tick(tracer, engine, window, *rest)
        driver._trace_tick = kept_tick

    state = driver.prepare(ctx)
    tracer = trace.Tracer()
    m = driver.measure(state, ctx, args.seconds, tracer)
    Ctx.log(f"e2e {m['e2e']}, setup_s {ctx.setup_s!r}")
    ids = [d.id for d in used]
    t = time.perf_counter()
    red = spans.read(trace.find_xplane(tracer.dir), ids)
    read_s = time.perf_counter() - t
    base = tracer.reduce(devices=ids)
    waits = None
    window = seen.get("window", {})
    if "admit_wait_hist" in window.get("stop", {}):
        waits = [b - a for a, b in zip(window["start"]["admit_wait_hist"],
                                       window["stop"]["admit_wait_hist"])]
    Ctx.log(f"spans.read {read_s:.2f} s; trace.reduce busy "
            f"{base['busy_s']}, window {base['window_s']!r} s; counters "
            f"{m['counters']}")
    Ctx.log(spans.table(red))
    out = {"workload": args.workload, "seed": args.seed,
           "whole": args.whole,
           "e2e": dict(m["e2e"], setup_s=ctx.setup_s),
           "readings": readings(red, m["counters"], waits),
           "counters": m["counters"], "spans": red,
           "device": {"kind": devices[0].device_kind}}
    for k, v in out["readings"].items():
        Ctx.log(f"{k}: {v!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
