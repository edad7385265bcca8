"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python benchmarks/chip/tests/record_trace.py <out.xplane.pb>

On one chip: two ``snn_apply_batched`` calls of a 6x6 two-layer network
at T=2 and batch 1 on binned DVS frames (small, so that the file is),
with host sleeps around them so the window has idle gaps, inside the
``bench.window`` span of ``trace.Tracer``.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]


def main(out: str) -> int:
    import jax

    from benchmarks.chip import inputs, program
    from benchmarks.chip.cell import load_module, prng_key
    from benchmarks.chip.trace import Tracer, find_xplane, reduce
    from repro.core.csnn import snn_apply_batched

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 2
    from bench_fixture import TINY
    cfg = dict(TINY, input_hw=[6, 6], t_steps=2,
               layers=[{"conv": 4, "kernel": 3},
                       {"conv": 4, "kernel": 3, "pool": 3}, {"fc": 10}],
               conversion={"percentile": 99.9, "calibration_inputs": 1})
    reference = load_module(ROOT / "benchmarks/chip/reference/csnn.py")
    mix = {"input": "events", "params": {}}
    traces = inputs.make(mix, cfg, 1, 1)
    x = inputs.frames(traces, cfg)
    params = reference.make_params(prng_key(1, "weights"),
                                   inputs.ann_input("events", traces, cfg),
                                   cfg, 2)
    net = program.csnn_config(cfg, 2)
    plan = program.plan(net, cfg, 1)
    fn = jax.jit(lambda p, s: snn_apply_batched(p, s, net, plan,
                                                collect_stats=False))
    xd = jax.device_put(x)
    fn(params, xd).block_until_ready()
    tracer = Tracer()
    tracer.start()
    for _ in range(2):
        time.sleep(0.01)
        fn(params, xd).block_until_ready()
    time.sleep(0.01)
    tracer.stop()
    shutil.copy(find_xplane(tracer.dir), out)
    red = reduce(out)
    print(json.dumps({k: red[k] for k in ("window_s", "busy_s")}))
    return 0


if __name__ == "__main__":
    sys.path[1:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main(sys.argv[1]))
