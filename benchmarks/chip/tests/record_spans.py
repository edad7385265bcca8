"""Record the small TPU trace that ``test_bench_spans.py`` reads.

    python benchmarks/chip/tests/record_spans.py <out.xplane.pb>

On one chip, inside the ``bench.window`` span of ``trace.Tracer``: a
tiny stream engine (12x12, two conv layers, T=4, 4 slots) serving eight
DVS traces submitted 2 ms apart from a coroutine of their own, so that
its host spans and its chunk step's device scopes are in the file; then
one offline call, encode + ``snn_apply_batched`` on 4 images, for the
``encode`` scope.  Small, so that the file is; the ``/host:metadata``
plane (the programs' HLO, which neither reader uses) is left out.
"""
from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
DROP = "/host:metadata"


def without_plane(data: bytes, name: str) -> bytes:
    """The XSpace ``data`` without its plane named ``name``.  Every field
    of an XSpace (planes 1, errors 2, warnings 3, hostnames 4) is
    length-delimited."""
    from benchmarks.chip.spans import _fields, _text, _varint
    buf, out, i = memoryview(data), [], 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        n, i = _varint(buf, i)
        payload, i = (i, i + n), i + n
        if key >> 3 == 1 and any(num == 2 and _text(buf, v) == name
                                 for num, v in _fields(buf, *payload)):
            continue
        out.append(bytes(buf[start:i]))
    return b"".join(out)


async def _serve(engine, traces):
    async with engine:
        futs = []
        for tr in traces:
            futs.append(engine.submit_nowait(tr))
            await asyncio.sleep(0.002)
        await asyncio.gather(*futs)


def main(out: str) -> int:
    import jax

    from benchmarks.chip import spans
    from benchmarks.chip.trace import Tracer, find_xplane, reduce
    from repro.core import (CSNNConfig, ConvSpec, FCSpec, encode_input,
                            init_params, plan_network, snn_apply_batched)
    from repro.data.dvs import dvs_moving_edges
    from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig

    if jax.devices()[0].platform != "tpu":
        print("record_spans.py: no TPU", file=sys.stderr)
        return 2
    cfg = CSNNConfig(input_hw=(12, 12), input_channels=2,
                     layers=(ConvSpec(8), ConvSpec(8, pool=3), FCSpec(10)),
                     t_steps=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = plan_network(cfg, capacity=144, channel_block=8, batch_tile=4,
                        event_par=None, ingest=True)
    engine = CSNNEngine(params, cfg, plan, CSNNServeConfig(
        max_batch=4, continuous=True, stream=True, t_chunk=2))
    engine.warmup()
    traces = dvs_moving_edges(8, cfg.t_steps, cfg.input_hw, seed=3)[0]
    img_cfg = CSNNConfig(input_hw=(12, 12), layers=cfg.layers,
                         t_steps=cfg.t_steps)
    img_params = init_params(jax.random.PRNGKey(1), img_cfg)
    img_plan = plan_network(img_cfg, capacity=144, channel_block=8,
                            batch_tile=4, event_par=None)
    offline = jax.jit(lambda p, x: snn_apply_batched(
        p, encode_input(x, img_cfg), img_cfg, img_plan,
        collect_stats=False))
    images = jax.random.uniform(jax.random.PRNGKey(2), (4, 12, 12, 1))
    offline(img_params, images).block_until_ready()
    asyncio.run(_serve(engine, traces[:4]))  # warm the host paths

    tracer = Tracer()
    tracer.start()
    asyncio.run(_serve(engine, traces))
    offline(img_params, images).block_until_ready()
    tracer.stop()
    Path(out).write_bytes(without_plane(
        Path(find_xplane(tracer.dir)).read_bytes(), DROP))
    print(spans.table(spans.read(out)), file=sys.stderr)
    red = reduce(out)
    print(json.dumps({k: red[k] for k in ("window_s", "busy_s")}))
    return 0


if __name__ == "__main__":
    sys.path[1:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main(sys.argv[1]))
