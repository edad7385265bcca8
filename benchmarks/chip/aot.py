"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/chip/aot.py \
        paper-offline --batch 256 512 1024

For an offline cell, the timed call (``drivers/offline_batch.make_call``)
at each batch given, on one chip; for a serving cell, the engine's chunk
step (``snn_step_chunk`` on a stream window) at the largest occupancy
bucket, the cell's slot count.  Prints the plan and ``memory_analysis``
per program.  Nothing runs: this says whether the program compiles and
fits, not how fast it is.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def compile_cell(name: str, batches) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import inputs, program
    from benchmarks.chip.cell import load_cell, load_module

    cell = load_cell(ROOT, name)
    cfg, mix = cell.cfg, cell.mix
    reference = load_module(cell.bench_dir / "reference"
                            / f"{cfg['reference']}.py")
    c_in = inputs.CHANNELS[mix["input"]]
    h, w = cfg["input_hw"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    calib = jax.ShapeDtypeStruct((8, h, w, c_in), jnp.float32)
    params = jax.eval_shape(
        lambda k, c: reference.make_params(k, c, cfg, c_in),
        jax.random.key_data(jax.random.PRNGKey(0)), calib)
    net = program.csnn_config(cfg, c_in)

    def shaped(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)

    if mix["driver"] == "open_loop_stream":
        from repro.core.aeq import StreamState
        from repro.core.csnn import init_state, snn_step_chunk
        slots = cell.own["slots"]
        plan = program.plan(net, cfg, slots, ingest=True)
        geom = plan.layers[0].geometry
        state = jax.eval_shape(lambda p: init_state(p, net, plan, slots),
                               params)
        banks = jax.ShapeDtypeStruct(
            (slots, 1, c_in, geom.n_banks, -(-h // geom.kh),
             -(-w // geom.kw)), jnp.bool_)
        progs = {f"chunk step, {slots} slots, {plan}": (
            lambda p, s, b: snn_step_chunk(p, s, StreamState(banks=b), net,
                                           plan),
            (shaped(params, one), shaped(state, one), shaped(banks, one)))}
    else:
        from benchmarks.chip.drivers.offline_batch import make_call
        progs = {}
        for b in batches:
            plan = program.plan(net, cfg, b)
            x = jax.ShapeDtypeStruct((b, h, w, c_in), jnp.float32)
            progs[f"B={b}, {plan}"] = (
                make_call(net, plan, encode=True),
                (shaped(params, one), shaped(x, one)))
    for label, (fn, args) in progs.items():
        t = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        mem = compiled.memory_analysis()
        print(f"{name} {label}\n  compiled in "
              f"{time.perf_counter() - t:.1f} s; args "
              f"{mem.argument_size_in_bytes} B, out "
              f"{mem.output_size_in_bytes} B, temp "
              f"{mem.temp_size_in_bytes} B", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--batch", type=int, nargs="*", default=None,
                    help="batches of an offline cell (default: the mix's)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmarks.chip.cell import load_cell
    for name in args.cells:
        compile_cell(name, args.batch or [load_cell(ROOT, name).mix.get(
            "batch")])
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
