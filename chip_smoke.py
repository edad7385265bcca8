"""Chip smoke run: the CSNN serving path once on a TPU, checked.

    python chip_smoke.py               # one chip: the paper network served
    python chip_smoke.py --chips 4     # four chips: the batch-sharded path

One chip runs the paper's network (28x28-32C3-32C3-P3-10C3-F10, T=5;
``configs/csnn_paper.FULL``) at full width with random weights from
``init_params(PRNGKey(seed))``, through the entry points a user calls:

  (a) ``plan_network`` with the analytic plan the serving launcher uses;
  (b) ``CSNNEngine`` in continuous mode with ``stream=True``: 64
      synthetic 2-polarity ``dvs_moving_edges`` traces through a 64-slot
      table (the ``serve --stream`` path);
  (c) the same engine on encoded images, plus one ``snn_apply_batched``
      call on the same batch;
  (d) every result against ``snn_apply_dense``, the frame-based float32
      reference, run on the chip at ``highest`` matmul precision.  Its FC
      head is recomputed here in float64 numpy from the reference's spike
      drive, so a head that ran as bf16 passes (in the program or in the
      reference) cannot agree with it;
  (e) each Pallas kernel, compiled, against its ``ref.py`` oracle,
      bit-exact.

``--chips 4`` runs only ``snn_apply_sharded`` over a 4-device batch mesh
at B=256 against ``snn_apply_batched`` on one chip.

Queues are sized to the whole feature map, so no event is dropped and the
event path must reproduce the dense reference.  Logits agree within
``csnn.LOGITS_RTOL``/``LOGITS_ATOL`` and spike counts exactly — except where the
reference conv sums a membrane potential in another order and a neuron
within float rounding of the threshold flips.  Such a sample is reported,
its divergence must be covered by reference neurons within
``FLIP_EPS`` of the threshold, and at most ``MAX_FLIPPED`` samples per
phase may flip; the logits check is never loosened for it.

``CSNNEngine`` does not expose per-layer spike counts, so the engine's
counts are inferred, not checked: its logits must match one
``snn_apply_batched`` call on the same inputs, and that call's counts are
checked against the reference.

The script needs a TPU: on any other platform it exits non-zero before
doing any work.  It runs in one process and starts no other.  The last
line of its standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from dataclasses import replace

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.core.csnn import LOGITS_ATOL, LOGITS_RTOL  # noqa: E402

FLIP_EPS = 1e-4      # |V_m - v_t| a summation-order difference can bridge
MAX_FLIPPED = 4      # samples per phase whose spike trains may diverge
BATCH = 64
SHARDED_BATCH = 256
TIMED_RUNS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- reference
def dense_reference(params, spikes, cfg):
    """Per-sample float32 reference at highest precision, vmapped over the
    batch: the logits of ``snn_apply_dense``; per conv layer, the
    pre-pool spike counts (B, T, C_out) and the reference membrane
    potentials' distance to the threshold, min over steps (B, H, W, C_out)
    — what a rounding flip is checked against; and the spikes the last
    conv layer hands to the FC head (B, T, H, W, C)."""
    import jax
    import jax.numpy as jnp

    from repro.core.csnn import ConvSpec, snn_apply_dense
    from repro.core.scheduler import run_conv_layer_dense

    def layer_trace(x, w, b):
        u = jax.vmap(lambda xt: jax.lax.conv_general_dilated(
            xt[None].astype(jnp.float32), w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)[0])(x)
        vm = jnp.cumsum(u + b, axis=0)                   # (T, H, W, C)
        return jnp.min(jnp.abs(vm - cfg.v_t), axis=0)

    def one(sp):
        counts, margins, x = [], [], sp
        for idx, spec in enumerate(cfg.layers):
            if not isinstance(spec, ConvSpec):
                continue
            p = params[f"conv{idx}"]
            pre = run_conv_layer_dense(x, p["w"], p["b"], cfg.v_t)
            counts.append(jnp.sum(pre, axis=(1, 2)).astype(jnp.int32))
            margins.append(layer_trace(x, p["w"], p["b"]))
            x = (run_conv_layer_dense(x, p["w"], p["b"], cfg.v_t,
                                      pool=spec.pool) if spec.pool else pre)
        return snn_apply_dense(params, sp, cfg), counts, margins, x

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(one))(spikes)


def float64_head(params, cfg, head_in):
    """Logits (B, K) of the FC head recomputed outside the program: the
    spike drive summed over T and contracted with the weights in float64
    numpy.  A head run as bf16 passes rounds the weights to 8 bits of
    mantissa (about 4e-3 relative) and cannot come within LOGITS_ATOL."""
    from repro.core.csnn import FCSpec
    (idx,) = [i for i, s in enumerate(cfg.layers) if isinstance(s, FCSpec)]
    p = params[f"fc{idx}"]
    b, t = head_in.shape[:2]
    drive = np.asarray(head_in, np.float64).reshape(b, t, -1).sum(1)
    return (drive @ np.asarray(p["w"], np.float64)
            + t * np.asarray(p["b"], np.float64))


def check_against_reference(name, logits, counts, ref, t_steps):
    """Compare one phase's logits (B, K) and per-layer counts [(B, T, C)]
    with ``dense_reference`` output; raise on any violation.  Returns the
    largest logit error over the samples whose spike trains match."""
    ref_logits, ref_counts, ref_margins = (np.asarray(ref[0]),
                                           [np.asarray(c) for c in ref[1]],
                                           [np.asarray(m) for m in ref[2]])
    logits = np.asarray(logits)
    b = logits.shape[0]
    flipped = []
    for s in range(b):
        for li, (got, want) in enumerate(zip(counts, ref_counts)):
            diff = np.abs(np.asarray(got[s]).astype(np.int64) - want[s])
            if diff.any():
                # neurons the divergence needs at the first diverging
                # layer (a latched neuron moves each later step's count
                # by at most one) against reference neurons that came
                # within FLIP_EPS of the threshold there
                need = -(-int(diff.sum()) // t_steps)
                near = int(np.sum(ref_margins[li][s] <= FLIP_EPS))
                log(f"{name}: sample {s} layer {li}: spike counts differ by "
                    f"{int(diff.sum())} (>= {need} neurons), reference "
                    f"neurons within {FLIP_EPS} of v_t: {near}")
                if need > near:
                    raise AssertionError(
                        f"{name}: sample {s} layer {li} diverges by {need} "
                        f"neurons but only {near} lie within float "
                        f"rounding of v_t — not a summation-order flip")
                flipped.append(s)
                break
    if len(flipped) > MAX_FLIPPED:
        raise AssertionError(f"{name}: {len(flipped)} samples flipped, more "
                             f"than the bound {MAX_FLIPPED}")
    keep = np.setdiff1d(np.arange(b), flipped)
    err = float(np.max(np.abs(logits[keep] - ref_logits[keep]),
                       initial=0.0))
    np.testing.assert_allclose(logits[keep], ref_logits[keep],
                               rtol=LOGITS_RTOL, atol=LOGITS_ATOL,
                               err_msg=f"{name}: logits vs reference")
    np.testing.assert_array_equal(
        np.argmax(logits[keep], -1), np.argmax(ref_logits[keep], -1),
        err_msg=f"{name}: predicted classes vs reference")
    log(f"{name}: logits within rtol={LOGITS_RTOL} atol={LOGITS_ATOL} of the "
        f"reference on {keep.size}/{b} samples (max |err| {err:.3e}); "
        f"classes agree; flipped samples: {flipped}")
    return err


# ---------------------------------------------------------------- phases
def timed(fn, runs=TIMED_RUNS):
    """(first-call seconds, median steady seconds, result)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return first, statistics.median(times), out


def phase_plan(cfg, batch, *, ingest=False):
    """(a) the analytic plan, sized so no queue can drop an event."""
    from repro.core.plan import plan_network
    h, w = cfg.input_hw
    plan = plan_network(cfg, capacity=h * w, channel_block=8,
                        batch_tile=batch, event_par=None, ingest=ingest)
    log(repr(plan))
    return plan


def phase_engine(name, params, cfg, plan, requests, *, stream, device):
    """Serve ``requests`` through the continuous engine; returns logits."""
    from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig
    n = len(requests)
    engine = CSNNEngine(params, cfg, plan,
                        CSNNServeConfig(max_batch=n, continuous=True,
                                        stream=stream))
    compile_s = engine.warmup()
    times, logits = [], None
    for _ in range(TIMED_RUNS):
        t0 = time.perf_counter()
        logits = engine.run_requests(requests)
        times.append(time.perf_counter() - t0)
    dt = statistics.median(times)
    log(f"{name}: compile {compile_s:.2f} s; steady {n / dt:.1f} samples/s "
        f"on {device} (median of {TIMED_RUNS}, {n} requests, "
        f"{n} slots, chunks={engine.stats['chunks']}, "
        f"slot_utilization={engine.slot_utilization:.2f})")
    return logits


def phase_batched(name, params, cfg, plan, spikes, *, device):
    """One jitted ``snn_apply_batched`` with per-layer stats."""
    import jax

    from repro.core.csnn import snn_apply_batched
    fn = jax.jit(lambda sp: snn_apply_batched(params, sp, cfg, plan,
                                              collect_stats=True))
    first, dt, (logits, stats) = timed(lambda: fn(spikes))
    n = spikes.shape[0]
    log(f"{name}: compile+first {first:.2f} s; steady {n / dt:.1f} "
        f"samples/s on {device} (median of {TIMED_RUNS}, batch {n})")
    for lp, st in zip(plan.layers, stats):
        log(f"{name}: layer {lp.name}: events={int(np.sum(st.in_spike_counts))}"
            f" spikes={int(np.sum(st.out_spike_counts))} "
            f"peak_queue={int(np.max(st.in_spike_counts))} "
            f"capacity={lp.capacity} variant={lp.resolve_variant()}")
    return logits, [st.out_spike_counts for st in stats]


def phase_reference(name, params, cfg, spikes):
    """The reference's (logits, counts, margins), with the logits from
    ``float64_head``; ``snn_apply_dense``'s own float32 head must agree
    with them too."""
    t0 = time.perf_counter()
    dense_logits, counts, margins, head_in = dense_reference(params, spikes,
                                                             cfg)
    log(f"{name}: reference compile+run {time.perf_counter() - t0:.2f} s")
    logits = float64_head(params, cfg, head_in)
    dense_logits = np.asarray(dense_logits)
    err = float(np.max(np.abs(dense_logits - logits)))
    np.testing.assert_allclose(dense_logits, logits, rtol=LOGITS_RTOL,
                               atol=LOGITS_ATOL,
                               err_msg=f"{name}: snn_apply_dense head vs "
                                       f"float64 head")
    log(f"{name}: snn_apply_dense logits within rtol={LOGITS_RTOL} "
        f"atol={LOGITS_ATOL} of the float64 head (max |err| {err:.3e})")
    return (logits, [np.asarray(c) for c in counts],
            [np.asarray(m) for m in margins])


def same_path(name, engine_logits, batched_logits):
    """The engine runs the batched event path slot by slot, so its logits
    must match one batched call on the same inputs — this is what lets
    the batched call's spike counts stand for the engine's, which the
    engine does not expose."""
    np.testing.assert_allclose(np.asarray(engine_logits),
                               np.asarray(batched_logits),
                               rtol=LOGITS_RTOL, atol=LOGITS_ATOL,
                               err_msg=f"{name}: engine vs batched logits")
    log(f"{name}: engine logits within rtol={LOGITS_RTOL} atol={LOGITS_ATOL}"
        f" of snn_apply_batched on every sample")


def phase_kernels(seed, device):
    """(e) Each Pallas entry point compiled on the chip at the served
    shapes, bit-exact against its ``ref.py`` oracle (through the ops
    wrappers, which give kernel and oracle the same padded operands)."""
    import jax
    import jax.numpy as jnp

    from repro.core.aeq import build_aeq_batched
    from repro.core.geometry import GEOM_3X3
    from repro.kernels.event_conv.ops import event_conv, event_conv_batched
    from repro.kernels.threshold_pool.ops import threshold_pool

    rng = np.random.default_rng(seed)
    b, h, w, cb = BATCH, 28, 28, 8
    fmaps = jnp.asarray(rng.random((b, h, w)) < 0.3)
    queues = build_aeq_batched(fmaps, h * w, geometry=GEOM_3X3)
    vm = jnp.asarray(rng.normal(size=(b, h, w, cb)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 3, cb)), jnp.float32)
    q0 = jax.tree_util.tree_map(lambda x: x[0], queues)

    def same(name, got, want):
        for g, r in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                          err_msg=name)
        log(f"kernel {name}: compiled on {device}, bit-exact vs ref.py")

    for par in (1, 8):
        same(f"event_conv_pallas{'_interlaced' if par > 1 else ''}",
             event_conv(vm[0], q0, k, block_e=None, event_par=par,
                        interpret=False),
             event_conv(vm[0], q0, k, block_e=None, event_par=par,
                        use_kernel=False))
        same(f"event_conv_pallas{'_interlaced' if par > 1 else ''}_batched",
             event_conv_batched(vm, queues, k, block_e=None, event_par=par,
                                interpret=False),
             event_conv_batched(vm, queues, k, block_e=None, event_par=par,
                                use_kernel=False))

    c = 32
    tvm = jnp.asarray(rng.normal(size=(h, w, c)) + 0.8, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(c,)) * 0.1, jnp.float32)
    fired = jnp.asarray(rng.random((h, w, c)) < 0.05)
    for pool, cap in ((3, 100), (3, 24), (None, None)):
        kw = dict(v_t=1.0, pool=pool, emit_capacity=cap)
        same(f"threshold_pool_pallas(pool={pool}, emit_capacity={cap})",
             threshold_pool(tvm, bias, fired, interpret=False, **kw),
             threshold_pool(tvm, bias, fired, use_kernel=False, **kw))


def one_chip(seed: int, device: str) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import csnn_paper
    from repro.core.csnn import encode_input, init_params
    from repro.data.dvs import dvs_moving_edges, events_to_frames

    cfg = csnn_paper.FULL
    h, w = cfg.input_hw
    errors = {}

    # (b) streaming DVS: 2-polarity traces, 64-slot continuous engine
    cfg_s = replace(cfg, input_channels=2)
    params_s = init_params(jax.random.PRNGKey(seed), cfg_s)
    plan_s = phase_plan(cfg_s, BATCH, ingest=True)
    traces, _ = dvs_moving_edges(BATCH, cfg.t_steps, (h, w), seed=seed + 1)
    log(f"stream: {sum(t.shape[0] for t in traces)} events in "
        f"{len(traces)} traces")
    logits = phase_engine("stream engine", params_s, cfg_s, plan_s, traces,
                          stream=True, device=device)
    frames = jnp.asarray(np.stack([events_to_frames(t, cfg.t_steps, (h, w))
                                   for t in traces]))
    b_logits, b_counts = phase_batched("stream batched", params_s, cfg_s,
                                       plan_s, frames, device=device)
    same_path("stream", logits, b_logits)
    ref = phase_reference("stream reference", params_s, cfg_s, frames)
    errors["stream engine"] = check_against_reference(
        "stream engine", logits, b_counts, ref, cfg.t_steps)
    errors["stream batched"] = check_against_reference(
        "stream batched", b_logits, b_counts, ref, cfg.t_steps)

    # (a) + (c) encoded images through the same engine mode and batched
    params = init_params(jax.random.PRNGKey(seed), cfg)
    plan = phase_plan(cfg, BATCH)
    images = jax.random.uniform(jax.random.PRNGKey(seed + 2),
                                (BATCH, h, w, cfg.input_channels))
    logits = phase_engine("image engine", params, cfg, plan, list(images),
                          stream=False, device=device)
    spikes = encode_input(images, cfg)
    b_logits, b_counts = phase_batched("image batched", params, cfg, plan,
                                       spikes, device=device)
    same_path("image", logits, b_logits)
    ref = phase_reference("image reference", params, cfg, spikes)
    errors["image engine"] = check_against_reference(
        "image engine", logits, b_counts, ref, cfg.t_steps)
    errors["image batched"] = check_against_reference(
        "image batched", b_logits, b_counts, ref, cfg.t_steps)

    # (e) the Pallas kernels, compiled
    phase_kernels(seed, device)
    return errors


def four_chips(seed: int, device: str) -> dict:
    """``snn_apply_sharded`` over a 4-device batch mesh vs
    ``snn_apply_batched`` on one chip, same plan and batch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import csnn_paper
    from repro.core.csnn import (encode_input, init_params,
                                 snn_apply_batched, snn_apply_sharded)
    from repro.sharding.specs import batch_mesh

    cfg = csnn_paper.FULL
    h, w = cfg.input_hw
    params = init_params(jax.random.PRNGKey(seed), cfg)
    plan = phase_plan(cfg, SHARDED_BATCH)
    mesh = batch_mesh(4, axis=plan.batch_axis)
    images = jax.random.uniform(jax.random.PRNGKey(seed + 2),
                                (SHARDED_BATCH, h, w, cfg.input_channels))
    spikes = jax.device_put(encode_input(images, cfg),
                            NamedSharding(mesh, P(plan.batch_axis)))
    for shard in spikes.addressable_shards:
        log(f"sharded: input rows {shard.index[0].start}:"
            f"{shard.index[0].stop} on {shard.device}")
    fn = jax.jit(lambda sp: snn_apply_sharded(params, sp, cfg, plan,
                                              mesh=mesh, collect_stats=True))
    first, dt, (logits, stats) = timed(lambda: fn(spikes))
    log(f"sharded: compile+first {first:.2f} s; steady "
        f"{SHARDED_BATCH / dt:.1f} samples/s on 4 x {device} (median of "
        f"{TIMED_RUNS}, batch {SHARDED_BATCH}); logits on "
        f"{sorted(str(d) for d in logits.devices())}")
    one = jax.device_put(spikes, jax.devices()[0])
    b_logits, b_counts = phase_batched("sharded reference (one chip)",
                                       params, cfg, plan, one, device=device)
    for li, (st, want) in enumerate(zip(stats, b_counts)):
        np.testing.assert_array_equal(
            np.asarray(st.out_spike_counts), np.asarray(want),
            err_msg=f"sharded: layer {li} spike counts vs one chip")
    err = float(np.max(np.abs(np.asarray(logits) - np.asarray(b_logits))))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(b_logits),
                               rtol=LOGITS_RTOL, atol=LOGITS_ATOL,
                               err_msg="sharded: logits vs one chip")
    log(f"sharded: per-layer spike counts equal; logits within "
        f"rtol={LOGITS_RTOL} atol={LOGITS_ATOL} of one chip "
        f"(max |err| {err:.3e})")
    return {"sharded": err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip; 4: only the "
                         "batch-sharded path over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"platform={platform} device_kind={kind} device_count={len(devices)}")
    if platform != "tpu":
        log(f"chip_smoke: no TPU — JAX reports platform {platform!r}; "
            f"this script measures and checks the chip only")
        return 1
    if len(devices) < args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX reports {len(devices)}")
        return 1

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    log(f"compile cache: {cache or os.environ['JAX_COMPILATION_CACHE_DIR']}")

    run = four_chips if args.chips == 4 else one_chip
    errors = run(args.seed, kind)
    peak = devices[0].memory_stats().get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak} on {devices[0]}")
    log(f"largest logit error: {max(errors.values()):.3e} "
        f"({', '.join(f'{k} {v:.3e}' for k, v in errors.items())})")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
