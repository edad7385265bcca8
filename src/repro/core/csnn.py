"""CSNN model assembly: the paper's 28x28-32C3-32C3-P3-10C3-F10 network.

The execution paths share one parameter pytree:

* ``ann_apply``     — the clamped-ReLU CNN used for training (paper
  Sec. VII trains a conventional CNN and converts it);
* ``snn_apply``     — T-step m-TTFS spiking inference through the
  event-driven scheduler (Algorithm 1), the system under study;
* ``snn_apply_batched`` — the same inference for a whole sample batch
  with queue construction and kernel launches amortized across it
  (bit-exact vs ``vmap(snn_apply)``; the serving entry point).  Built as
  a thin wrapper over the step-resumable form below;
* ``init_state`` / ``snn_step_chunk`` / ``snn_readout`` — the pipeline
  cut at time-chunk boundaries: an explicit :class:`CSNNState` carry
  (per-layer MemPot stacks + fired latches + accumulated FC drive)
  advances ``plan.chunk_steps`` steps per call.  Chaining chunks is
  bit-exact vs the monolithic apply; the serving engine's continuous
  batching (slot-level refill) runs on this form;
* ``snn_apply_sharded`` — ``snn_apply_batched`` shard_mapped over the
  batch axis of a device mesh (queues are per-sample-independent, so the
  shards never communicate; bit-exact vs the unsharded batched path);
* ``snn_apply_dense`` — frame-based spiking oracle (dense baseline).

Every entry point consumes a :class:`~repro.core.plan.NetworkPlan` — the
static per-layer resource plan (queue capacities, channel/event blocks,
membrane tiles) derived once by ``plan_network``.  The loose
``capacity=``/``channel_block=`` kwargs remain as deprecation shims that
build an equivalent plan on the fly (bit-exact; tests/test_plan.py).

Parameters are plain dicts of jnp arrays; layer specs are tiny frozen
dataclasses so a config file can describe any CSNN in one line.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .aeq import StreamState, build_fused_handoff
from .encoding import mttfs_thresholds, multi_threshold_encode
from .plan import NetworkPlan, plan_network
from .scheduler import (ConvCarry, LayerStats, init_conv_carry,
                        run_conv_layer_batched_chunk,
                        run_conv_layer_batched_chunk_streamed,
                        run_conv_layer_batched_planned, run_conv_layer_dense,
                        run_conv_layer_planned, head_dot, run_fc_head,
                        run_fc_head_batched)


# Logits of one sample agree across batch shapes (engine slot tables,
# tile padding, shards) and with the float32 reference to this tolerance,
# not bit for bit: the head contracts exact spike counts with float32
# weights, and the dot's reduction order depends on the operand shape.
# Spike counts agree exactly.  chip_smoke.py and the serving tests hold
# every path to it.
LOGITS_RTOL = 1e-5
LOGITS_ATOL = 1e-5


@dataclass(frozen=True)
class ConvSpec:
    channels: int
    kernel: int = 3
    pool: Optional[int] = None  # OR-max-pool window applied after this layer


@dataclass(frozen=True)
class FCSpec:
    features: int


@dataclass(frozen=True)
class CSNNConfig:
    """`28x28-32C3-32C3-P3-10C3-F10` == the paper's network (defaults)."""

    input_hw: tuple[int, int] = (28, 28)
    input_channels: int = 1   # e.g. 2 for 2-polarity DVS event frames
    layers: Sequence = field(default_factory=lambda: (
        ConvSpec(32), ConvSpec(32, pool=3), ConvSpec(10), FCSpec(10)))
    t_steps: int = 5          # paper: T=5 gave the best accuracy
    v_t: float = 1.0          # firing threshold after conversion
    relu_clamp: float = 1.0   # clamped-ReLU ceiling used during ANN training


def conv_out_hw(hw: tuple[int, int], spec: ConvSpec) -> tuple[int, int]:
    h, w = hw  # SAME padding keeps H, W; pooling ceil-divides
    if spec.pool:
        return (-(-h // spec.pool), -(-w // spec.pool))
    return (h, w)


def init_params(rng: jax.Array, cfg: CSNNConfig, dtype=jnp.float32) -> dict:
    params = {}
    hw, c_in = cfg.input_hw, cfg.input_channels
    for idx, spec in enumerate(cfg.layers):
        key = jax.random.fold_in(rng, idx)
        if isinstance(spec, ConvSpec):
            fan_in = spec.kernel * spec.kernel * c_in
            params[f"conv{idx}"] = {
                "w": jax.random.normal(key, (spec.kernel, spec.kernel, c_in, spec.channels),
                                       dtype) * (2.0 / fan_in) ** 0.5,
                "b": jnp.zeros((spec.channels,), dtype),
            }
            hw, c_in = conv_out_hw(hw, spec), spec.channels
        else:
            d = hw[0] * hw[1] * c_in
            params[f"fc{idx}"] = {
                "w": jax.random.normal(key, (d, spec.features), dtype) * (1.0 / d) ** 0.5,
                "b": jnp.zeros((spec.features,), dtype),
            }
    return params


def ann_apply(params: dict, images: jax.Array, cfg: CSNNConfig) -> jax.Array:
    """Clamped-ReLU CNN forward (training path).
    images: (B, H, W, cfg.input_channels) in [0,1]."""
    x = images
    for idx, spec in enumerate(cfg.layers):
        if isinstance(spec, ConvSpec):
            p = params[f"conv{idx}"]
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = x + p["b"]
            x = jnp.clip(x, 0.0, cfg.relu_clamp)  # clamped ReLU (Rueckauer)
            if spec.pool:
                x = _max_pool(x, spec.pool)
        else:
            p = params[f"fc{idx}"]
            x = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
    return x


def _max_pool(x: jax.Array, window: int) -> jax.Array:
    pads = [(0, 0), (0, -x.shape[1] % window), (0, -x.shape[2] % window), (0, 0)]
    x = jnp.pad(x, pads, constant_values=-jnp.inf)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1), (1, window, window, 1), "VALID")


def encode_input(images: jax.Array, cfg: CSNNConfig) -> jax.Array:
    """(B, H, W, C) floats in [0,1] -> (B, T, H, W, C) m-TTFS input spikes."""
    with jax.named_scope("encode"):
        thresholds = mttfs_thresholds(cfg.t_steps)
        enc = lambda img: multi_threshold_encode(img, thresholds, cfg.t_steps)
        return jax.vmap(enc)(images)


def _resolve_plan(
    cfg: CSNNConfig,
    plan: Optional[NetworkPlan],
    capacity: int | Sequence[int],
    channel_block: int,
    sat_bits: Optional[int],
) -> NetworkPlan:
    """Deprecation-shim glue: build a plan from loose kwargs when the
    caller did not pass one, else validate the given plan against cfg."""
    if plan is None:
        return plan_network(cfg, capacity=capacity,
                            channel_block=channel_block, sat_bits=sat_bits)
    return plan.validate(cfg)


def snn_apply(
    params: dict,
    in_spikes: jax.Array,
    cfg: CSNNConfig,
    plan: Optional[NetworkPlan] = None,
    *,
    capacity: int | Sequence[int] = 256,
    channel_block: int = 1,
    sat_bits: Optional[int] = None,
    collect_stats: bool = True,
):
    """Event-driven m-TTFS inference for ONE sample.

    in_spikes: (T, H, W, 1) bool.  Returns (logits, [LayerStats, ...]).
    ``plan`` carries the per-layer resource sizing (build it once with
    ``plan_network``); the ``capacity``/``channel_block``/``sat_bits``
    kwargs are the deprecated shim spelling and are ignored when a plan
    is given.  vmap over samples for batching; the paper's xP parallelism
    sweep maps to batching + channel_block.
    """
    plan = _resolve_plan(cfg, plan, capacity, channel_block, sat_bits)
    x, stats, ci = in_spikes, [], 0
    for idx, spec in enumerate(cfg.layers):
        if isinstance(spec, ConvSpec):
            p = params[f"conv{idx}"]
            with jax.named_scope(f"conv{idx}"):
                x, st = run_conv_layer_planned(x, p["w"], p["b"], cfg.v_t,
                                               plan.layers[ci])
            stats.append(st)
            ci += 1
        else:
            p = params[f"fc{idx}"]
            with jax.named_scope("head"):
                logits = run_fc_head(x, p["w"], p["b"],
                                     capacity=plan.fc_capacity)
    return (logits, stats) if collect_stats else logits


class CSNNState(NamedTuple):
    """Explicit per-layer carry of the event pipeline over a sample batch.

    Everything ``snn_apply_batched`` used to keep implicit inside its
    per-layer scans, extracted so execution can stop and resume at any
    chunk boundary:

    * ``convs`` — one :class:`~repro.core.scheduler.ConvCarry` per conv
      layer (halo-padded MemPot stack + m-TTFS fired latches);
    * ``fc_drive`` — (B, D) accumulated spike drive into the
      classification head (exact small integers in the head's dtype, so
      chunked accumulation is bit-exact vs one whole-T sum).

    A pytree (NamedTuple of arrays): jit/donate/device_put all work.
    Every row is per-sample independent — the serving engine exploits
    this by resetting single rows as batch slots retire and refill.
    """

    convs: tuple
    fc_drive: jax.Array


def init_state(params: dict, cfg: CSNNConfig,
               plan: NetworkPlan, batch: int) -> CSNNState:
    """Fresh (t=0) :class:`CSNNState` for ``batch`` samples."""
    plan.validate(cfg)
    convs = tuple(init_conv_carry(lp, batch) for lp in plan.layers)
    last = plan.layers[-1]
    d = last.out_hw[0] * last.out_hw[1] * last.c_out
    fc_dtype = jnp.float32
    for idx, spec in enumerate(cfg.layers):
        if not isinstance(spec, ConvSpec):
            fc_dtype = params[f"fc{idx}"]["w"].dtype
    return CSNNState(convs=convs, fc_drive=jnp.zeros((batch, d), fc_dtype))


def snn_step_chunk(
    params: dict,
    state: CSNNState,
    spikes_chunk: jax.Array,
    cfg: CSNNConfig,
    plan: NetworkPlan,
    *,
    backend: str = "jax",
    collect_stats: bool = False,
):
    """Advance the batched event pipeline by one chunk of time steps.

    spikes_chunk: (B, t_chunk, H, W, C_in) bool — the next ``t_chunk``
    input time steps for every batch row (``plan.chunk_steps`` per call;
    any chunk length works, but the serving engine keeps one shape so
    nothing retraces) — OR a :class:`~repro.core.aeq.StreamState` with
    banks (B, t_chunk, C_in, n_banks, HB, WB): pre-ingested raw DVS events
    (``aeq.append_events*``), in which case the first conv layer consumes
    the input queues finalized sort-free from the banks instead of
    re-compacting dense frames (bit-exact either way;
    tests/test_streaming.py).  Each conv layer consumes the chunk from
    its carry, the head drive accumulates the final conv layer's output
    spikes, and the new :class:`CSNNState` is returned.  Chaining
    T/t_chunk calls from ``init_state`` reproduces the monolithic
    pipeline bit-exactly (per time step the computation is identical;
    only the scans are cut), which is what lets the engine admit new
    requests mid-flight without perturbing in-flight ones.

    Fused spike emission (ISSUE 10): when the NEXT conv layer is pinned
    to the ``"fused-handoff"`` variant, this loop is where the handoff
    happens — the producer's pooled output is compacted once into the
    consumer's :class:`~repro.core.aeq.FusedHandoff` carrier at the layer
    boundary and passed in place of the dense spike tensor, so the
    consumer never re-runs the dense->queue compaction pass.

    Every op runs under a named scope of its unit (``jax.named_scope``):
    ``conv{i}`` per conv layer, with ``compact``, ``conv_unit``,
    ``threshold`` and ``handoff`` inside it, and ``head`` for the drive
    into the classification unit, so a device trace names the unit of
    each op.

    Returns ``state`` or ``(state, [chunk LayerStats, ...])`` with
    ``collect_stats``.
    """
    x, stats, ci = spikes_chunk, [], 0
    n_conv = len(plan.layers)
    new_convs = []
    for idx, spec in enumerate(cfg.layers):
        if not isinstance(spec, ConvSpec):
            continue
        p = params[f"conv{idx}"]
        with jax.named_scope(f"conv{idx}"):
            if isinstance(x, StreamState):  # streamed input, layer 0 only
                x, carry, st = run_conv_layer_batched_chunk_streamed(
                    x, p["w"], p["b"], cfg.v_t, plan.layers[ci],
                    state.convs[ci], backend=backend)
            else:
                x, carry, st = run_conv_layer_batched_chunk(
                    x, p["w"], p["b"], cfg.v_t, plan.layers[ci],
                    state.convs[ci], backend=backend)
            new_convs.append(carry)
            stats.append(st)
            ci += 1
            if (ci < n_conv and plan.layers[ci].resolve_variant(backend)
                    == "fused-handoff"):
                nxt = plan.layers[ci]
                with jax.named_scope("handoff"):
                    x = build_fused_handoff(x, nxt.capacity, nxt.geometry)
    b, c = x.shape[:2]
    with jax.named_scope("head"):
        drive = x.reshape(b, c, -1).astype(state.fc_drive.dtype).sum(axis=1)
    state = CSNNState(convs=tuple(new_convs),
                      fc_drive=state.fc_drive + drive)
    return (state, stats) if collect_stats else state


def snn_readout(params: dict, state: CSNNState, cfg: CSNNConfig,
                plan: Optional[NetworkPlan] = None) -> jax.Array:
    """Classification-unit readout of a (fully or partially stepped) state.

    Matches ``run_fc_head_batched`` on the accumulated drive: the output
    neurons integrate weighted spikes plus ``T x bias`` and are never
    thresholded.  After all T steps the result is bit-exact vs the
    monolithic ``snn_apply_batched`` logits — ``fc_drive`` holds exact
    spike counts, so the (B, D) contraction sees identical values.
    When ``plan.fc_capacity`` is set, the drive routes through the
    event-driven sparse head (``sparse_ffn.event_readout``) instead:
    top-``fc_capacity`` AEQ compaction scattered back into the same
    dense contraction — bit-exact while the queue covers every nonzero
    drive entry (tests/test_sparse_ffn.py).
    """
    fc_capacity = plan.fc_capacity if plan is not None else None
    logits = None
    for idx, spec in enumerate(cfg.layers):
        if not isinstance(spec, ConvSpec):
            p = params[f"fc{idx}"]
            drive = state.fc_drive
            with jax.named_scope("head"):
                if fc_capacity is not None:
                    from .sparse_ffn import event_readout
                    logits = (event_readout(drive, p["w"],
                                            capacity=fc_capacity)
                              + cfg.t_steps * p["b"])
                else:
                    logits = head_dot(drive, p["w"]) + cfg.t_steps * p["b"]
    if logits is None:
        raise ValueError("cfg has no FC head layer")
    return logits


def _merge_chunk_stats(chunks: list) -> list:
    """Stitch per-chunk LayerStats back into whole-T stats: counts
    concatenate along the time axis; ``in_sparsity`` averages the
    (equal-length) chunk means; ``event_block`` is constant."""
    merged = []
    for per_layer in zip(*chunks):
        merged.append(LayerStats(
            in_spike_counts=jnp.concatenate(
                [s.in_spike_counts for s in per_layer], axis=1),
            out_spike_counts=jnp.concatenate(
                [s.out_spike_counts for s in per_layer], axis=1),
            in_sparsity=sum(s.in_sparsity for s in per_layer) / len(per_layer),
            event_block=per_layer[0].event_block,
            event_par=per_layer[0].event_par,
        ))
    return merged


def snn_apply_batched(
    params: dict,
    in_spikes: jax.Array,
    cfg: CSNNConfig,
    plan: Optional[NetworkPlan] = None,
    *,
    capacity: int | Sequence[int] = 256,
    channel_block: int = 1,
    sat_bits: Optional[int] = None,
    collect_stats: bool = True,
    backend: str = "jax",
):
    """Event-driven m-TTFS inference for a SAMPLE BATCH.

    in_spikes: (B, T, H, W, C_in) bool.  Returns (logits (B, n_classes),
    [LayerStats, ...]) — stats carry a leading batch dim.  Logits are
    bit-exact vs ``jax.vmap(snn_apply)`` (tests/test_batched.py); the
    difference is purely structural: per layer, ONE fused queue
    compaction over (B, T, C_in) and ONE conv-unit launch per
    (t, c_in, channel-block) step feed the whole batch, and the
    self-timed early exit is shared batch-wide.  This is the serving
    path (launch/serve.py, serve/csnn_engine.py) and the batched row of
    Table V.  ``plan`` carries the per-layer sizing; the loose kwargs are
    the deprecated shim spelling, ignored when a plan is given.

    Execution is a wrapper over the step-resumable form: ``init_state``
    then ``snn_step_chunk`` over ``plan.chunk_steps`` slices (one chunk —
    the original monolithic graph — unless the plan sets ``t_chunk``),
    then ``snn_readout``.  Bit-exact for every chunking
    (tests/test_chunked.py).
    """
    plan = _resolve_plan(cfg, plan, capacity, channel_block, sat_bits)
    t, chunk = cfg.t_steps, plan.chunk_steps
    state = init_state(params, cfg, plan, in_spikes.shape[0])
    chunk_stats = []
    for k in range(0, t, chunk):
        state, stats = snn_step_chunk(
            params, state, in_spikes[:, k:k + chunk], cfg, plan,
            backend=backend, collect_stats=True)
        chunk_stats.append(stats)
    logits = snn_readout(params, state, cfg, plan)
    if not collect_stats:
        return logits
    return logits, _merge_chunk_stats(chunk_stats)


def _conv_stack_batched(params: dict, x: jax.Array, cfg: CSNNConfig,
                        plan: NetworkPlan, backend: str):
    """The event-driven conv layers of the batched pipeline (everything up
    to the classification unit).  Split out so ``snn_apply_sharded`` can
    run it per shard — it is per-sample exact for any leading batch size —
    while the FC head matmul runs once on the gathered batch (matmul
    reduction order depends on the contraction shape, so the head must see
    the same (B, D) as the unsharded path to stay bit-exact)."""
    stats, ci = [], 0
    for idx, spec in enumerate(cfg.layers):
        if isinstance(spec, ConvSpec):
            p = params[f"conv{idx}"]
            with jax.named_scope(f"conv{idx}"):
                x, st = run_conv_layer_batched_planned(
                    x, p["w"], p["b"], cfg.v_t, plan.layers[ci],
                    backend=backend)
            stats.append(st)
            ci += 1
    return x, stats


def _fc_head_batched(params: dict, x: jax.Array, cfg: CSNNConfig,
                     fc_capacity: Optional[int] = None) -> jax.Array:
    logits = None
    for idx, spec in enumerate(cfg.layers):
        if not isinstance(spec, ConvSpec):
            p = params[f"fc{idx}"]
            # last head wins, matching snn_apply's per-layer loop exactly
            with jax.named_scope("head"):
                logits = run_fc_head_batched(x, p["w"], p["b"],
                                             capacity=fc_capacity)
    if logits is None:
        raise ValueError("cfg has no FC head layer")
    return logits


def snn_apply_sharded(
    params: dict,
    in_spikes: jax.Array,
    cfg: CSNNConfig,
    plan: Optional[NetworkPlan] = None,
    *,
    mesh=None,
    capacity: int | Sequence[int] = 256,
    channel_block: int = 1,
    sat_bits: Optional[int] = None,
    collect_stats: bool = False,
    backend: str = "jax",
):
    """``snn_apply_batched`` sharded over the batch axis of a device mesh.

    in_spikes: (B, T, H, W, 1) bool with B divisible by the mesh's
    ``plan.batch_axis`` size.  The event queues are per-sample-independent
    and the early-exit bound only ever *skips invalid slots*, so each
    device runs the event-driven conv stack on its B/n shard with zero
    communication; the final spike maps (tiny: T x H' x W' x C_out bools)
    are gathered and the classification head runs once on the full batch
    — the head matmul must see the same (B, D) contraction as the
    unsharded path because XLA's dot reduction order is shape-dependent.
    The gathered logits are bit-exact vs ``snn_apply_batched``
    (tests/test_sharded.py; ISSUE 3 acceptance).

    ``mesh`` defaults to a 1-D mesh over all local devices
    (``sharding.specs.batch_mesh``).  Validated on the forced-host-device
    CPU mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.specs import batch_mesh

    plan = _resolve_plan(cfg, plan, capacity, channel_block, sat_bits)
    axis = plan.batch_axis
    if mesh is None:
        mesh = batch_mesh(axis=axis)
    if axis not in mesh.shape:
        raise ValueError(f"mesh {mesh.shape} lacks the plan's batch axis "
                         f"{axis!r}")
    n_dev = mesh.shape[axis]
    b = in_spikes.shape[0]
    if b % n_dev != 0:
        raise ValueError(f"batch {b} does not divide over {n_dev} devices")

    def body(p, sp):
        return _conv_stack_batched(p, sp, cfg, plan, backend)

    n_conv = len(plan.layers)
    out_specs = (P(axis),
                 [LayerStats(P(axis), P(axis), P(axis), P(), P())] * n_conv)
    # check_vma off: per-shard constants (event_block) come back replicated
    # from device-varying inputs, which strict vma tracking rejects.
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axis)),
                       out_specs=out_specs, check_vma=False)
    x, stats = fn(params, in_spikes)
    # Gather the (still batch-sharded) spike maps onto one device before
    # the head: a dot over a row-sharded operand would run one-row-per-
    # device matmuls, whose reduction order differs from the unsharded
    # (B, D) contraction in the last bit.
    x = jax.device_put(x, mesh.devices.flatten()[0])
    logits = _fc_head_batched(params, x, cfg, plan.fc_capacity)
    return (logits, stats) if collect_stats else logits


def snn_apply_dense(params: dict, in_spikes: jax.Array, cfg: CSNNConfig) -> jax.Array:
    """Frame-based spiking oracle (per sample); bit-exact vs snn_apply."""
    x = in_spikes
    for idx, spec in enumerate(cfg.layers):
        if isinstance(spec, ConvSpec):
            p = params[f"conv{idx}"]
            x = run_conv_layer_dense(x, p["w"], p["b"], cfg.v_t, pool=spec.pool)
        else:
            p = params[f"fc{idx}"]
            logits = run_fc_head(x, p["w"], p["b"])
    return logits
