"""Channel-multiplexed layer scheduling (paper Sec. V-D, Algorithm 1).

The accelerator holds membrane potentials for only a *single* channel in
MemPot and reuses that buffer across all output channels: for each
``c_out`` it simulates all T time steps, walking the input AEQ of every
``c_in`` each step, then thresholds and emits the output AEQ for
``(c_out, t)``.  Memory therefore scales with one fmap, not with
``C_out`` fmaps.

TPU adaptation: the sequential "one channel at a time" schedule is kept
(via ``lax.map`` over output-channel *blocks*) but each block is
vectorized over the lane dimension — MemPot becomes an
(H+2, W+2, block) VMEM-resident tile.  ``channel_block=1`` reproduces the
paper's schedule exactly; larger blocks are the beyond-paper throughput
knob (benchmarks/table1_parallelism.py sweeps it, the analogue of the
paper's xP parallelization sweep).

``run_conv_layer_batched_planned`` extends Algorithm 1 to a sample batch:
the channel-multiplexed schedule is unchanged, but all B samples' queues
for a given (t, c_in) are built in ONE fused compaction
(``build_aeq_batched``) and consumed by ONE kernel launch
(``event_conv_pallas_batched`` / ``apply_events_batched``), with the
self-timed early exit shared across the batch.  MemPot becomes a
(B, H+2, W+2, block) stack of tiles.  Bit-exact vs ``vmap`` over the
single-sample path (tests/test_batched.py).

Plan/execute split: the ``*_planned`` runners are the real implementation
— all resource sizing (queue depth, channel block, event block, event
parallelism) lives in a static :class:`~repro.core.plan.LayerPlan`
derived once per network by ``plan_network``.  The legacy kwargs
signatures remain as deprecation shims that derive a single-layer plan on
the fly, bit-exact vs the planned path (tests/test_plan.py).

Kernel variants (``LayerPlan.resolve_variant``: an explicitly pinned
``LayerPlan.variant`` — e.g. the measured autotuner's winner — takes
precedence; otherwise ``event_par`` + backend decide):

* ``"sequential"`` — the sequential conv unit: walk each (t, c_in)
  queue one event at a time (``apply_events*`` on the jax backend,
  ``event_conv_pallas*`` on the pallas backend).
* ``"banked-jax"`` — the memory-interlaced event-parallel unit on the
  jax backend: the MemPot stack is held **banked** (9 RAM banks, paper
  Fig. 6) for the whole time step and each interlace column's events are
  applied as one vectorized masked select (``aeq.build_bank_masks`` +
  ``event_conv.apply_banked_columns``; no sort, no per-event loop).
* ``"interlaced-pallas"`` — the queues are segment-padded
  (``aeq.segment_pad``) and fed to ``event_conv_pallas_interlaced*``,
  which applies ``event_par`` hazard-free events per
  gather->add->scatter step.
* ``"fused-handoff"`` — the fused spike-emission path (ISSUE 10): the
  layer input arrives as the producer's halo-padded centre-bank masks
  (``aeq.FusedHandoff``, built inside the upstream threshold unit or by
  ``aeq.build_fused_handoff`` from dense spikes at the network edge) and
  the conv unit applies them through static per-(bank, column) slices
  (``event_conv.apply_banked_columns_fused``) — no deinterlace, no dense
  intermediate, no second compaction pass, and no pre-shifted 81-mask
  stack (the slices alias one padded carrier).
* ``"dense-mxu"`` — the conv unit as one SAME convolution over every
  frame of the chunk at ``Precision.HIGHEST`` (``_run_chunk_dense``): no
  compaction, no channel blocks, no per-``c_in`` loop; the per-step scan
  only accumulates and thresholds.  Chosen in place of ``banked-jax`` on
  lossless float32 layers (``LayerPlan.runs_dense``).

The event variants are bit-exact vs the sequential schedule
(tests/test_interlaced.py); ``dense-mxu`` sums the same products in
another order and matches them to float rounding (tests/test_dense_mxu.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .aeq import (BatchedEventQueue, EventQueue, FusedHandoff, StreamState,
                  build_aeq_batched, build_bank_masks, build_fused_handoff,
                  fused_handoff_from_banks, segment_pad, stream_frames,
                  stream_queues)
from .event_conv import (apply_banked_columns, apply_banked_columns_fused,
                         apply_events, apply_events_batched, bank_vm, crop_vm,
                         dense_conv, pad_vm, shifted_bank_masks, tap_matrix,
                         unbank_vm)
from .plan import LayerPlan, plan_conv_layer
from .threshold import threshold_unit


class LayerStats(NamedTuple):
    """Per-layer observability used for Table III and capacity calibration."""

    in_spike_counts: jax.Array   # (T, C_in) events fed to the conv unit
    out_spike_counts: jax.Array  # (T, C_out) spikes after thresholding (pre-pool)
    in_sparsity: jax.Array       # () fraction of zeros in the input activations
    event_block: jax.Array = 0   # () chosen block_e (autotuned; perf record)
    event_par: jax.Array = 1     # () interlaced parallel width (1=sequential)


class ConvCarry(NamedTuple):
    """One conv layer's per-time-step carry over a sample batch.

    This is what Algorithm 1 keeps between time steps: the halo-padded
    MemPot stack and the m-TTFS spike-indicator latches.  Extracting it
    lets execution stop at any chunk boundary and resume bit-exactly
    (``run_conv_layer_batched_chunk``) — the basis of continuous batching
    in the serving engine.  Stored channel-flat (C_out last); the block
    split/merge happens inside the chunk runner.
    """

    vm: jax.Array     # (B, H+2hh, W+2hw, C_out) membrane potentials,
                      # halo-padded by the plan geometry (hh=kh//2, hw=kw//2)
    fired: jax.Array  # (B, H, W, C_out) spike-indicator bits


def init_conv_carry(lp: LayerPlan, batch: int, vm_dtype=None) -> ConvCarry:
    """Fresh (all-zero) carry for one conv layer and ``batch`` samples."""
    h, w = lp.in_hw
    hh, hw = lp.geometry.halo
    dt = lp.vm_dtype if vm_dtype is None else vm_dtype
    return ConvCarry(
        vm=jnp.zeros((batch, h + 2 * hh, w + 2 * hw, lp.c_out), dt),
        fired=jnp.zeros((batch, h, w, lp.c_out), jnp.bool_))


def run_conv_layer(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    *,
    capacity: int,
    pool: Optional[int] = None,
    channel_block: int = 1,
    sat_bits: Optional[int] = None,
    vm_dtype=jnp.float32,
    backend: str = "jax",
) -> tuple[jax.Array, LayerStats]:
    """Deprecated kwargs shim over :func:`run_conv_layer_planned`.

    Derives a single-layer :class:`~repro.core.plan.LayerPlan` from the
    loose knobs and executes it — bit-exact vs the planned path by
    construction (the plan only rounds capacity the way this function
    always did).  New code should build plans via ``plan_network``.
    """
    t_steps, h, w, c_in = spikes_in.shape
    lp = plan_conv_layer(0, "conv", (h, w), c_in, kernels.shape[-1],
                         capacity=capacity, pool=pool,
                         channel_block=channel_block, sat_bits=sat_bits)
    return run_conv_layer_planned(spikes_in, kernels, bias, v_t, lp,
                                  backend=backend, vm_dtype=vm_dtype)


def run_conv_layer_planned(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    lp: LayerPlan,
    *,
    backend: str = "jax",
    vm_dtype=None,
) -> tuple[jax.Array, LayerStats]:
    """Run one spiking conv layer for all T steps, Algorithm-1 style.

    spikes_in: (T, H, W, C_in) bool — the previous layer's output spikes.
    kernels:   (kh, kw, C_in, C_out) — *unrotated* trained weights; the
               window must match ``lp.geometry`` (3x3 in the paper).
    bias:      (C_out,) — integrated once per time step by the threshold unit.
    lp:        the layer's static resource plan (queue depth, channel
               block, event block, membrane tile — see core/plan.py).
    backend: "jax" (pure scan reference) or "pallas" (the event_conv TPU
        kernel in interpret mode — the production compute path).

    Returns (spikes_out (T, H', W', C_out) bool, LayerStats).
    """
    t_steps, h, w, c_in = spikes_in.shape
    c_out = kernels.shape[-1]
    channel_block = lp.channel_block
    vm_dtype = lp.vm_dtype if vm_dtype is None else vm_dtype
    variant = lp.resolve_variant(backend)
    if variant == "dense-mxu":  # a single sample is a batch of one
        spikes_out, _, stats = _run_chunk_dense(
            spikes_in[None], kernels, bias, v_t, lp,
            init_conv_carry(lp, 1, vm_dtype), vm_dtype=vm_dtype)
        return spikes_out[0], stats._replace(
            in_spike_counts=stats.in_spike_counts[0],
            out_spike_counts=stats.out_spike_counts[0],
            in_sparsity=stats.in_sparsity[0])
    banked = variant == "banked-jax"
    fused = variant == "fused-handoff"
    geom = lp.geometry
    hh, hw_ = geom.halo
    fmaps = spikes_in.transpose(0, 3, 1, 2)  # (T, C_in, H, W)
    if fused:
        # fused spike-emission path: the padded centre-bank carrier IS the
        # consumable representation — no pre-shifted mask stack at all
        with jax.named_scope("handoff"):
            ho = build_fused_handoff(spikes_in[None], lp.capacity, geom)
        smasks = ho.masks[:, :, 0]  # (T, C_in, n_banks, HB+2, WB+2)
        counts = ho.count[:, 0]     # (T, C_in)
    elif banked:
        # interlaced event-parallel path: sort-free bank-mask compaction,
        # write masks pre-shifted once and reused by every channel block
        with jax.named_scope("compact"):
            events = build_bank_masks(fmaps, lp.capacity, geom)
            # (T, C_in, n_banks cols, n_banks banks, hb, wb)
            smasks = shifted_bank_masks(events.masks, geom)
        counts = events.count
    else:
        with jax.named_scope("compact"):
            queues = build_aeq_batched(fmaps, lp.capacity, geometry=geom)
            if lp.event_par > 1:
                queues = segment_pad(queues, lp.event_par, geom)
        counts = queues.count

    def run_block(kernel_block: jax.Array, bias_block: jax.Array) -> jax.Array:
        # kernel_block: (kh, kw, C_in, B); bias_block: (B,)
        block = kernel_block.shape[-1]
        vm0 = pad_vm(jnp.zeros((h, w, block), vm_dtype), geom)  # MemPot, reused (Alg. 1 l.2)
        fired0 = jnp.zeros((h, w, block), jnp.bool_)
        if banked or fused:  # (C_in, cols, banks, block) tap routing, hoisted
            taps = jnp.moveaxis(tap_matrix(kernel_block), 2, 0).astype(vm_dtype)

        def apply_all_cins(vm, t):
            if banked or fused:
                if fused:
                    def apply(vb, m, tp):
                        return apply_banked_columns_fused(vb, m, tp, geom)
                else:
                    apply = apply_banked_columns
                vb = bank_vm(vm, geom)
                vb = jax.lax.fori_loop(
                    0, c_in,
                    lambda ci, vb: apply(vb, smasks[t, ci], taps[ci]),
                    vb)
                return unbank_vm(vb, h + 2 * hh, w + 2 * hw_, geom)

            def per_cin(ci, vm):
                if variant == "interlaced-pallas":
                    from repro.kernels.event_conv.kernel import \
                        event_conv_pallas_interlaced
                    return event_conv_pallas_interlaced(
                        vm, queues.coords[t, ci], queues.valid[t, ci],
                        kernel_block[:, :, ci, :].astype(vm.dtype),
                        block_e=lp.block_e, event_par=lp.event_par)
                if backend == "pallas":
                    from repro.kernels.event_conv.kernel import \
                        event_conv_pallas
                    return event_conv_pallas(
                        vm, queues.coords[t, ci], queues.valid[t, ci],
                        kernel_block[:, :, ci, :].astype(vm.dtype),
                        block_e=lp.block_e)
                q = EventQueue(queues.coords[t, ci], queues.valid[t, ci],
                               queues.count[t, ci])
                return apply_events(vm, q, kernel_block[:, :, ci, :])

            return jax.lax.fori_loop(0, c_in, per_cin, vm)

        def time_step(carry, t):
            vm, fired = carry
            with jax.named_scope("conv_unit"):
                vm = apply_all_cins(vm, t)

            def thresh_one(v, f, b):
                r = threshold_unit(v, b, v_t, f, pool=None, sat_bits=lp.sat_bits)
                return r.v_m, r.fired, r.spikes

            with jax.named_scope("threshold"):
                inner = crop_vm(vm, geom)
                v_new, fired, spk = jax.vmap(
                    thresh_one, in_axes=(2, 2, 0), out_axes=2)(
                        inner, fired, bias_block)
                vm = vm.at[hh:h + hh, hw_:w + hw_, :].set(v_new)
            return (vm, fired), spk

        (_, _), spikes = jax.lax.scan(time_step, (vm0, fired0), jnp.arange(t_steps))
        return spikes  # (T, H, W, B)

    kh, kw = kernels.shape[:2]
    kb = kernels.reshape(kh, kw, c_in, c_out // channel_block, channel_block)
    kb = jnp.moveaxis(kb, 3, 0)              # (n_blocks, kh, kw, C_in, B)
    bb = bias.reshape(c_out // channel_block, channel_block)
    spikes_blocks = jax.lax.map(lambda kb_bb: run_block(*kb_bb), (kb, bb))
    spikes_out = jnp.moveaxis(spikes_blocks, 0, 3)  # (T, H, W, n_blocks, B)
    spikes_out = spikes_out.reshape(t_steps, h, w, c_out)

    stats = LayerStats(
        in_spike_counts=counts,
        out_spike_counts=jnp.sum(spikes_out, axis=(1, 2)).astype(jnp.int32),
        in_sparsity=1.0 - jnp.mean(spikes_in.astype(jnp.float32)),
        event_block=jnp.asarray(lp.block_e, jnp.int32),
        event_par=jnp.asarray(lp.event_par, jnp.int32),
    )
    if lp.pool is not None:
        with jax.named_scope("threshold"):
            return _pool_all(spikes_out, lp.pool), stats
    return spikes_out, stats


def _pool_all(spikes: jax.Array, window: int) -> jax.Array:
    """OR-max-pool (..., H, W, C) binary maps over non-overlapping windows."""
    *lead, h, w, c = spikes.shape
    ph, pw = -h % window, -w % window
    pads = [(0, 0)] * len(lead) + [(0, ph), (0, pw), (0, 0)]
    s = jnp.pad(spikes.astype(bool), pads)
    hh, ww = s.shape[-3:-1]
    s = s.reshape(*lead, hh // window, window, ww // window, window, c)
    return jnp.any(s, axis=(-4, -2))


def run_conv_layer_dense(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    *,
    pool: Optional[int] = None,
    vm_dtype=jnp.float32,
) -> jax.Array:
    """Frame-based oracle for run_conv_layer (sliding-window conv; SIES-style).

    Used (a) as the correctness oracle in tests and (b) as the dense
    baseline the paper compares against.
    """
    t_steps, h, w, c_in = spikes_in.shape
    c_out = kernels.shape[-1]

    def step(carry, x_t):
        vm, fired = carry
        x = x_t.astype(vm_dtype)[None]  # (1, H, W, C_in)
        u = jax.lax.conv_general_dilated(
            x, kernels.astype(vm_dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)[0]
        vm = vm + u + bias.astype(vm_dtype)
        spikes = (vm > jnp.asarray(v_t, vm_dtype)) | fired
        return (vm, spikes), spikes

    vm0 = jnp.zeros((h, w, c_out), vm_dtype)
    fired0 = jnp.zeros((h, w, c_out), jnp.bool_)
    (_, _), spikes = jax.lax.scan(step, (vm0, fired0), spikes_in)
    return _pool_all(spikes, pool) if pool is not None else spikes


def run_conv_layer_batched(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    *,
    capacity: int,
    pool: Optional[int] = None,
    channel_block: int = 1,
    sat_bits: Optional[int] = None,
    vm_dtype=jnp.float32,
    backend: str = "jax",
    event_block: Optional[int] = None,
) -> tuple[jax.Array, LayerStats]:
    """Deprecated kwargs shim over :func:`run_conv_layer_batched_planned`.

    Derives a single-layer plan from the loose knobs (``event_block=None``
    autotunes the event block) and executes it — bit-exact by construction.
    New code should build plans via ``plan_network``.
    """
    b_sz, t_steps, h, w, c_in = spikes_in.shape
    lp = plan_conv_layer(0, "conv", (h, w), c_in, kernels.shape[-1],
                         capacity=capacity, pool=pool,
                         channel_block=channel_block, block_e=event_block,
                         sat_bits=sat_bits)
    return run_conv_layer_batched_planned(spikes_in, kernels, bias, v_t, lp,
                                          backend=backend, vm_dtype=vm_dtype)


def run_conv_layer_batched_planned(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    lp: LayerPlan,
    *,
    backend: str = "jax",
    vm_dtype=None,
) -> tuple[jax.Array, LayerStats]:
    """Algorithm 1 over a whole sample batch with amortized event handling.

    spikes_in: (B, T, H, W, C_in) bool — batch of previous-layer spikes.
    Remaining arguments match ``run_conv_layer_planned``.  One fused
    compaction builds every (t, b, c_in) queue; each (t, c_in) step then
    feeds all B queues to one batched conv-unit invocation (a 2-D-grid
    Pallas call for ``backend="pallas"``, a batch-vectorized event loop
    with shared early exit for ``backend="jax"``).

    Returns (spikes_out (B, T, H', W', C_out) bool, LayerStats with a
    leading batch dim: in_spike_counts (B, T, C_in), out_spike_counts
    (B, T, C_out), in_sparsity (B,)).  Bit-exact vs
    ``jax.vmap(run_conv_layer_planned)`` — the paper's per-sample schedule
    is preserved; only the launch structure is batched.  Implemented as
    one whole-T call of :func:`run_conv_layer_batched_chunk` from a fresh
    carry.
    """
    carry = init_conv_carry(lp, spikes_in.shape[0], vm_dtype=vm_dtype)
    spikes_out, _, stats = run_conv_layer_batched_chunk(
        spikes_in, kernels, bias, v_t, lp, carry, backend=backend,
        vm_dtype=vm_dtype)
    return spikes_out, stats


def _split_blocks(arr: jax.Array, n_blocks: int, cb: int) -> jax.Array:
    """(B, ..., C_out) -> (n_blocks, B, ..., Cb); channel c maps to block
    c // Cb, lane c % Cb — the same contiguous split as the kernel reshape."""
    out = arr.reshape(arr.shape[:-1] + (n_blocks, cb))
    return jnp.moveaxis(out, -2, 0)


def _merge_blocks(arr: jax.Array) -> jax.Array:
    """Inverse of ``_split_blocks``."""
    out = jnp.moveaxis(arr, 0, -2)
    return out.reshape(out.shape[:-2] + (-1,))


def run_conv_layer_batched_chunk(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    lp: LayerPlan,
    carry: ConvCarry,
    *,
    backend: str = "jax",
    vm_dtype=None,
) -> tuple[jax.Array, ConvCarry, LayerStats]:
    """Step one conv layer through a CHUNK of time steps from ``carry``.

    spikes_in: (B, t_chunk, H, W, C_in) bool — any chunk length >= 1; OR
               an :class:`~repro.core.aeq.FusedHandoff` carrier when the
               layer is pinned to the ``"fused-handoff"`` variant and the
               producer already emitted the compacted representation
               (``csnn.snn_step_chunk`` threads it between layers).
    carry:     the layer's :class:`ConvCarry` at the chunk start (a fresh
               ``init_conv_carry`` at t=0, the previous chunk's result
               otherwise).

    Returns (spikes_out (B, t_chunk, H', W', C_out) bool, new carry,
    chunk LayerStats).  Per time step the computation is identical to the
    monolithic path — only the scan is cut at the chunk boundary — so
    chaining chunks over a T-step input is bit-exact vs one whole-T call
    (tests/test_chunked.py).  This is the device-side half of the serving
    engine's slot-level refill: the engine holds one shared carry batch
    and resets individual rows as slots retire and admit.
    """
    variant = lp.resolve_variant(backend)
    if variant == "fused-handoff":
        if isinstance(spikes_in, FusedHandoff):
            ho = spikes_in
        else:
            # network edge (or unfused producer): build the carrier here —
            # same cost class as the banked compaction, still no
            # pre-shifted mask stack downstream
            with jax.named_scope("handoff"):
                ho = build_fused_handoff(spikes_in, lp.capacity, lp.geometry)
        t_steps, c_in, b_sz = ho.masks.shape[:3]
        h, w = lp.in_hw
        # sparsity from the pre-truncation counts: 0/1 sums in f32 are
        # exact integers < 2^24, so this is bit-identical to
        # 1 - mean(dense spikes) without ever materializing the frames
        total = jnp.sum(ho.count.astype(jnp.float32), axis=(0, 2))
        sparsity = 1.0 - total / float(t_steps * h * w * c_in)
        # ho.masks is (t, C_in, B, ...) — already the scan's xs layout
        return _run_chunk_from_events(
            None, ho.masks, ho.count, sparsity,
            (b_sz, t_steps, h, w, c_in), kernels, bias, v_t, lp, carry,
            variant=variant, backend=backend, vm_dtype=vm_dtype)
    if variant == "dense-mxu":
        return _run_chunk_dense(spikes_in, kernels, bias, v_t, lp, carry,
                                vm_dtype=vm_dtype)
    b_sz, t_steps, h, w, c_in = spikes_in.shape
    banked = variant == "banked-jax"
    # (B, t, H, W, C_in) -> per-(t, b, c_in) event sets, built in one pass
    fmaps = spikes_in.transpose(1, 0, 4, 2, 3)  # (t, B, C_in, H, W)
    if banked:
        # interlaced event-parallel path: compact straight into the 9
        # membrane RAM banks (sort-free) and pre-shift the write masks
        # once; every (t, c_in, channel-block) step below then applies a
        # whole hazard-free column per vectorized select.  The pre-shifted
        # stack is 81/9 x the bank masks and lives for the whole chunk —
        # the chunk length (plan.t_chunk) is the knob that bounds it; the
        # amortization across channel blocks AND time steps is what pays
        # for the banked path (recomputing per step would cost more than
        # the conv work it saves on wide-C_in layers).
        with jax.named_scope("compact"):
            events = build_bank_masks(fmaps, lp.capacity, lp.geometry)
            # (t, B, C_in, cols, banks, hb, wb) -> (t, C_in, B, ...) for
            # scan + fori
            queues = None
            smasks = jnp.swapaxes(
                shifted_bank_masks(events.masks, lp.geometry), 1, 2)
        counts = events.count
    else:
        with jax.named_scope("compact"):
            queues = build_aeq_batched(fmaps, lp.capacity,
                                       geometry=lp.geometry)
            if lp.event_par > 1:
                queues = segment_pad(queues, lp.event_par, lp.geometry)
        smasks, counts = None, queues.count
    sparsity = 1.0 - jnp.mean(spikes_in.astype(jnp.float32),
                              axis=(1, 2, 3, 4))
    return _run_chunk_from_events(
        queues, smasks, counts, sparsity, (b_sz, t_steps, h, w, c_in),
        kernels, bias, v_t, lp, carry, variant=variant, backend=backend,
        vm_dtype=vm_dtype)


def run_conv_layer_batched_chunk_streamed(
    stream: StreamState,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    lp: LayerPlan,
    carry: ConvCarry,
    *,
    backend: str = "jax",
    vm_dtype=None,
) -> tuple[jax.Array, ConvCarry, LayerStats]:
    """Chunk runner over PRE-INGESTED input events instead of dense frames.

    stream: :class:`~repro.core.aeq.StreamState` with banks
    (B, t_chunk, C_in, n_banks, HB, WB) — raw DVS events appended incrementally
    by ``aeq.append_events*``.  The conv-unit schedule, thresholding and
    carry handling are byte-for-byte the ones of
    :func:`run_conv_layer_batched_chunk`; only the queue construction
    differs — ``aeq.stream_queues`` finalizes the banks sort-free (the
    sequential/pallas variants; ``segment_pad`` applies on top exactly as
    in the binned path), and the banked event-parallel variant compacts
    the streamed occupancy with the same ``build_bank_masks`` call the
    binned path uses.  ``lp.resolve_stream_finalize() == "sort"`` swaps
    the rank-based finalization for the binned compaction over the dense
    bank view (``build_aeq_batched``) — bit-exact by the
    streaming-equivalence theorem, and the variant the measured autotuner
    (and the fmap-size default) picks at small fmaps where the fused sort
    beats the rank cumsums' constant factor.  The ``"fused-handoff"``
    variant compacts the streamed banks straight into the padded carrier
    (``aeq.fused_handoff_from_banks``) — no dense frame view at all.
    Bit-exact vs binning the same events into frames and calling the
    dense-chunk runner either way (tests/test_streaming.py).
    """
    h, w = lp.in_hw
    b_sz, t_steps, c_in = stream.banks.shape[:3]
    variant = lp.resolve_variant(backend)
    banked = variant == "banked-jax"
    if variant == "fused-handoff":
        with jax.named_scope("handoff"):
            ho = fused_handoff_from_banks(stream.banks, lp.capacity, (h, w),
                                          lp.geometry)
        total = jnp.sum(ho.count.astype(jnp.float32), axis=(0, 2))
        sparsity = 1.0 - total / float(t_steps * h * w * c_in)
        return _run_chunk_from_events(
            None, ho.masks, ho.count, sparsity,
            (b_sz, t_steps, h, w, c_in), kernels, bias, v_t, lp, carry,
            variant=variant, backend=backend, vm_dtype=vm_dtype)
    # dense view only where the binned path itself is dense (sparsity
    # stat; bank-mask/sort compaction input) — a reshape/transpose, no sort
    frames = stream_frames(stream, (h, w), lp.geometry)  # (B, t, C_in, H, W)
    if variant == "dense-mxu":  # only when pinned: see LayerPlan.runs_dense
        return _run_chunk_dense(frames.transpose(0, 1, 3, 4, 2), kernels,
                                bias, v_t, lp, carry, vm_dtype=vm_dtype)
    if banked:
        with jax.named_scope("compact"):
            events = build_bank_masks(frames.transpose(1, 0, 2, 3, 4),
                                      lp.capacity, lp.geometry)
            queues = None
            smasks = jnp.swapaxes(
                shifted_bank_masks(events.masks, lp.geometry), 1, 2)
        counts = events.count
    else:
        with jax.named_scope("compact"):
            if lp.resolve_stream_finalize() == "sort":
                # binned finalization: fused sort over the dense bank
                # view, already in the (t, B, C_in) lead layout the
                # launches index
                queues = build_aeq_batched(frames.transpose(1, 0, 2, 3, 4),
                                           lp.capacity, geometry=lp.geometry)
            else:
                queues = stream_queues(stream, lp.capacity, (h, w),
                                       geometry=lp.geometry)
                # (B, t, C_in, ...) -> (t, B, C_in, ...): the layout the
                # per-(t, c_in) kernel launches below index
                queues = BatchedEventQueue(*(None if x is None
                                             else jnp.swapaxes(x, 0, 1)
                                             for x in queues))
            if lp.event_par > 1:
                queues = segment_pad(queues, lp.event_par, lp.geometry)
        smasks, counts = None, queues.count
    sparsity = 1.0 - jnp.mean(frames.astype(jnp.float32), axis=(1, 2, 3, 4))
    return _run_chunk_from_events(
        queues, smasks, counts, sparsity, (b_sz, t_steps, h, w, c_in),
        kernels, bias, v_t, lp, carry, variant=variant, backend=backend,
        vm_dtype=vm_dtype)


def _run_chunk_dense(
    spikes_in: jax.Array,
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    lp: LayerPlan,
    carry: ConvCarry,
    *,
    vm_dtype=None,
) -> tuple[jax.Array, ConvCarry, LayerStats]:
    """Chunk body of the ``"dense-mxu"`` variant, with the signature and
    results of :func:`run_conv_layer_batched_chunk`.

    The conv unit's input for the whole chunk is on hand and does not
    depend on the layer's state, so it runs as ONE SAME convolution over
    all B·t_chunk frames, every C_in into every C_out, at
    ``Precision.HIGHEST`` (float32 on the MXU).  The scan over t only
    accumulates and thresholds, ``V += u_t`` then bias, ``> v_t`` and the
    m-TTFS latch, over every channel at once — the order of the dense
    oracle (``run_conv_layer_dense``).  The carry keeps its halo-padded
    layout; the halo is never written.  ``in_spike_counts`` are the
    spike sums over (H, W), equal to the event path's queue counts.
    """
    b_sz, t_steps, h, w, c_in = spikes_in.shape
    c_out = kernels.shape[-1]
    vm_dtype = lp.vm_dtype if vm_dtype is None else vm_dtype
    hh, hw_ = lp.geometry.halo
    with jax.named_scope("conv_unit"):
        x = jnp.swapaxes(spikes_in, 0, 1).reshape(t_steps * b_sz, h, w, c_in)
        u = jax.lax.conv_general_dilated(
            x.astype(vm_dtype), kernels.astype(vm_dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        # (W, C_out) flattened onto the minor axis: the per-step
        # elementwise work stays lane-dense whatever C_out is
        u = u.reshape(t_steps, b_sz, h, w * c_out)

    bias_wc = jnp.tile(bias.astype(vm_dtype), w)  # channel c at w*C_out + c

    def time_step(c, u_t):
        vm, fired = c
        with jax.named_scope("threshold"):
            r = threshold_unit(vm + u_t, bias_wc, v_t, fired, pool=None,
                               sat_bits=lp.sat_bits)
        return (r.v_m, r.fired), r.spikes

    vm0 = carry.vm.astype(vm_dtype)
    inner = vm0[:, hh:h + hh, hw_:w + hw_, :].reshape(b_sz, h, w * c_out)
    fired0 = carry.fired.reshape(b_sz, h, w * c_out)
    (vm, fired), spikes = jax.lax.scan(time_step, (inner, fired0), u)
    new_carry = ConvCarry(
        vm=vm0.at[:, hh:h + hh, hw_:w + hw_, :].set(
            vm.reshape(b_sz, h, w, c_out)),
        fired=fired.reshape(b_sz, h, w, c_out))
    spikes_out = jnp.swapaxes(spikes, 0, 1).reshape(b_sz, t_steps, h, w,
                                                    c_out)
    stats = LayerStats(
        in_spike_counts=jnp.sum(spikes_in, axis=(2, 3), dtype=jnp.int32),
        out_spike_counts=jnp.sum(spikes_out, axis=(2, 3)).astype(jnp.int32),
        in_sparsity=1.0 - jnp.mean(spikes_in.astype(jnp.float32),
                                   axis=(1, 2, 3, 4)),
        event_block=jnp.asarray(lp.block_e, jnp.int32),
        event_par=jnp.asarray(lp.event_par, jnp.int32),
    )
    if lp.pool is not None:
        with jax.named_scope("threshold"):
            return _pool_all(spikes_out, lp.pool), new_carry, stats
    return spikes_out, new_carry, stats


def _run_chunk_from_events(
    queues: Optional[BatchedEventQueue],
    smasks: Optional[jax.Array],
    counts: jax.Array,
    sparsity: jax.Array,
    shape: tuple[int, int, int, int, int],
    kernels: jax.Array,
    bias: jax.Array,
    v_t,
    lp: LayerPlan,
    carry: ConvCarry,
    *,
    variant: str,
    backend: str,
    vm_dtype=None,
) -> tuple[jax.Array, ConvCarry, LayerStats]:
    """Shared chunk body: consume pre-built per-(t, b, c_in) event sets
    (queues for the sequential/pallas variants, pre-shifted bank masks for
    the banked variant, the padded fused-handoff carrier for the fused
    variant — both ride the ``smasks`` slot) — the part of the chunk
    runner that is identical whether the events came from dense frames,
    the streaming ingestion path, or an upstream fused emission."""
    banked = variant == "banked-jax"
    fused = variant == "fused-handoff"
    b_sz, t_steps, h, w, c_in = shape
    c_out = kernels.shape[-1]
    channel_block = lp.channel_block
    vm_dtype = lp.vm_dtype if vm_dtype is None else vm_dtype
    block_e = lp.block_e
    geom = lp.geometry
    hh, hw_ = geom.halo

    def run_block(kernel_block, bias_block, vm0, fired0):
        # kernel_block: (kh, kw, C_in, Cb); bias_block: (Cb,)
        # vm0: (B, H+2hh, W+2hw, Cb); fired0: (B, H, W, Cb)
        if banked or fused:  # (C_in, cols, banks, Cb) tap routing, hoisted
            taps = jnp.moveaxis(tap_matrix(kernel_block), 2, 0).astype(vm_dtype)

        def apply_all_cins(vm, smasks_t, t):
            if banked or fused:
                if fused:
                    def apply(vb, m, tp):
                        return apply_banked_columns_fused(vb, m, tp, geom)
                else:
                    apply = apply_banked_columns
                vb = bank_vm(vm, geom)  # (B, n_banks, hb, wb, Cb)
                vb = jax.lax.fori_loop(
                    0, c_in,
                    lambda ci, vb: apply(vb, smasks_t[ci], taps[ci]),
                    vb)
                return unbank_vm(vb, h + 2 * hh, w + 2 * hw_, geom)

            def per_cin(ci, vm):
                coords = queues.coords[t, :, ci]   # (B, cap, 2)
                valid = queues.valid[t, :, ci]     # (B, cap)
                k_ci = kernel_block[:, :, ci, :]
                if variant == "interlaced-pallas":
                    from repro.kernels.event_conv.kernel import (
                        event_conv_pallas_interlaced_batched)
                    return event_conv_pallas_interlaced_batched(
                        vm, coords, valid, k_ci.astype(vm.dtype),
                        block_e=block_e, event_par=lp.event_par)
                if backend == "pallas":
                    from repro.kernels.event_conv.kernel import (
                        event_conv_pallas_batched)
                    return event_conv_pallas_batched(
                        vm, coords, valid, k_ci.astype(vm.dtype),
                        block_e=block_e)
                return apply_events_batched(
                    vm, coords, valid, queues.count[t, :, ci], k_ci,
                    block=block_e)

            return jax.lax.fori_loop(0, c_in, per_cin, vm)

        def time_step(carry, xs):
            smasks_t, t = xs
            vm, fired = carry
            with jax.named_scope("conv_unit"):
                vm = apply_all_cins(vm, smasks_t, t)

            def thresh_one(v, f, b):
                r = threshold_unit(v, b, v_t, f, pool=None, sat_bits=lp.sat_bits)
                return r.v_m, r.fired, r.spikes

            with jax.named_scope("threshold"):
                inner = vm[:, hh:h + hh, hw_:w + hw_, :]
                per_channel = jax.vmap(thresh_one, in_axes=(2, 2, 0),
                                       out_axes=2)
                v_new, fired, spk = jax.vmap(
                    per_channel, in_axes=(0, 0, None))(
                        inner, fired, bias_block)
                vm = vm.at[:, hh:h + hh, hw_:w + hw_, :].set(v_new)
            return (vm, fired), spk

        xs = (smasks if (banked or fused)
              else jnp.zeros((t_steps, 0), jnp.bool_),
              jnp.arange(t_steps))
        (vm, fired), spikes = jax.lax.scan(time_step, (vm0, fired0), xs)
        return spikes, vm, fired  # spikes: (t, B, H, W, Cb)

    n_blocks = c_out // channel_block
    kh, kw = kernels.shape[:2]
    kb = kernels.reshape(kh, kw, c_in, n_blocks, channel_block)
    kb = jnp.moveaxis(kb, 3, 0)              # (n_blocks, kh, kw, C_in, Cb)
    bb = bias.reshape(n_blocks, channel_block)
    vm_b = _split_blocks(carry.vm.astype(vm_dtype), n_blocks, channel_block)
    fired_b = _split_blocks(carry.fired, n_blocks, channel_block)
    spikes_blocks, vm_out, fired_out = jax.lax.map(
        lambda a: run_block(*a), (kb, bb, vm_b, fired_b))
    new_carry = ConvCarry(vm=_merge_blocks(vm_out),
                          fired=_merge_blocks(fired_out))
    spikes_out = jnp.moveaxis(spikes_blocks, 0, 4)  # (t, B, H, W, n_blocks, Cb)
    spikes_out = spikes_out.reshape(t_steps, b_sz, h, w, c_out)
    spikes_out = jnp.swapaxes(spikes_out, 0, 1)     # (B, t, H, W, C_out)

    stats = LayerStats(
        in_spike_counts=jnp.swapaxes(counts, 0, 1),  # (B, t, C_in)
        out_spike_counts=jnp.sum(spikes_out, axis=(2, 3)).astype(jnp.int32),
        in_sparsity=sparsity,
        event_block=jnp.asarray(lp.block_e, jnp.int32),
        event_par=jnp.asarray(lp.event_par, jnp.int32),
    )
    if lp.pool is not None:
        with jax.named_scope("threshold"):
            return _pool_all(spikes_out, lp.pool), new_carry, stats
    return spikes_out, new_carry, stats


def head_dot(drive: jax.Array, weights: jax.Array) -> jax.Array:
    """The classification unit's contraction, drive @ weights, at full
    float32 precision.  Named explicitly because a TPU runs a float32 dot
    at the default precision as bf16 passes, which would round the
    trained weights; every head (served, sparse, reference) goes through
    here."""
    return jnp.dot(drive, weights, precision=jax.lax.Precision.HIGHEST)


def run_fc_head(spikes_in: jax.Array, weights: jax.Array, bias: jax.Array,
                capacity: Optional[int] = None) -> jax.Array:
    """Classification unit (paper Sec. V-A): integrate-only FC readout.

    spikes_in: (T, ...) binary; weights: (D, n_classes).  The output
    neurons integrate weighted spikes plus bias every step and are never
    thresholded; the class is the argmax of the final membrane potential.
    ``capacity`` opts the accumulated drive into the event-driven sparse
    head (``sparse_ffn.event_readout``: top-``capacity`` AEQ compaction +
    scatter-back) — bit-exact vs the dense contraction whenever the queue
    covers every nonzero drive entry.
    """
    t_steps = spikes_in.shape[0]
    flat = spikes_in.reshape(t_steps, -1).astype(weights.dtype)
    drive = flat.sum(0)
    if capacity is not None:
        from .sparse_ffn import event_readout
        return event_readout(drive, weights,
                             capacity=capacity) + t_steps * bias
    return head_dot(drive, weights) + t_steps * bias


def run_fc_head_batched(spikes_in: jax.Array, weights: jax.Array,
                        bias: jax.Array,
                        capacity: Optional[int] = None) -> jax.Array:
    """Classification unit over a batch: (B, T, ...) -> (B, n_classes).

    One batched matmul replaces B vector-matrix products; numerically it
    is the same dot_general ``vmap(run_fc_head)`` lowers to.  ``capacity``
    opts into the event-driven sparse head exactly as in
    :func:`run_fc_head`.
    """
    b_sz, t_steps = spikes_in.shape[:2]
    flat = spikes_in.reshape(b_sz, t_steps, -1).astype(weights.dtype)
    drive = flat.sum(1)
    if capacity is not None:
        from .sparse_ffn import event_readout
        return event_readout(drive, weights,
                             capacity=capacity) + t_steps * bias
    return head_dot(drive, weights) + t_steps * bias
