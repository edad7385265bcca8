"""Plan/execute split for the event pipeline (paper Sec. IV-V design flow).

The accelerator works because every resource is sized *per layer at design
time* — queue depths, PE tiling and interlaced membrane RAMs are static
while the spike stream is dynamic.  This module is the TPU analogue of
that design step: ``plan_network`` walks a ``CSNNConfig`` once and derives
a frozen :class:`LayerPlan` per conv layer (padded queue capacity, channel
block, event block, membrane-tile shape), plus network-wide serving knobs
(batch tile, batch mesh axis) on the :class:`NetworkPlan`.  The runtime
(``scheduler.run_conv_layer_planned`` / ``csnn.snn_apply*``) then only
executes plans; it never sizes anything.

Sizing rules (all static, all pure functions of geometry + calibration):

* **capacity** — the effective AEQ depth is ``min(pad64(requested), H·W)``:
  padded to a 64-multiple so event blocks tile evenly (the extra slots
  carry ``valid=False``), but never deeper than the feature map itself —
  a queue can hold at most H·W events, so capping there drops nothing and
  is what removes the padded-slot waste of a single shared capacity.
  When per-layer spike-count ``stats`` are given, the requested depth
  comes from ``aeq.calibrate_capacity`` per layer (BRAM sizing analogue).
* **channel_block** — snapped to a divisor of C_out (``snap_divisor``).
* **block_e** — autotuned from the capacity and the VMEM budget
  (``kernels.event_conv.ops.autotune_block_e``) unless pinned.
* **vm_tile** — the (H+2·(kh//2), W+2·(kw//2), channel_block)
  halo-padded MemPot tile held VMEM-resident per conv-unit launch
  (H+2, W+2 for the paper's 3x3 window).
* **event_par** — the memory-interlaced event-parallel width (paper
  Fig. 6 cashed in): 1 keeps the sequential one-event-at-a-time conv
  unit; > 1 selects the interlace-aware kernel variants, which apply
  same-column (hazard-free) events in parallel — the banked-select jax
  path and the ``event_conv_pallas_interlaced*`` kernels — or, on
  lossless float32 layers of the jax backend, the dense MXU conv
  (``"dense-mxu"``, :meth:`LayerPlan.runs_dense`).  ``None``
  autotunes it next to ``block_e`` (``autotune_event_par``: snapped to a
  power of two, VMEM-aware, floored to 1 when queues are too shallow to
  pay for parallelism).  When > 1, ``block_e`` is additionally snapped to
  a multiple of ``event_par`` dividing the segment-padded
  :attr:`LayerPlan.queue_depth`.

Every rule only ever *lowers* the effective queue depth to the point
where nothing can be dropped (or keeps the requested truncation depth),
so planned execution is bit-exact vs the legacy shared-capacity kwargs —
the deprecation shims in scheduler.py/csnn.py rely on this; the
interlaced event variants are bit-exact vs the sequential schedule by the
interlace disjointness argument (tests/test_interlaced.py), and the dense
MXU conv matches them to float rounding (tests/test_dense_mxu.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from repro.kernels.event_conv.ops import (autotune_block_e,
                                          autotune_event_par,
                                          snap_block_e_for_par,
                                          snap_divisor)

from .aeq import calibrate_capacity, interlaced_capacity
from .geometry import GEOM_3X3, ConvGeometry

_VM_DTYPES = {None: "float32", 8: "int8", 16: "int16"}

# Kernel variants a LayerPlan can pin (None = resolve from event_par +
# backend, the legacy rule).  "sequential" walks the queue one event at a
# time (jax loop, or the sequential Pallas kernel on the pallas backend);
# "banked-jax" holds the MemPot stack in the 9 interlace banks and applies
# whole hazard-free columns per vectorized select; "interlaced-pallas"
# feeds segment-padded queues to event_conv_pallas_interlaced*;
# "fused-handoff" consumes the producer's fused spike emission directly —
# the layer input arrives as halo-padded centre-bank occupancy masks
# (aeq.FusedHandoff, built in the upstream threshold unit) and the conv
# applies them through static per-(bank, column) slices, skipping the
# deinterlace -> dense -> recompact round trip entirely (ISSUE 10).  All
# four are bit-exact — the variant is a pure perf knob, which is what
# lets the measured autotuner (repro.tune) pick per layer.
# "fused-handoff" only runs when pinned: resolve_variant never
# auto-selects it, because it changes the *inter*-layer dataflow.
# "dense-mxu" runs the conv unit as one SAME convolution per chunk on the
# MXU (no compaction, no per-event work); resolve_variant picks it in
# place of "banked-jax" where that variant would do dense-cost work and
# the dense result is the same one (LayerPlan.runs_dense).  It sums the
# same float32 products in another order, so it matches the event
# variants to float rounding, not bit for bit.
KERNEL_VARIANTS = ("sequential", "banked-jax", "interlaced-pallas",
                   "fused-handoff", "dense-mxu")

# Streaming-ingestion finalization variants (input layer only): "ranks"
# is the sort-free exclusive-cumulative-rank path (aeq.stream_queues);
# "sort" scatters the banks to dense frames and re-compacts with the
# fused sort (build_aeq_batched) — bit-exact by the streaming-equivalence
# theorem, and measurably faster at small fmaps where the O(HW log HW)
# sort beats the rank computation's constant factor (BENCH_streaming).
# None resolves by fmap size (LayerPlan.resolve_stream_finalize).
STREAM_FINALIZE = ("ranks", "sort")

# Fmap-size crossover for the None stream_finalize default: at/below this
# many fmap cells the fused-sort finalization wins (BENCH_streaming
# measured the 12x12 DVS smoke at 0.83x under "ranks" vs 1.0x+ under
# "sort"); larger fmaps amortize the rank cumsums and "ranks" wins.
_FINALIZE_SORT_MAX_HW = 256


def pad_capacity(capacity: int) -> int:
    """Queue depth padded to a multiple of 64 so the Pallas event-block
    grid divides evenly (the extra slots carry valid=False).  Depths <= 64
    are kept as-is — identical rounding in every path is part of the
    bit-exactness contract (overflow must truncate identically)."""
    return -(-capacity // 64) * 64 if capacity > 64 else capacity


def effective_capacity(requested: int, hw: int) -> int:
    """Effective AEQ depth: padded to 64-multiples, capped at the fmap
    size.  The cap never changes results — a (H, W) fmap holds at most
    H·W events, so truncation depth stays ``min(pad64(requested), hw)``
    in the legacy path and here alike."""
    return min(pad_capacity(requested), hw)


def snap_t_chunk(t_steps: int, requested: int) -> int:
    """Largest divisor of ``t_steps`` that is <= ``requested``.

    Chunked execution (``snn_step_chunk``) requires every chunk to have
    the same length — slots in a continuous-batching batch sit at
    different time offsets, so a ragged tail chunk would force a second
    compiled shape and break slot alignment.  Snapping to a divisor keeps
    one shape and exact T coverage."""
    if t_steps < 1 or requested < 1:
        raise ValueError(f"t_steps={t_steps} and requested={requested} "
                         f"must be >= 1")
    for c in range(min(requested, t_steps), 0, -1):
        if t_steps % c == 0:
            return c


@dataclass(frozen=True)
class LayerPlan:
    """Static per-layer resource plan (the design-time sizing record).

    One instance per conv layer; everything the scheduler needs to execute
    the layer without sizing decisions at trace time.
    """

    index: int                    # position in cfg.layers
    name: str                     # parameter key, e.g. "conv0"
    in_hw: tuple[int, int]        # input fmap geometry (pre-conv)
    out_hw: tuple[int, int]       # output geometry (post-pool)
    c_in: int
    c_out: int
    pool: Optional[int]           # OR-max-pool window (None = no pool)
    capacity: int                 # effective AEQ depth per (t, c_in) queue
    channel_block: int            # output channels per MemPot tile
    block_e: int                  # event-block size (divides queue_depth)
    vm_tile: tuple[int, int, int]  # halo-padded MemPot tile
                                  # (H+2*(kh//2), W+2*(kw//2), cb)
    sat_bits: Optional[int] = None  # 8/16-bit saturating datapath, None=f32
    event_par: int = 1            # same-column events applied in parallel
                                  # (1 = sequential legacy conv unit)
    ingest_capacity: Optional[int] = None  # raw-event buffer depth per
                                  # StreamChunk admission (DVS ingestion;
                                  # input layer only, None = not ingesting)
    ingest_depth: Optional[int] = None     # time bins buffered per stream
                                  # admission window (None = not ingesting)
    variant: Optional[str] = None  # pinned kernel variant (KERNEL_VARIANTS);
                                  # None = resolve from event_par + backend
    stream_finalize: Optional[str] = None  # streamed-queue finalization
                                  # ("ranks"/"sort"; input layer only,
                                  # None = resolve by fmap size —
                                  # resolve_stream_finalize)
    geometry: ConvGeometry = GEOM_3X3  # conv window + interlace layout
                                  # (kh x kw, n_banks = kh*kw membrane
                                  # banks; the paper's 3x3 by default)

    def resolve_variant(self, backend: str = "jax") -> str:
        """Effective kernel variant for this layer under ``backend``.

        A pinned :attr:`variant` wins (the measured autotuner's choice);
        otherwise ``event_par > 1`` selects the interlaced machinery
        (Pallas kernels on the pallas backend; on the jax backend the
        dense MXU conv where :meth:`runs_dense` holds, else the
        banked-select path), ``event_par == 1`` the sequential conv unit.
        """
        if self.variant is not None:
            return self.variant
        if self.runs_dense(backend):
            return "dense-mxu"
        if self.event_par > 1:
            return ("interlaced-pallas" if backend == "pallas"
                    else "banked-jax")
        return "sequential"

    def runs_dense(self, backend: str = "jax") -> bool:
        """Whether an unpinned layer takes ``"dense-mxu"`` under ``backend``.

        The banked jax path costs k²·H·W·C_out element operations per
        input channel and step whatever the spike count, so it never
        beats one dense convolution on the MXU; the dense conv then runs
        wherever it gives the event path's result: an unpinned
        ``event_par > 1`` layer on the jax backend whose queue is
        lossless (``capacity >= H·W``: a truncating queue drops events,
        a dense conv would not), with the float32 datapath (int
        saturation is applied per event and has no dense equivalent),
        and that ingests no raw stream (a streamed input layer keeps its
        banks).
        """
        h, w = self.in_hw
        return (self.variant is None and backend == "jax"
                and self.event_par > 1 and self.capacity >= h * w
                and self.sat_bits is None and self.ingest_capacity is None)

    def resolve_stream_finalize(self) -> str:
        """Effective streamed-queue finalization for this (input) layer.

        An explicit :attr:`stream_finalize` (user pin or the measured
        autotuner's choice) always wins; ``None`` resolves by fmap size —
        small fmaps take the fused-sort path, larger ones the sort-free
        ranks path (the measured crossover, see ``STREAM_FINALIZE``).
        Both finalizations are bit-exact, so the default is pure perf.
        """
        if self.stream_finalize is not None:
            return self.stream_finalize
        h, w = self.in_hw
        return "sort" if h * w <= _FINALIZE_SORT_MAX_HW else "ranks"

    @property
    def vm_dtype(self):
        import jax.numpy as jnp
        return jnp.dtype(_VM_DTYPES[self.sat_bits])

    @property
    def queue_depth(self) -> int:
        """Allocated queue slots: ``capacity``, or the segment-padded
        depth (``aeq.interlaced_capacity``) when the interlaced Pallas
        layout is in play (``event_par`` > 1)."""
        return interlaced_capacity(self.capacity, self.event_par,
                                   self.geometry.n_banks)

    @property
    def event_slots(self) -> int:
        """Padded queue slots allocated per time step (all C_in queues)."""
        return self.queue_depth * self.c_in

    def __repr__(self) -> str:
        h, w = self.in_hw
        oh, ow = self.out_hw
        pool = f" pool{self.pool}" if self.pool else ""
        par = f", par={self.event_par}" if self.event_par > 1 else ""
        ing = (f", ingest={self.ingest_capacity}x{self.ingest_depth}"
               if self.ingest_capacity is not None else "")
        var = (f", variant={self.variant} (pinned)"
               if self.variant is not None
               else f", variant={self.resolve_variant()}")
        fin = (f", finalize={self.stream_finalize}"
               if self.stream_finalize is not None else "")
        geo = ("" if self.geometry == GEOM_3X3
               else f", k={self.geometry.describe()}")
        return (f"LayerPlan({self.name}: {h}x{w}x{self.c_in}{geo} -> "
                f"{oh}x{ow}x{self.c_out}{pool}, cap={self.capacity}, "
                f"cb={self.channel_block}, block_e={self.block_e}, "
                f"vm={self.vm_tile}, "
                f"{_VM_DTYPES[self.sat_bits]}{par}{var}{fin}{ing})")


@dataclass(frozen=True)
class NetworkPlan:
    """Per-layer plans plus the network-wide serving/sharding knobs."""

    layers: tuple[LayerPlan, ...]   # one per conv layer, in network order
    t_steps: int
    batch_tile: int = 8             # serving engine pads batches to this
    batch_axis: str = "batch"       # mesh axis snn_apply_sharded shards over
    t_chunk: Optional[int] = None   # time steps per snn_step_chunk call
                                    # (None = t_steps: one monolithic chunk)
    fc_capacity: Optional[int] = None  # event-driven FC readout queue depth
                                    # (sparse_ffn.event_readout opt-in;
                                    # None = dense classification head)

    @property
    def chunk_steps(self) -> int:
        """Resolved chunk length: ``t_chunk`` or the whole T window."""
        return self.t_chunk if self.t_chunk is not None else self.t_steps

    @property
    def total_event_slots(self) -> int:
        """Padded queue slots allocated over the whole T-step inference —
        the figure the per-layer capacities strictly reduce vs a single
        shared capacity (ISSUE 3 acceptance)."""
        return self.t_steps * sum(lp.event_slots for lp in self.layers)

    def layer(self, name: str) -> LayerPlan:
        for lp in self.layers:
            if lp.name == name:
                return lp
        raise KeyError(name)

    def validate(self, cfg) -> "NetworkPlan":
        """Check the plan matches ``cfg`` geometry; returns self."""
        from .csnn import ConvSpec, conv_out_hw
        conv_specs = [(i, s) for i, s in enumerate(cfg.layers)
                      if isinstance(s, ConvSpec)]
        if len(conv_specs) != len(self.layers):
            raise ValueError(
                f"plan has {len(self.layers)} conv layers, cfg has "
                f"{len(conv_specs)}")
        if self.t_steps != cfg.t_steps:
            raise ValueError(
                f"plan t_steps={self.t_steps} != cfg t_steps={cfg.t_steps}")
        if self.t_chunk is not None and (
                not 1 <= self.t_chunk <= self.t_steps
                or self.t_steps % self.t_chunk != 0):
            raise ValueError(
                f"t_chunk={self.t_chunk} must divide t_steps={self.t_steps}")
        if self.fc_capacity is not None:
            last = self.layers[-1]
            d = last.out_hw[0] * last.out_hw[1] * last.c_out
            if not 1 <= self.fc_capacity <= d:
                raise ValueError(
                    f"fc_capacity={self.fc_capacity} must be in [1, D={d}] "
                    f"(the flattened final conv output feeding the head)")
        hw, c_in = tuple(cfg.input_hw), cfg.input_channels
        for lp, (idx, spec) in zip(self.layers, conv_specs):
            if lp.in_hw != hw or lp.c_in != c_in or lp.c_out != spec.channels:
                raise ValueError(f"{lp!r} does not match cfg layer {idx} "
                                 f"(in_hw={hw}, c_in={c_in}, "
                                 f"c_out={spec.channels})")
            if lp.geometry.window != (spec.kernel, spec.kernel):
                raise ValueError(
                    f"{lp!r} geometry {lp.geometry.describe()} does not "
                    f"match cfg layer {idx} kernel {spec.kernel}x"
                    f"{spec.kernel}")
            if lp.ingest_depth is not None and not (
                    1 <= lp.ingest_depth <= self.t_steps):
                raise ValueError(
                    f"{lp!r} ingest_depth={lp.ingest_depth} must be in "
                    f"[1, t_steps={self.t_steps}]")
            if lp.variant == "fused-handoff":
                # the fused carrier is built against THIS layer's window:
                # the producer's emission places banks on the halo-padded
                # grid derived from (in_hw, geometry), and the consumer
                # slices assume vm_tile covers exactly that grid
                h, w = lp.in_hw
                hh, hw2 = lp.geometry.halo
                want = (h + 2 * hh, w + 2 * hw2, lp.channel_block)
                if tuple(lp.vm_tile) != want:
                    raise ValueError(
                        f"{lp!r} variant='fused-handoff' needs the "
                        f"halo-padded vm_tile {want} matching in_hw="
                        f"{lp.in_hw} under {lp.geometry.describe()}, got "
                        f"{tuple(lp.vm_tile)} — the handoff bank grid and "
                        f"the MemPot banks would desynchronize")
            hw, c_in = conv_out_hw(hw, spec), spec.channels
        return self

    def __repr__(self) -> str:
        lines = [f"NetworkPlan(T={self.t_steps}, t_chunk={self.chunk_steps}, "
                 f"batch_tile={self.batch_tile}, "
                 f"batch_axis={self.batch_axis!r}, "
                 f"total_event_slots={self.total_event_slots})"]
        lines += [f"  {lp!r}" for lp in self.layers]
        return "\n".join(lines)


def plan_conv_layer(
    index: int,
    name: str,
    in_hw: tuple[int, int],
    c_in: int,
    c_out: int,
    *,
    capacity: int,
    pool: Optional[int] = None,
    channel_block: int = 1,
    block_e: Optional[int] = None,
    sat_bits: Optional[int] = None,
    per_layer: bool = True,
    batch_tile: int = 1,
    vmem_budget: Optional[int] = None,
    event_par: Optional[int] = 1,
    ingest_capacity: Optional[int] = None,
    ingest_depth: Optional[int] = None,
    variant: Optional[str] = None,
    stream_finalize: Optional[str] = None,
    geometry: ConvGeometry = GEOM_3X3,
) -> LayerPlan:
    """Derive one conv layer's plan from its geometry.

    ``batch_tile`` models the batched path's residency for the block_e
    autotuner — the MemPot stack is (B, H+2, W+2, cb), B tiles resident
    at once, not one.  ``per_layer=False`` reproduces the legacy
    shared-capacity sizing (queue arrays padded to the shared depth
    regardless of fmap size) — kept as the baseline the per-layer plans
    are measured against.  ``event_par=None`` autotunes the interlaced
    event-parallel width next to ``block_e``; the default 1 keeps the
    sequential conv-unit schedule (and with it the legacy shims'
    bit-exactness-by-identity).
    """
    geometry.require_event_compatible(f"plan_conv_layer({name})")
    h, w = in_hw
    hh, hw_ = geometry.halo
    cap = (effective_capacity(capacity, h * w) if per_layer
           else pad_capacity(capacity))
    cb = snap_divisor(c_out, channel_block)
    vm_tile = (h + 2 * hh, w + 2 * hw_, cb)
    vm_bytes = {None: 4, 8: 1, 16: 2}[sat_bits]
    kwargs = {"vmem_budget": vmem_budget} if vmem_budget else {}
    if event_par is None:
        ep = autotune_event_par(cap, (max(batch_tile, 1),) + vm_tile,
                                vm_bytes=vm_bytes, geometry=geometry,
                                **kwargs)
    else:
        ep = max(1, int(event_par))
    depth = interlaced_capacity(cap, ep, geometry.n_banks)
    if block_e is None:
        be = autotune_block_e(depth, (max(batch_tile, 1),) + vm_tile,
                              vm_bytes=vm_bytes, **kwargs)
    else:
        be = block_e
    if ep > 1:
        # the interlaced grid walks event_par-aligned groups of the
        # segment-padded queue: block_e must be a multiple of event_par
        # that divides the padded depth
        be = snap_block_e_for_par(depth, be, ep)
    else:
        be = snap_divisor(depth, be)
    if pool:
        out_hw = (-(-h // pool), -(-w // pool))
    else:
        out_hw = (h, w)
    if (ingest_capacity is None) != (ingest_depth is None):
        raise ValueError("ingest_capacity and ingest_depth must be set "
                         "together (both None for non-ingesting layers)")
    if ingest_capacity is not None and (ingest_capacity < 1
                                        or ingest_depth < 1):
        raise ValueError(f"ingest_capacity={ingest_capacity} and "
                         f"ingest_depth={ingest_depth} must be >= 1")
    if variant is not None and variant not in KERNEL_VARIANTS:
        raise ValueError(f"variant={variant!r} must be one of "
                         f"{KERNEL_VARIANTS} (or None to resolve from "
                         f"event_par + backend)")
    if variant == "interlaced-pallas" and ep <= 1:
        raise ValueError(
            f"variant='interlaced-pallas' requires event_par > 1 (got "
            f"{ep}): the interlaced kernel walks event_par-aligned groups "
            f"of the segment-padded queue")
    if variant == "dense-mxu" and (cap < h * w or sat_bits is not None):
        raise ValueError(
            f"variant='dense-mxu' requires a lossless float32 layer "
            f"(capacity={cap} >= {h * w} cells, sat_bits=None; got "
            f"sat_bits={sat_bits}): a dense conv neither drops events "
            f"nor saturates per event")
    if stream_finalize is not None and stream_finalize not in STREAM_FINALIZE:
        raise ValueError(f"stream_finalize={stream_finalize!r} must be one "
                         f"of {STREAM_FINALIZE} (or None = resolve by fmap "
                         f"size)")
    return LayerPlan(index=index, name=name, in_hw=in_hw, out_hw=out_hw,
                     c_in=c_in, c_out=c_out, pool=pool, capacity=cap,
                     channel_block=cb, block_e=be, vm_tile=vm_tile,
                     sat_bits=sat_bits, event_par=ep,
                     ingest_capacity=ingest_capacity,
                     ingest_depth=ingest_depth, variant=variant,
                     stream_finalize=stream_finalize, geometry=geometry)


def plan_network(
    cfg,
    *,
    capacity: int | Sequence[int] = 256,
    channel_block: int | Sequence[int] = 1,
    block_e: Optional[int] | Sequence[Optional[int]] = None,
    sat_bits: Optional[int] = None,
    stats: Optional[Sequence] = None,
    percentile: float = 99.9,
    margin: float = 1.25,
    batch_tile: int = 8,
    batch_axis: str = "batch",
    per_layer: bool = True,
    vmem_budget: Optional[int] = None,
    t_chunk: Optional[int] = None,
    event_par: Optional[int] | Sequence[Optional[int]] = 1,
    ingest: bool = False,
    ingest_capacity: Optional[int] = None,
    variant: Optional[str] | Sequence[Optional[str]] = None,
    stream_finalize: Optional[str] = None,
    fc_capacity: Optional[int] = None,
    tune: str = "analytic",
    tune_config=None,
    cache_path=None,
) -> NetworkPlan:
    """Derive a :class:`NetworkPlan` from a ``CSNNConfig``.

    ``capacity``/``channel_block`` may be a single value or one per conv
    layer.  When per-layer spike-count ``stats`` are given (anything
    ``aeq.calibrate_capacity`` accepts, e.g. ``LayerStats.in_spike_counts``
    from a calibration run), the requested capacity of each layer is
    calibrated from its own distribution instead — the two-tier adaptive
    capacity from the ROADMAP.  ``per_layer=False`` keeps the legacy
    shared-capacity sizing (the baseline).

    ``t_chunk`` sets how many time steps one ``snn_step_chunk`` call
    consumes (``snap_t_chunk`` snaps it to a divisor of T); ``None``
    keeps the monolithic whole-T execution.  The input channel count is
    read from ``cfg.input_channels`` (multi-channel inputs, e.g.
    2-polarity DVS encodings).  ``event_par`` selects the interlaced
    event-parallel kernel variant per layer (1 = sequential legacy
    schedule, ``None`` = autotune, or one value per conv layer).

    ``ingest=True`` sizes the streaming-DVS ingestion buffers on the
    input layer: ``ingest_depth`` is the admission window in time bins
    (the chunk length), and ``ingest_capacity`` the raw-event buffer
    depth per admitted :class:`~repro.core.aeq.StreamChunk` — by default
    one input-queue depth worth of events per (bin, channel) of the
    window, padded to a 64-multiple so jitted admission keeps one shape
    (the hardware analogue: the ingress FIFO in front of the AEQ
    builders).  Raw events beyond the buffer are refused at admission
    (host-side backpressure), never silently dropped mid-queue.

    ``variant`` pins the kernel variant per layer (one of
    :data:`KERNEL_VARIANTS`, single value or one per conv layer; ``None``
    keeps the event_par/backend resolution, ``LayerPlan.resolve_variant``)
    and ``stream_finalize`` the streamed-queue finalization of the
    ingesting input layer (:data:`STREAM_FINALIZE`) — both are pure perf
    knobs, bit-exact across every setting but ``"dense-mxu"``, which
    matches to float rounding.

    ``fc_capacity`` opts the classification head into the event-driven
    sparse readout (``sparse_ffn.event_readout``): the accumulated FC
    drive is top-k-compacted to that queue depth and scattered back into
    the dense contraction's operand — bit-exact vs the dense head
    whenever the queue covers every nonzero drive entry (size it with
    ``aeq.calibrate_capacity`` over ``sparse_ffn.drive_active_counts``).

    ``tune`` selects how the perf knobs are derived: ``"analytic"`` (the
    default) keeps the closed-form VMEM model above; ``"measured"``
    micro-benchmarks candidate (block_e, event_par, t_chunk, variant)
    tuples per layer and picks measured winners (``repro.tune``),
    persisting them in the on-disk plan cache; ``"cached"`` loads a
    previously measured plan from the cache (keyed by layer geometry,
    dtype, backend, device kind and jax version; ``REPRO_PLAN_CACHE``
    overrides the location, ``cache_path`` wins over both) and only falls
    back to measuring on a miss.  ``tune_config`` is a
    :class:`repro.tune.TuneConfig`.  Tuning never changes results — every
    candidate is bit-exact — it only changes which bit-exact schedule
    runs.
    """
    if tune not in ("analytic", "measured", "cached"):
        raise ValueError(f"tune={tune!r} must be 'analytic', 'measured' or "
                         f"'cached'")
    if tune != "analytic":
        from repro.tune import tune_network
        base = dict(capacity=capacity, channel_block=channel_block,
                    block_e=block_e, sat_bits=sat_bits, stats=stats,
                    percentile=percentile, margin=margin,
                    batch_tile=batch_tile, batch_axis=batch_axis,
                    per_layer=per_layer, vmem_budget=vmem_budget,
                    t_chunk=t_chunk, event_par=event_par, ingest=ingest,
                    ingest_capacity=ingest_capacity, variant=variant,
                    stream_finalize=stream_finalize,
                    fc_capacity=fc_capacity)
        return tune_network(cfg, mode=tune, base=base, config=tune_config,
                            cache_path=cache_path)
    from .csnn import ConvSpec, conv_out_hw
    conv_specs = [(i, s) for i, s in enumerate(cfg.layers)
                  if isinstance(s, ConvSpec)]
    n = len(conv_specs)
    caps = list(capacity) if not isinstance(capacity, int) else [capacity] * n
    cbs = (list(channel_block) if not isinstance(channel_block, int)
           else [channel_block] * n)
    eps = (list(event_par) if isinstance(event_par, (list, tuple))
           else [event_par] * n)
    bes = (list(block_e) if isinstance(block_e, (list, tuple))
           else [block_e] * n)
    variants = (list(variant) if isinstance(variant, (list, tuple))
                else [variant] * n)
    if (len(caps) != n or len(cbs) != n or len(eps) != n or len(bes) != n
            or len(variants) != n):
        raise ValueError(f"need one capacity/channel_block/event_par/"
                         f"block_e/variant per conv layer ({n}), got "
                         f"{len(caps)}/{len(cbs)}/{len(eps)}/{len(bes)}/"
                         f"{len(variants)}")
    if stats is not None:
        if len(stats) != n:
            raise ValueError(f"need one stats entry per conv layer ({n}), "
                             f"got {len(stats)}")
        caps = [calibrate_capacity(np.asarray(s), percentile=percentile,
                                   margin=margin, align=8) for s in stats]

    if t_chunk is not None:
        t_chunk = snap_t_chunk(cfg.t_steps, t_chunk)
    plans, hw, c_in = [], tuple(cfg.input_hw), cfg.input_channels
    for ci, (idx, spec) in enumerate(conv_specs):
        ing_cap = ing_depth = None
        if ci == 0 and (ingest or ingest_capacity is not None):
            ing_depth = t_chunk if t_chunk is not None else cfg.t_steps
            h0, w0 = hw
            auto = (effective_capacity(caps[ci], h0 * w0)
                    * c_in * ing_depth)
            ing_cap = (ingest_capacity if ingest_capacity is not None
                       else pad_capacity(auto))
        plans.append(plan_conv_layer(
            idx, f"conv{idx}", hw, c_in, spec.channels, capacity=caps[ci],
            pool=spec.pool, channel_block=cbs[ci], block_e=bes[ci],
            sat_bits=sat_bits, per_layer=per_layer, batch_tile=batch_tile,
            vmem_budget=vmem_budget, event_par=eps[ci],
            ingest_capacity=ing_cap, ingest_depth=ing_depth,
            variant=variants[ci],
            stream_finalize=stream_finalize if ci == 0 else None,
            geometry=ConvGeometry(spec.kernel, spec.kernel)))
        hw, c_in = conv_out_hw(hw, spec), spec.channels
    return NetworkPlan(layers=tuple(plans), t_steps=cfg.t_steps,
                       batch_tile=batch_tile, batch_axis=batch_axis,
                       t_chunk=t_chunk, fc_capacity=fc_capacity)
