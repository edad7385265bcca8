"""Jitted public wrapper around the event_conv Pallas kernels.

Handles: halo padding, event padding to the block size, channel tiling to
the lane width, shape validation (clear errors *before* any Pallas
tracing), and the queue-exhausted early exit (the self-timed analogue —
see DESIGN.md Sec. 2).

Also home of the event-pipeline autotuners: ``block_e`` (events streamed
per grid step) and ``event_par`` (same-interlace-column events applied in
parallel per step) are pure perf knobs — every setting produces
bit-identical results (invalid slots contribute exact zeros; same-column
events write disjoint patches) — so both are derived from the padded
queue capacity and the VMEM budget instead of being hard-coded
(``autotune_block_e`` / ``autotune_event_par``).

Interpret mode is resolved centrally (``kernels.runtime``): pass
``interpret=None`` (the default everywhere) and the backend decides —
compiled on a TPU, interpreted elsewhere.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.aeq import BatchedEventQueue, EventQueue, segment_pad
from repro.core.event_conv import crop_vm, pad_vm
from repro.core.geometry import GEOM_3X3, ConvGeometry

from .kernel import (event_conv_pallas, event_conv_pallas_batched,
                     event_conv_pallas_interlaced,
                     event_conv_pallas_interlaced_batched)
from .ref import event_conv_ref, event_conv_ref_batched

# Bytes one queue slot streams through VMEM: (i, j) int32 coords + valid int8.
EVENT_BYTES = 2 * 4 + 1
# Per-core VMEM (TPU ~16 MB); the vm tile must stay resident against it.
VMEM_BUDGET = 16 * 1024 * 1024


def snap_divisor(n: int, requested: int) -> int:
    """Largest divisor of ``n`` <= ``requested``.  Snaps the throughput
    knobs (channel_block, block_e) onto values that tile evenly — they are
    perf knobs, never correctness constraints."""
    requested = max(1, min(requested, n))
    if n % requested == 0:
        return requested
    return max(d for d in range(1, requested + 1) if n % d == 0)


def autotune_block_e(capacity: int, vm_tile: tuple[int, ...] = (), *,
                     vm_bytes: int = 4, vmem_budget: int = VMEM_BUDGET) -> int:
    """Pick the event-block size for a queue of ``capacity`` slots.

    The grid streams ``block_e`` (coords, valid) entries per step while the
    ``vm_tile`` stays VMEM-resident (twice: input + aliased output), so the
    block must fit the spare budget double-buffered.  Below that ceiling we
    keep at least ~4 blocks per queue so the block-granular early exit
    (self-timed analogue) still skips work on sparse queues, with a floor
    of 64 entries to amortize grid-step overhead.  Always returns a
    divisor of ``capacity`` (the grid must tile the queue evenly).
    """
    if capacity <= 0:
        return 1
    resident = 2 * math.prod(vm_tile) * vm_bytes if vm_tile else 0
    spare = max(vmem_budget - resident, 2 * EVENT_BYTES)
    vmem_cap = max(spare // (2 * EVENT_BYTES), 1)
    granule = max(capacity // 4, 64)
    return snap_divisor(capacity, min(capacity, vmem_cap, granule))


def snap_block_e_for_par(depth: int, block_e: int, event_par: int) -> int:
    """Snap ``block_e`` onto the interlaced grid: a multiple of
    ``event_par`` that divides the segment-padded queue ``depth`` (which
    is itself a multiple of ``event_par``), so parallel groups tile every
    event block and blocks tile the queue.  The single source of this
    invariant — plan_conv_layer and both ops wrappers all go through it."""
    return event_par * snap_divisor(depth // event_par,
                                    max(1, block_e // event_par))


def autotune_event_par(capacity: int, vm_tile: tuple[int, ...] = (), *,
                       vm_bytes: int = 4, vmem_budget: int = VMEM_BUDGET,
                       max_par: int = 8,
                       geometry: ConvGeometry = GEOM_3X3) -> int:
    """Pick the interlaced event-parallel width for a queue.

    A parallel step holds ``event_par`` gathered kh x kw patches live
    next to the resident vm tile (double-buffered), so the width must fit
    the spare VMEM; below that ceiling it is capped so the average
    interlace column segment (capacity/n_banks events) spans at least ~2
    groups — shallower queues would spend the parallelism on segment
    padding.  Snapped to a power of two, floored at 1 (= sequential
    kernel).  Both VMEM and segment models scale with the geometry: a
    5x5 window gathers ~2.8x bigger patches across 25 (vs 9) thinner
    columns, so the tuned width naturally shrinks.
    """
    if capacity < 2:
        return 1
    nb = geometry.n_banks
    resident = 2 * math.prod(vm_tile) * vm_bytes if vm_tile else 0
    channels = vm_tile[-1] if vm_tile else 1
    patch_bytes = 2 * nb * channels * vm_bytes
    spare = max(vmem_budget - resident, 0)
    vmem_cap = spare // patch_bytes if patch_bytes else max_par
    target = min(max_par, vmem_cap, max(capacity // (2 * nb), 1))
    par = 1
    while par * 2 <= target:
        par *= 2
    return par


def candidate_block_es(capacity: int, vm_tile: tuple[int, ...] = (), *,
                       vm_bytes: int = 4,
                       vmem_budget: int = VMEM_BUDGET) -> list[int]:
    """Analytic-prior candidate set for the measured autotuner.

    The analytic pick (``autotune_block_e``) plus its neighbours one
    octave up and down, all snapped to divisors of ``capacity`` and kept
    under the same VMEM ceiling — the measured tuner searches this small
    set instead of every divisor, so tuning cost stays bounded while the
    prior's mis-tunes (the granule heuristic is a TPU model; CPU interpret
    backends often prefer bigger blocks) are still recoverable.  Sorted,
    deduplicated, always non-empty (contains the analytic pick).
    """
    prior = autotune_block_e(capacity, vm_tile, vm_bytes=vm_bytes,
                             vmem_budget=vmem_budget)
    if capacity <= 0:
        return [prior]
    resident = 2 * math.prod(vm_tile) * vm_bytes if vm_tile else 0
    spare = max(vmem_budget - resident, 2 * EVENT_BYTES)
    vmem_cap = max(spare // (2 * EVENT_BYTES), 1)
    cands = {prior}
    for req in (prior // 2, prior * 2, prior * 4, capacity):
        if req >= 1:
            cands.add(snap_divisor(capacity, min(req, vmem_cap)))
    return sorted(cands)


def validate_event_shapes(coords: jax.Array, valid: jax.Array,
                          vm_padded: jax.Array | None = None, *,
                          block_e: int | None = None,
                          event_par: int = 1,
                          batched: bool = False,
                          geometry: ConvGeometry | None = None) -> None:
    """Validate event-stream shapes with actionable messages.

    The raw kernels require E to already be a multiple of ``block_e`` (the
    grid must tile the queue) and formerly surfaced that as a bare
    ``E=... must be a multiple of block_e=...`` mid-trace; the ops
    wrappers call this *before* padding so mismatched queue/vm shapes fail
    fast with the fix spelled out.  Pass the planned ``geometry`` so the
    messages name the actual kernel window instead of assuming 3x3.
    """
    geo = f" [{geometry.describe()} geometry]" if geometry is not None else ""
    want = 3 if batched else 2
    kind = "batched " if batched else ""
    if coords.ndim != want or coords.shape[-1] != 2:
        raise ValueError(
            f"{kind}event coords must be {'(Q, E, 2)' if batched else '(E, 2)'}"
            f" (i, j) address pairs, got shape {coords.shape}{geo}")
    if valid.shape != coords.shape[:-1]:
        raise ValueError(
            f"valid bits shape {valid.shape} does not match event coords "
            f"{coords.shape} — expected {coords.shape[:-1]}{geo}")
    if batched and vm_padded is not None and vm_padded.shape[0] != coords.shape[0]:
        raise ValueError(
            f"queue count mismatch: vm stack has {vm_padded.shape[0]} tiles "
            f"but coords describe {coords.shape[0]} queues{geo}")
    if block_e is not None and block_e < 1:
        raise ValueError(f"block_e={block_e} must be >= 1{geo}")
    if event_par < 1:
        raise ValueError(f"event_par={event_par} must be >= 1{geo}")
    if event_par > 1 and block_e is not None and block_e % event_par != 0:
        raise ValueError(
            f"block_e={block_e} must be a multiple of event_par={event_par} "
            f"so parallel groups tile the event blocks evenly (plan_network "
            f"snaps both; pass block_e=None to autotune){geo}")
    if geometry is not None:
        geometry.require_event_compatible("event_conv")


def _pad_events(queue: EventQueue, block_e: int) -> tuple[jax.Array, jax.Array]:
    e = queue.capacity
    pad = -e % block_e
    coords = jnp.pad(queue.coords, ((0, pad), (0, 0)))
    valid = jnp.pad(queue.valid, (0, pad))
    return coords, valid


@partial(jax.jit, static_argnames=("block_e", "use_kernel", "interpret",
                                   "event_par"))
def event_conv(
    vm: jax.Array,
    queue: EventQueue,
    kernel: jax.Array,
    *,
    block_e: int | None = 128,
    use_kernel: bool = True,
    interpret: bool | None = None,
    event_par: int = 1,
) -> jax.Array:
    """Event-driven conv accumulation onto an *unpadded* (H, W, C) vm
    (the kernel window — 3x3 by default — is taken from the weight shape).

    The Pallas kernel (or the jnp oracle when ``use_kernel=False``) sees
    the halo-padded tile; this wrapper crops it back.  ``block_e=None``
    autotunes the event block from the queue capacity and VMEM budget.
    ``event_par > 1`` segment-pads the queue (``aeq.segment_pad``) and
    dispatches the interlace-parallel kernel — bit-exact vs the
    sequential kernel by hazard-freedom of same-column events.
    """
    if vm.ndim == 2:
        out = event_conv(vm[:, :, None], queue, kernel[:, :, None],
                         block_e=block_e, use_kernel=use_kernel,
                         interpret=interpret, event_par=event_par)
        return out[:, :, 0]
    geom = ConvGeometry.from_kernel_shape(kernel.shape)
    hh, hw = geom.halo
    validate_event_shapes(queue.coords, queue.valid, block_e=block_e,
                          event_par=event_par, geometry=geom)
    if event_par > 1:
        queue = segment_pad(queue, event_par, geom)
    if block_e is None:
        block_e = autotune_block_e(
            queue.capacity,
            (vm.shape[0] + 2 * hh, vm.shape[1] + 2 * hw) + vm.shape[2:],
            vm_bytes=vm.dtype.itemsize)
        if event_par > 1:
            block_e = snap_block_e_for_par(queue.capacity, block_e, event_par)
    coords, valid = _pad_events(queue, block_e)
    vm_p = pad_vm(vm, geom)
    if use_kernel and event_par > 1:
        out = event_conv_pallas_interlaced(
            vm_p, coords, valid, kernel, block_e=block_e,
            event_par=event_par, interpret=interpret)
    elif use_kernel:
        out = event_conv_pallas(vm_p, coords, valid, kernel,
                                block_e=block_e, interpret=interpret)
    else:
        out = event_conv_ref(vm_p, coords, valid, kernel)
    return crop_vm(out, geom)


@partial(jax.jit, static_argnames=("block_e", "use_kernel", "interpret",
                                   "event_par"))
def event_conv_batched(
    vm: jax.Array,
    queues: BatchedEventQueue,
    kernel: jax.Array,
    *,
    block_e: int | None = 128,
    use_kernel: bool = True,
    interpret: bool | None = None,
    event_par: int = 1,
) -> jax.Array:
    """Batched event-driven conv accumulation onto (Q, H, W, C) vm tiles.

    ``queues`` must have a single leading dim Q matching ``vm``; the
    (kh, kw, C) kernel is shared by every queue (its shape fixes the
    geometry).  One fused 2-D-grid pallas_call (or the vmapped jnp oracle
    when ``use_kernel=False``) processes all queues; the wrapper
    halo-pads, pads the event axis to ``block_e``, and crops back.
    ``block_e=None`` autotunes from the queue capacity and VMEM budget;
    ``event_par > 1`` segment-pads the queues and dispatches the
    interlace-parallel kernel.
    """
    if queues.coords.ndim != 3:
        raise ValueError("event_conv_batched expects queues with one leading "
                         f"dim, got coords shape {queues.coords.shape}")
    geom = ConvGeometry.from_kernel_shape(kernel.shape)
    hh, hw = geom.halo
    validate_event_shapes(queues.coords, queues.valid, vm, block_e=block_e,
                          event_par=event_par, batched=True, geometry=geom)
    if event_par > 1:
        queues = segment_pad(queues, event_par, geom)
    if block_e is None:
        block_e = autotune_block_e(
            queues.capacity,
            (vm.shape[1] + 2 * hh, vm.shape[2] + 2 * hw) + vm.shape[3:],
            vm_bytes=vm.dtype.itemsize)
        if event_par > 1:
            block_e = snap_block_e_for_par(queues.capacity, block_e, event_par)
    pad = -queues.capacity % block_e
    coords = jnp.pad(queues.coords, ((0, 0), (0, pad), (0, 0)))
    valid = jnp.pad(queues.valid, ((0, 0), (0, pad)))
    vm_p = jax.vmap(lambda v: pad_vm(v, geom))(vm)
    if use_kernel and event_par > 1:
        out = event_conv_pallas_interlaced_batched(
            vm_p, coords, valid, kernel, block_e=block_e,
            event_par=event_par, interpret=interpret)
    elif use_kernel:
        out = event_conv_pallas_batched(vm_p, coords, valid, kernel,
                                        block_e=block_e, interpret=interpret)
    else:
        out = event_conv_ref_batched(vm_p, coords, valid, kernel)
    return jax.vmap(lambda v: crop_vm(v, geom))(out)
