"""Pallas TPU kernel: fused thresholding unit (paper Secs. V-C / VI-C).

One VMEM pass per channel block fuses the paper's 5-stage thresholding
pipeline: bias add (saturating for integer datapaths), threshold compare,
m-TTFS spike-indicator OR, and the 3x3 OR-max-pool reduction.  The dense
sweep of the FPGA (stride-3 3x3 windows, 9 comparators) becomes one
vectorized tile op; the pool reduces H through a leading-axis reshape
and W through strided sublane reads of a VMEM scratch, on 0/1 int32
(Mosaic relayouts neither a sublane-splitting reshape nor i1 vectors).

Grid: over channel blocks (channels are independent).  The firing
threshold V_t is layer-static and baked into the kernel as a constant —
exactly like the synthesized comparator constant on the FPGA.

Fused emission (ISSUE 10): ``emit_capacity`` extends the same VMEM pass
with the producer-side queue compaction — the output spikes leave the
unit already as the next layer's fused-handoff bank masks plus
per-column segment counts (the content of ``ref.emit_banked``: sort-free
cumulative ranks, the ``aeq.stream_queues`` machinery, unrolled over the
static bank grid in-kernel), the TPU analogue of the paper's runtime
AEQ-builder circuitry sitting right behind the comparators.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import GEOM_3X3, ConvGeometry
from repro.kernels.runtime import resolve_interpret

_SAT_RANGE = {jnp.int8.dtype: (-128, 127), jnp.int16.dtype: (-32768, 32767)}


def _or_pool(spikes, pool, scratch):
    """OR-max-pool of a (H, W, C) 0/1 int32 map over pool x pool windows.

    H is a leading (untiled) axis, so its windows reduce through a free
    reshape; W lives on sublanes, so its windows are read back as
    ``pool`` strided slices of a VMEM scratch (Mosaic relayouts neither a
    sublane-splitting reshape nor an i1 reduction)."""
    h, w, c = spikes.shape
    scratch[...] = jnp.max(spikes.reshape(h // pool, pool, w, c), axis=1)
    out = scratch[:, pl.ds(0, w // pool, stride=pool), :]
    for b in range(1, pool):
        out = jnp.maximum(out,
                          scratch[:, pl.ds(b, w // pool, stride=pool), :])
    return out


def _emit(pooled, capacity, geometry, banks_scratch, masks_scratch):
    """In-kernel ``ref.emit_banked`` of a (H', W', C) 0/1 int32 map.

    Interlace banks are strided reads of a zero-padded copy; the
    cumulative-rank truncation of ``aeq.ranked_keep`` is unrolled over
    the (static, small) bank grid with lane-vector prefix sums; each kept
    bank is stored at its static offset in the padded carrier.  Returns
    (masks (n_banks, HBp+2, WBp+2, C), seg_counts (n_banks, C)), int32."""
    h, w, c = pooled.shape
    kh, kw = geometry.kh, geometry.kw
    hh, hw_ = geometry.halo
    nb = geometry.n_banks
    hb, wb = -(-h // kh), -(-w // kw)
    banks_scratch[...] = jnp.zeros_like(banks_scratch)
    banks_scratch[:h, :w, :] = pooled
    banks = [banks_scratch[pl.ds(si, hb, stride=kh),
                           pl.ds(sj, wb, stride=kw), :]
             for si, sj in (divmod(s, kw) for s in range(nb))]
    seg_full = [jnp.sum(b, axis=(0, 1), keepdims=True) for b in banks]
    count = sum(seg_full)
    kept = jnp.minimum(count, min(capacity, h * w))
    seg_off, off = [], jnp.zeros_like(count)
    for s in range(nb):                        # exclusive prefix over s
        seg_off.append(off)
        off = off + seg_full[s]
    seg_counts = [jnp.clip(kept - seg_off[s], 0, seg_full[s])
                  for s in range(nb)]
    if capacity < h * w:
        # rank of a cell in (s, I, J) raster order: actives in earlier
        # columns + earlier rows of its bank + earlier cells of its row
        for s in range(nb):
            b = banks[s]
            row_off, run = [], seg_off[s]
            for i in range(hb):
                row_off.append(run)
                run = run + jnp.sum(b[i:i + 1], axis=1, keepdims=True)
            rank = jnp.concatenate(row_off, axis=0)  # (hb, 1, C)
            cols = []
            for j in range(wb):
                cell = b[:, j:j + 1, :]
                cols.append(jnp.where(rank < kept, cell, 0))
                rank = rank + cell
            banks[s] = jnp.concatenate(cols, axis=1)
    masks_scratch[...] = jnp.zeros_like(masks_scratch)
    for s in range(nb):
        si, sj = divmod(s, kw)
        tb = ((si + hh) % kh) * kw + (sj + hw_) % kw
        oi = 1 + (si + hh) // kh
        oj = 1 + (sj + hw_) // kw
        masks_scratch[tb, oi:oi + hb, oj:oj + wb, :] = banks[s]
    return (masks_scratch[...],
            jnp.concatenate(seg_counts, axis=0).reshape(nb, c))


def _threshold_pool_kernel(vm_ref, bias_ref, fired_ref, vm_out_ref,
                           spikes_ref, pooled_ref, *rest, v_t, pool,
                           emit_capacity, emit_geometry):
    emit_refs = rest[:2] if emit_capacity is not None else ()
    scratch = list(rest[len(emit_refs):])
    pool_scratch = scratch.pop(0) if pool is not None else None
    vm = vm_ref[...]
    bias = bias_ref[...]  # (1, 1, block_c) broadcast over the tile
    sat = _SAT_RANGE.get(vm.dtype)
    if sat is not None:
        wide = vm.astype(jnp.int32) + bias.astype(jnp.int32)
        vm_new = jnp.clip(wide, sat[0], sat[1]).astype(vm.dtype)
    else:
        vm_new = vm + bias
    # 0/1 int32 from here on: Mosaic cannot relayout i1 vectors through
    # the pool's reshapes and reductions
    spikes = ((vm_new > jnp.asarray(v_t, vm_new.dtype))
              | (fired_ref[...] != 0)).astype(jnp.int32)
    vm_out_ref[...] = vm_new
    spikes_ref[...] = spikes.astype(jnp.int8)
    pooled = spikes if pool is None else _or_pool(spikes, pool, pool_scratch)
    pooled_ref[...] = pooled.astype(jnp.int8)
    if emit_capacity is not None:
        masks_ref, seg_ref = emit_refs
        masks, seg_counts = _emit(pooled, emit_capacity, emit_geometry,
                                  *scratch)
        masks_ref[...] = masks.astype(jnp.int8)
        seg_ref[...] = seg_counts


@partial(jax.jit, static_argnames=("v_t", "pool", "block_c", "interpret",
                                   "emit_capacity", "emit_geometry"))
def threshold_pool_pallas(
    vm: jax.Array,
    bias: jax.Array,
    fired: jax.Array,
    *,
    v_t: float,
    pool: int | None,
    block_c: int = 128,
    interpret: bool | None = None,
    emit_capacity: int | None = None,
    emit_geometry: ConvGeometry = GEOM_3X3,
):
    """Fused threshold unit over (H, W, C) membrane potentials.

    vm:    (H, W, C); H and W must already be multiples of ``pool``.
    bias:  (C,) per-output-channel bias (paper applies it every step).
    fired: (H, W, C) int8 m-TTFS indicator bits.

    Returns (vm_out, spikes int8 (H,W,C), pooled int8 (H/p, W/p, C)); when
    ``pool`` is None the third output duplicates ``spikes``.

    ``emit_capacity`` additionally emits the fused-handoff compaction of
    the (post-pool) output inside the same pass — two extra outputs,
    masks int8 (n_banks, HBp+2, WBp+2, C) and seg_counts int32
    (n_banks, C) in the ``ref.emit_banked`` layout, with the AEQ capacity
    truncation applied per channel under ``emit_geometry`` (the NEXT
    layer's window).  Bit-exact vs the oracle (analysis kernel audit).
    """
    h, w, c = vm.shape
    if pool is not None and (h % pool or w % pool):
        raise ValueError(f"H,W=({h},{w}) must be multiples of pool={pool} (pad first)")
    if c % block_c != 0:
        raise ValueError(f"C={c} must be a multiple of block_c={block_c} (pad first)")
    ph, pw = (h // pool, w // pool) if pool is not None else (h, w)
    grid = (c // block_c,)
    in_specs = [
        pl.BlockSpec((h, w, block_c), lambda b: (0, 0, b)),
        pl.BlockSpec((1, 1, block_c), lambda b: (0, 0, b)),
        pl.BlockSpec((h, w, block_c), lambda b: (0, 0, b)),
    ]
    out_specs = [
        pl.BlockSpec((h, w, block_c), lambda b: (0, 0, b)),
        pl.BlockSpec((h, w, block_c), lambda b: (0, 0, b)),
        pl.BlockSpec((ph, pw, block_c), lambda b: (0, 0, b)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((h, w, c), vm.dtype),
        jax.ShapeDtypeStruct((h, w, c), jnp.int8),
        jax.ShapeDtypeStruct((ph, pw, c), jnp.int8),
    ]
    if emit_capacity is not None:
        geo = emit_geometry
        hh, hw_ = geo.halo
        nb = geo.n_banks
        hbp = -(-(ph + 2 * hh) // geo.kh) + 2
        wbp = -(-(pw + 2 * hw_) // geo.kw) + 2
        out_specs += [
            pl.BlockSpec((nb, hbp, wbp, block_c), lambda b: (0, 0, 0, b)),
            pl.BlockSpec((nb, block_c), lambda b: (0, b)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((nb, hbp, wbp, c), jnp.int8),
            jax.ShapeDtypeStruct((nb, c), jnp.int32),
        ]
    scratch = ([pltpu.VMEM((h // pool, w, block_c), jnp.int32)]
               if pool is not None else [])
    if emit_capacity is not None:
        scratch += [pltpu.VMEM((geo.kh * -(-ph // geo.kh),
                                geo.kw * -(-pw // geo.kw), block_c),
                               jnp.int32),
                    pltpu.VMEM((nb, hbp, wbp, block_c), jnp.int32)]
    return pl.pallas_call(
        partial(_threshold_pool_kernel, v_t=v_t, pool=pool,
                emit_capacity=emit_capacity, emit_geometry=emit_geometry),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(vm, bias.reshape(1, 1, c), fired)
