"""Pallas TPU kernels: event-driven k x k convolution (paper conv unit,
C2+C3; 3x3 in the paper, parametric odd windows here).

Maps the FPGA convolution unit onto the TPU memory hierarchy:

* The membrane-potential tile ``vm`` (H+2hh, W+2hw, C) lives **resident
  in VMEM** for the whole call — the analogue of the kh*kw interlaced
  BRAM columns hard-wired to the PEs (9 for 3x3).  The halo (kh//2,
  kw//2 per side) replaces the FPGA's out-of-bounds detection (edge
  events write into the halo, which is cropped by the wrapper and never
  thresholded).  The kernel window is derived from the weight shape, so
  every entry point serves any odd k x k geometry with one code path.
* The grid runs over **event blocks**; each step streams one block of
  queue entries from HBM into SMEM (the scalar core reads the addresses)
  while vm stays put (``input_output_aliases`` accumulates in place across
  grid steps) — the analogue of the AEQ feeding the pipeline a steady
  event stream.  An entry is one packed int32 word, ``i << 16 | j`` for a
  valid event and -1 for an invalid slot (``_pack_events``).
* Parallelism is over the **C output channels in the lane dimension**
  (the TPU-native replacement for the FPGA's 9 tap-parallel PEs).
* An event updates a **row band**: the kh full rows ``vm[i:i+kh]`` are
  read, the (already 180deg-rotated) taps are placed at columns
  ``j..j+kw-1`` by an iota compare, and the band is written back.  Only
  the leading (untiled) axis is indexed dynamically; cells outside the
  window receive an exact zero, so the band update equals the window
  update bit for bit.
* Integer dtypes use saturating adds (paper C7): the accumulation is
  widened to int32 and clamped back to the storage width.

Two schedules per entry point:

* **sequential** (``event_conv_pallas``/``_batched``): events are applied
  one at a time, preserving program order exactly, so RAW hazards cannot
  occur — the paper's one-event-per-cycle conv unit.
* **interlaced event-parallel** (``event_conv_pallas_interlaced``/
  ``_batched``): each grid step walks groups of ``event_par`` consecutive
  queue slots.  The AEQ emits events in interlace-column order
  (s = kw*(i%kh)+(j%kw)), and same-column events are >= kh apart in i or
  >= kw apart in j, so their windows are DISJOINT: a column-homogeneous
  group reads all its bands, adds, and writes each window back (a masked
  select over the band, so a neighbour's window in the same rows keeps
  its own update).  Disjoint windows never reorder a single cell's
  accumulation, so the result is bit-exact vs the sequential kernel —
  saturating int paths included.  A group that straddles a column
  boundary falls back to the sequential body for just that group.
  Feeding the kernel a segment-padded queue (``aeq.segment_pad``; what
  the ops wrapper and the planned scheduler do) makes every group
  homogeneous by construction.  Invalid slots are replayed as copies of
  the group's first valid event — they re-write the identical updated
  window, which is idempotent under the all-reads-first schedule.

Batched variants run a 2-D grid over (queue, event block): one
``pallas_call`` streams every queue's events against its own
VMEM-resident vm tile.  The event-block axis is innermost, so each
queue's tile is loaded once and revisited until its stream is exhausted.

Interpret mode is resolved by ``kernels.runtime.resolve_interpret``
(compiled on a TPU backend, interpreted elsewhere).
"""
from __future__ import annotations

from functools import partial, reduce

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

_SAT_RANGE = {jnp.int8.dtype: (-128, 127), jnp.int16.dtype: (-32768, 32767)}


def _pack_events(coords: jax.Array, valid: jax.Array) -> jax.Array:
    """(..., E, 2) int32 addresses + (..., E) valid bits -> (..., E) int32
    words, ``i << 16 | j`` where valid and -1 where not — the one scalar
    the kernel's SMEM event stream carries per queue slot."""
    word = coords[..., 0] * 65536 + coords[..., 1]
    return jnp.where(valid != 0, word, -1).astype(jnp.int32)


def _acc_patch(patch, contrib, dtype):
    """patch + contrib in the compute dtype (int32 for the narrow int
    datapaths, which saturate at the storage width — the fixed-point PE
    adders of paper C7)."""
    sat = _SAT_RANGE.get(dtype)
    if sat is not None:
        return jnp.clip(patch + contrib, sat[0], sat[1])
    return patch + contrib


class _Unit:
    """Per-grid-step view of the conv unit: the event block, the rotated
    taps and the vm tile of the current queue (``prefix`` selects the
    tile inside the block — (0,) for the batched kernels' (1, ...)
    blocks)."""

    def __init__(self, ev_ref, kernel_ref, out_ref, prefix):
        self.ev_ref, self.out_ref, self.prefix = ev_ref, out_ref, prefix
        self.dtype = out_ref.dtype
        # narrow ints are widened for the band math: Mosaic selects and
        # adds on packed int8/int16 vectors do not lower
        self.wide = (jnp.int32 if self.dtype in _SAT_RANGE else self.dtype)
        k = kernel_ref[...].astype(self.wide)    # (kh, kw, C), rotated
        self.kh, self.kw = k.shape[:2]
        self.taps = [k[:, b:b + 1, :] for b in range(self.kw)]
        self.band_shape = (self.kh,) + tuple(out_ref.shape[-2:])
        self.col = jax.lax.broadcasted_iota(jnp.int32, self.band_shape, 1)

    def event(self, e):
        """(i, j, valid) of slot ``e``; invalid slots address (0, 0)."""
        word = self.ev_ref[0, 0, e]
        v = word >= 0
        return (jnp.where(v, word >> 16, 0), jnp.where(v, word & 0xFFFF, 0),
                v)

    def band(self, i):
        return self.prefix + (pl.ds(i, self.kh), slice(None), slice(None))

    def read(self, i):
        return self.out_ref[self.band(i)].astype(self.wide)

    def write(self, i, band):
        self.out_ref[self.band(i)] = band.astype(self.dtype)

    def window(self, j):
        return (self.col >= j) & (self.col < j + self.kw)

    def contrib(self, j, on):
        """The taps placed at columns j..j+kw-1 of a row band (zeros
        elsewhere, and everywhere when ``on`` is False)."""
        c = jnp.zeros(self.band_shape, self.wide)
        for b, tap in enumerate(self.taps):
            c = jnp.where(self.col == j + b, tap, c)
        return jnp.where(on, c, jnp.zeros_like(c))

    def apply(self, i, j, v):
        self.write(i, _acc_patch(self.read(i), self.contrib(j, v),
                                 self.dtype))


def _sequential_kernel(ev_ref, kernel_ref, vm_ref, out_ref, *, block_e,
                       prefix):
    """One grid step: apply ``block_e`` queue entries in program order.
    vm arrives through out_ref thanks to input_output_aliases, so every
    grid step accumulates into the same tile."""
    unit = _Unit(ev_ref, kernel_ref, out_ref, prefix)

    def body(e, _):
        unit.apply(*unit.event(e))
        return ()

    jax.lax.fori_loop(0, block_e, body, ())


def _interlaced_kernel(ev_ref, kernel_ref, vm_ref, out_ref, *, block_e,
                       event_par, prefix):
    """Apply ``block_e`` entries as ``event_par``-wide hazard-free groups.

    Per group: read the slots' (i, j, valid); pick the first valid event
    as the group anchor; if every valid slot shares the anchor's interlace
    column (always true on segment-padded queues), read all bands, add,
    and write each window back — reads complete before writes, and
    same-column disjointness makes the writes conflict-free.  Invalid
    slots replay the anchor (same window, same contribution — an
    idempotent duplicate write); a group with no valid slots writes the
    (0,0) window back unchanged.  Otherwise fall back to the sequential
    body for this group only (the column-boundary case on unpadded
    queues).
    """
    unit = _Unit(ev_ref, kernel_ref, out_ref, prefix)
    kh, kw = unit.kh, unit.kw

    def group(g, _):
        base = g * event_par
        ii, jj, vv = zip(*(unit.event(base + p) for p in range(event_par)))
        cols = [(i % kh) * kw + (j % kw) for i, j in zip(ii, jj)]
        # first-valid anchor (coords + column); zeros when the group is empty
        zero_i = jnp.zeros_like(ii[0])
        ai, aj, acol, found = zero_i, zero_i, zero_i, jnp.asarray(False)
        for p in range(event_par):
            take = vv[p] & ~found
            ai = jnp.where(take, ii[p], ai)
            aj = jnp.where(take, jj[p], aj)
            acol = jnp.where(take, cols[p], acol)
            found = found | vv[p]
        homog = reduce(jnp.logical_and,
                       [~vv[p] | (cols[p] == acol) for p in range(event_par)])

        @pl.when(homog)
        def _parallel():
            mi = [jnp.where(vv[p], ii[p], ai) for p in range(event_par)]
            mj = [jnp.where(vv[p], jj[p], aj) for p in range(event_par)]
            bands = [unit.read(mi[p]) for p in range(event_par)]  # gather
            updated = [_acc_patch(bands[p], unit.contrib(mj[p], found),
                                  unit.dtype)
                       for p in range(event_par)]                 # add
            for p in range(event_par):                            # scatter
                unit.write(mi[p], jnp.where(unit.window(mj[p]), updated[p],
                                            unit.read(mi[p])))

        @pl.when(~homog)
        def _sequential():
            for p in range(event_par):
                unit.apply(ii[p], jj[p], vv[p])

        return ()

    jax.lax.fori_loop(0, block_e // event_par, group, ())


def _conv_call(body, vm_padded, coords, valid, kernel, *, block_e,
               interpret):
    """One pallas_call over a (queue, event block) grid — a 1-D event-block
    grid when ``vm_padded`` is a single (Hp, Wp, C) tile."""
    batched = vm_padded.ndim == 4
    hp, wp, c = vm_padded.shape[-3:]
    kh, kw = kernel.shape[:2]
    words = _pack_events(coords, valid)
    e = words.shape[-1]
    n_blk = e // block_e
    if batched:
        q = vm_padded.shape[0]
        if words.shape[0] != q:
            raise ValueError(
                f"queue count mismatch: vm has {q} tiles, coords describe "
                f"{words.shape[0]} queues")
        # (Q * n_blk, 1, block_e): each block's trailing dims equal the
        # array's, the layout SMEM blocks need
        words = words.reshape(q * n_blk, 1, block_e)
        grid = (q, n_blk)
        ev_spec = pl.BlockSpec((1, 1, block_e),
                               lambda qi, b: (qi * n_blk + b, 0, 0),
                               memory_space=pltpu.SMEM)
        k_spec = pl.BlockSpec((kh, kw, c), lambda qi, b: (0, 0, 0))
        vm_spec = pl.BlockSpec((1, hp, wp, c), lambda qi, b: (qi, 0, 0, 0))
        prefix = (0,)
    else:
        words = words.reshape(n_blk, 1, block_e)
        grid = (n_blk,)
        ev_spec = pl.BlockSpec((1, 1, block_e), lambda b: (b, 0, 0),
                               memory_space=pltpu.SMEM)
        k_spec = pl.BlockSpec((kh, kw, c), lambda b: (0, 0, 0))
        vm_spec = pl.BlockSpec((hp, wp, c), lambda b: (0, 0, 0))
        prefix = ()
    return pl.pallas_call(
        partial(body, block_e=block_e, prefix=prefix),
        grid=grid,
        in_specs=[ev_spec, k_spec, vm_spec],
        out_specs=vm_spec,
        out_shape=jax.ShapeDtypeStruct(vm_padded.shape, vm_padded.dtype),
        input_output_aliases={2: 0},  # accumulate vm in place across steps
        interpret=resolve_interpret(interpret),
    )(words, kernel[::-1, ::-1, :], vm_padded)  # 180deg rotation (Fig. 4)


def _check_blocks(e: int, block_e: int) -> None:
    if e % block_e != 0:
        raise ValueError(
            f"event stream length E={e} must be a multiple of "
            f"block_e={block_e}: the grid tiles the queue evenly — go "
            f"through the ops.py wrappers, which pad the queue for you")


def _check_interlaced_blocks(e: int, block_e: int, event_par: int) -> None:
    if event_par < 2:
        raise ValueError(
            f"event_par={event_par}: the interlaced kernel needs >= 2 "
            f"events per group (use event_conv_pallas for the sequential "
            f"schedule)")
    if block_e % event_par != 0:
        raise ValueError(
            f"block_e={block_e} must be a multiple of event_par="
            f"{event_par} so parallel groups tile each event block")
    _check_blocks(e, block_e)


@partial(jax.jit, static_argnames=("block_e", "interpret"))
def event_conv_pallas(
    vm_padded: jax.Array,
    coords: jax.Array,
    valid: jax.Array,
    kernel: jax.Array,
    *,
    block_e: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Apply an event queue to halo-padded membrane potentials.

    vm_padded: (H+2hh, W+2hw, C) float32 / int16 / int8, halo-padded for
               the kernel's geometry.
    coords:    (E, 2) int32 event addresses (i, j) in *unpadded* space.
    valid:     (E,) bool/int8 — AEQ valid bits.
    kernel:    (kh, kw, C) unrotated weights, same dtype as vm; the
               window (and hence the geometry) is taken from this shape.

    Returns the updated (H+2hh, W+2hw, C) tile.  E is padded up to a
    multiple of ``block_e`` by the wrapper in ops.py.
    """
    _check_blocks(coords.shape[0], block_e)
    return _conv_call(_sequential_kernel, vm_padded, coords, valid, kernel,
                      block_e=block_e, interpret=interpret)


@partial(jax.jit, static_argnames=("block_e", "interpret"))
def event_conv_pallas_batched(
    vm_padded: jax.Array,
    coords: jax.Array,
    valid: jax.Array,
    kernel: jax.Array,
    *,
    block_e: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Apply Q event queues to Q halo-padded membrane-potential tiles.

    vm_padded: (Q, H+2hh, W+2hw, C) float32 / int16 / int8 — one tile per
               queue (in the batched scheduler Q is the sample batch B).
    coords:    (Q, E, 2) int32 event addresses in *unpadded* space.
    valid:     (Q, E) bool/int8 — AEQ valid bits.
    kernel:    (kh, kw, C) unrotated weights shared by every queue (all
               queues hold the same (c_in -> channel block) slice).

    One pallas_call, 2-D grid (queue, event block); E must be a multiple
    of ``block_e`` (ops.py pads).  Returns the updated (Q, H+2, W+2, C)
    tiles; per-queue program order is preserved exactly, so results are
    bit-identical to Q sequential ``event_conv_pallas`` calls.
    """
    _check_blocks(coords.shape[1], block_e)
    return _conv_call(_sequential_kernel, vm_padded, coords, valid, kernel,
                      block_e=block_e, interpret=interpret)


@partial(jax.jit, static_argnames=("block_e", "event_par", "interpret"))
def event_conv_pallas_interlaced(
    vm_padded: jax.Array,
    coords: jax.Array,
    valid: jax.Array,
    kernel: jax.Array,
    *,
    block_e: int = 128,
    event_par: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Interlace-parallel ``event_conv_pallas``: ``event_par`` same-column
    events per gather->add->scatter step.

    Same contract as ``event_conv_pallas``; feed it interlace-ordered
    queues (ideally ``aeq.segment_pad``-ed, which makes every aligned
    group column-homogeneous so the sequential fallback never fires).
    Bit-exact vs the sequential kernel for float32/int16/int8
    (tests/test_interlaced.py).
    """
    _check_interlaced_blocks(coords.shape[0], block_e, event_par)
    return _conv_call(partial(_interlaced_kernel, event_par=event_par),
                      vm_padded, coords, valid, kernel, block_e=block_e,
                      interpret=interpret)


@partial(jax.jit, static_argnames=("block_e", "event_par", "interpret"))
def event_conv_pallas_interlaced_batched(
    vm_padded: jax.Array,
    coords: jax.Array,
    valid: jax.Array,
    kernel: jax.Array,
    *,
    block_e: int = 128,
    event_par: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Interlace-parallel ``event_conv_pallas_batched`` (2-D grid over
    (queue, event block), ``event_par`` hazard-free events per step).

    Same contract as the sequential batched kernel and bit-exact vs it;
    per-queue segment padding (``aeq.segment_pad``) keeps every group
    column-homogeneous.
    """
    _check_interlaced_blocks(coords.shape[1], block_e, event_par)
    return _conv_call(partial(_interlaced_kernel, event_par=event_par),
                      vm_padded, coords, valid, kernel, block_e=block_e,
                      interpret=interpret)
