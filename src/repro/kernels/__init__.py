"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel ships three files: kernel.py (pl.pallas_call + BlockSpec VMEM
tiling, validated in interpret mode), ops.py (jit'd public wrapper with
padding/tiling glue and shape validation), ref.py (pure-jnp oracle the
tests sweep against).

* event_conv      — the convolution unit (paper Sec. VI-B): VMEM-resident
                    membrane-potential tile, grid over AEQ event blocks,
                    channel-lane parallelism, saturating int8/16 adders.
                    Two schedules: the sequential one-event-per-step unit
                    and the memory-interlaced event-parallel unit
                    (``event_conv_pallas_interlaced*``: ``event_par``
                    same-column hazard-free events per vectorized
                    gather->add->scatter step, selected by
                    ``LayerPlan.event_par``).
* threshold_pool  — the thresholding unit (Sec. VI-C): fused bias +
                    compare + m-TTFS indicator + kxk OR-max-pool, plus
                    optional fused spike emission (ISSUE 10): with
                    ``emit_capacity`` set, the unit also returns the
                    (post-pool) spikes already compacted into the next
                    layer's padded interlace-bank carrier (occupancy
                    masks + per-column segment counts, the sort-free
                    cumulative-rank truncation of ``aeq.ranked_keep``) —
                    the producer-side queue handoff the ``"fused-handoff"``
                    scheduler variant consumes without any dense
                    intermediate.

Both are wired into the Algorithm-1 scheduler via
core.scheduler.run_conv_layer*(backend="pallas").

Interpret mode is one rule (``kernels.runtime.resolve_interpret``):
every wrapper defaults to ``interpret=None``, which compiles on a TPU
backend and interprets everywhere else; an explicit ``interpret=`` wins.
Every entry point compiles for TPU v5e at the paper network's served
shapes (``tests/test_tpu_compile.py``, ahead of time) and runs bit-exact
against its ``ref.py`` oracle on the chip (``chip_smoke.py``).
"""
