"""Paper Table V: throughput/latency of event-driven vs frame-based
processing, and the core scaling claim — processing time scales with the
number of spikes (queue occupancy), not with the frame size.

We sweep input sparsity, calibrate the AEQ capacity per sparsity level
(exactly how the queue BRAM would be sized), and time event-driven
inference against the dense frame-based baseline.  The figure of merit is
the slope: event-mode time follows capacity ~ spike count; dense-mode
time is flat.

Beyond-paper rows: the batched event pipeline (``snn_apply_batched``) vs
``vmap`` over the single-sample path vs the dense baseline — the batched
rows are the serving configuration and must be at least as fast per
sample as vmap (amortized queue compaction + batch-wide early exit) —
plus the memory-interlaced event-parallel pipeline (``event_par``
autotuned per layer: banked MemPot tiles, whole hazard-free columns
applied per step; bit-exact vs the sequential batched row and asserted
faster), the per-layer-planned pipeline (``plan_network`` capacities,
the padded-slot reduction recorded in the derived column), the async
micro-batching serving engine (``serve.csnn_engine``, requests submitted
one at a time and flushed on batch/deadline thresholds), — under a
bursty Poisson arrival trace — continuous batching (slot-level refill,
``t_chunk``-granular admission) vs the run-to-completion engine on the
identical trace (bit-exact logits, higher observed throughput), and the
``wide_5x5`` parametric-geometry row: the ``csnn_wide`` config's 5x5
first layer run through the identical event pipeline, bit-exact vs the
dense frame-based oracle (asserted).

``--json`` (via benchmarks.run) writes the rows to BENCH_table5.json —
the machine-readable throughput trajectory tracked across PRs.
"""
from __future__ import annotations

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import csnn_wide
from repro.core.aeq import calibrate_capacity
from repro.core.csnn import (encode_input, init_params, snn_apply,
                             snn_apply_batched, snn_apply_dense)
from repro.core.plan import plan_network
from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig
from repro.tune import TuneConfig

from .common import emit, timeit, trained_csnn, write_bench_json


def main(json_out: bool = False):
    cfg, params, (xtr, ytr, xte, yte) = trained_csnn()
    batch = 4

    # dense frame-based baseline (SIES-style): one timing, sparsity-blind
    imgs = jnp.asarray(xte[:batch])
    spikes = encode_input(imgs, cfg)
    dense_fn = jax.jit(jax.vmap(lambda s: snn_apply_dense(params, s, cfg)))
    us_dense = timeit(dense_fn, spikes) / batch
    emit("table5/dense_frame_based", us_dense, "mode=baseline")

    # event-driven at calibrated capacity per input-density level
    rng = np.random.default_rng(0)
    synth_cap, synth_us = None, None
    for density, name in [(0.05, "sparse5"), (0.15, "synth_digits"),
                          (0.35, "dense35"), (0.7, "dense70")]:
        if name == "synth_digits":
            x = imgs
        else:
            x = jnp.asarray((rng.random((batch, 28, 28, 1)) < density)
                            .astype(np.float32))
        sp = encode_input(x, cfg)
        # calibrate the queue depth from observed spike counts (layer 1 input)
        counts = np.asarray(sp.sum(axis=(2, 3, 4)))
        cap = calibrate_capacity(counts, percentile=100.0, margin=1.1, align=32)
        cap = int(min(cap, 784))
        fn = jax.jit(jax.vmap(lambda s: snn_apply(
            params, s, cfg, capacity=cap, channel_block=8, collect_stats=False)))
        us = timeit(fn, sp)
        if name == "synth_digits":  # reused as the vmap row below
            synth_cap, synth_us = cap, us
        emit(f"table5/event_driven_{name}", us / batch,
             f"capacity={cap};vs_dense={us_dense / (us / batch):.2f}x")

    # batched event pipeline vs vmap-over-samples vs dense (serving config);
    # the vmap row reuses the synth_digits timing above — same inputs, same
    # calibrated capacity, no second compile.
    cap = synth_cap
    batched_fn = jax.jit(lambda s: snn_apply_batched(
        params, s, cfg, capacity=cap, channel_block=8, collect_stats=False))
    us_vmap = synth_us / batch
    us_batched = timeit(batched_fn, spikes) / batch
    emit("table5/vmap_per_sample", us_vmap,
         f"capacity={cap};batch={batch};vs_dense={us_dense / us_vmap:.2f}x")
    emit("table5/batched_pipeline", us_batched,
         f"capacity={cap};batch={batch};vs_vmap={us_vmap / us_batched:.2f}x;"
         f"vs_dense={us_dense / us_batched:.2f}x")

    # memory-interlaced event-parallel pipeline: event_par autotuned per
    # layer, banked MemPot tiles, sort-free compaction, one vectorized
    # column application per (t, c_in, bank).  Bit-exact vs the batched
    # row (asserted) and the headline speedup of the interlaced refactor.
    plan_il = plan_network(cfg, capacity=cap, channel_block=8,
                           batch_tile=batch, event_par=None,
                           variant="banked-jax")
    il_fn = jax.jit(lambda s: snn_apply_batched(
        params, s, cfg, plan_il, collect_stats=False))
    assert np.array_equal(np.asarray(il_fn(spikes)),
                          np.asarray(batched_fn(spikes))), \
        "interlaced pipeline must be bit-exact vs the sequential batched row"
    us_il = timeit(il_fn, spikes) / batch
    speedup = us_batched / us_il
    emit("table5/interlaced", us_il,
         f"event_par={[lp.event_par for lp in plan_il.layers]};"
         f"vs_batched={speedup:.2f}x;vs_dense={us_dense / us_il:.2f}x")
    # the speedup assertion only makes sense when the autotuner actually
    # picked a parallel width (always true on the paper net; guards the
    # degenerate all-sequential case where both rows trace identical
    # computations and the ratio is pure timer noise)
    if any(lp.event_par > 1 for lp in plan_il.layers):
        assert speedup > 1.0, (
            f"interlaced event-parallel row must beat the sequential "
            f"batched row, got {speedup:.2f}x")

    # per-layer plan: same calibrated request, capacities capped per layer
    plan = plan_network(cfg, capacity=cap, channel_block=8, batch_tile=batch)
    shared = plan_network(cfg, capacity=cap, channel_block=8, per_layer=False)
    planned_fn = jax.jit(lambda s: snn_apply_batched(
        params, s, cfg, plan, collect_stats=False))
    us_planned = timeit(planned_fn, spikes) / batch
    emit("table5/planned_per_layer", us_planned,
         f"slots={plan.total_event_slots}_vs_shared={shared.total_event_slots};"
         f"vs_batched={us_batched / us_planned:.2f}x")

    # measured-autotuned plan (repro.tune): candidate (block_e, event_par,
    # variant) tuples micro-benchmarked per layer on synthetic queues at
    # calibrated occupancy, then network-level knobs (capacity sharing,
    # t_chunk) measured whole-pipeline; winners persist in the plan cache
    # the CI tuner lane uploads.  Bit-exact vs the reference batched
    # pipeline by construction (asserted), and never slower than the best
    # analytic row (interlaced) — when the tuner lands on the exact same
    # execution it reuses that row's timing (ratio 1.00x by identity)
    # instead of re-rolling timer noise.
    plan_tuned = plan_network(cfg, capacity=cap, channel_block=8,
                              batch_tile=batch, event_par=None,
                              tune="measured",
                              tune_config=TuneConfig(batch=batch),
                              cache_path="results/plan_cache.json")
    tuned_fn = jax.jit(lambda s: snn_apply_batched(
        params, s, cfg, plan_tuned, collect_stats=False))
    assert np.array_equal(np.asarray(tuned_fn(spikes)),
                          np.asarray(batched_fn(spikes))), \
        "tuned plan must be bit-exact vs the reference batched pipeline"

    def exec_sig(p):
        # what actually determines the traced computation on this backend
        return (p.chunk_steps, tuple(
            (lp.capacity, lp.channel_block, lp.event_par, lp.block_e,
             lp.resolve_variant("jax")) for lp in p.layers))

    if exec_sig(plan_tuned) == exec_sig(plan_il):
        us_tuned, vs_il = us_il, 1.0
    else:
        us_tuned = timeit(tuned_fn, spikes) / batch
        us_il_ref = us_il
        vs_il = us_il_ref / us_tuned
        for _ in range(2):  # re-measure interleaved before calling a loss
            if vs_il >= 1.0:
                break
            us_il_ref = min(us_il_ref, timeit(il_fn, spikes) / batch)
            us_tuned = min(us_tuned, timeit(tuned_fn, spikes) / batch)
            vs_il = us_il_ref / us_tuned
    assert vs_il >= 1.0, (
        f"tuned plan must not lose to the best analytic row, got "
        f"{vs_il:.2f}x vs interlaced")
    emit("table5/tuned", us_tuned,
         f"variants={[lp.resolve_variant('jax') for lp in plan_tuned.layers]};"
         f"t_chunk={plan_tuned.chunk_steps};"
         f"slots={plan_tuned.total_event_slots};"
         f"vs_interlaced={vs_il:.2f}x;vs_batched={us_batched / us_tuned:.2f}x")

    # fused spike emission (ISSUE 10): every layer pinned "fused-handoff",
    # so spikes leave each threshold unit already compacted into the next
    # layer's padded-bank carrier — no dense intermediate, no second O(HW)
    # compaction pass per (layer, timestep).  Bit-exact vs the reference
    # batched pipeline (asserted: the carrier provably holds the same kept
    # events as build_bank_masks) and required to beat the best prior
    # event-driven row by >= 1.15x — the headline of the fusion.
    plan_fused = plan_network(cfg, capacity=cap, channel_block=8,
                              batch_tile=batch,
                              variant=["fused-handoff"] * len(plan.layers))
    fused_fn = jax.jit(lambda s: snn_apply_batched(
        params, s, cfg, plan_fused, collect_stats=False))
    bit_exact = np.array_equal(np.asarray(fused_fn(spikes)),
                               np.asarray(batched_fn(spikes)))
    assert bit_exact, \
        "fused-handoff pipeline must be bit-exact vs the batched reference"
    # the 1.15x bar is against the best event-driven row that does NOT
    # itself use the fused handoff: now that "fused-handoff" sits on the
    # tuner's candidate axis the tuned row usually IS the fused pipeline
    # (comparing against it would be fused-vs-fused, identically 1.0x),
    # in which case the honest prior best is the interlaced row.
    tuned_is_fused = any(lp.resolve_variant("jax") == "fused-handoff"
                         for lp in plan_tuned.layers)
    prior_fn, us_prior = ((il_fn, us_il) if tuned_is_fused
                          else (tuned_fn, min(us_tuned, us_il)))
    us_fused = timeit(fused_fn, spikes) / batch
    vs_prior = us_prior / us_fused
    for _ in range(2):  # re-measure interleaved before calling a miss
        if vs_prior >= 1.15:
            break
        us_prior = min(us_prior, timeit(prior_fn, spikes) / batch)
        us_fused = min(us_fused, timeit(fused_fn, spikes) / batch)
        vs_prior = us_prior / us_fused
    assert vs_prior >= 1.15, (
        f"fused-handoff must beat the best non-fused event-driven row by "
        f">= 1.15x, got {vs_prior:.2f}x")
    emit("table5/fused_handoff", us_fused,
         f"bit_exact={bit_exact};vs_prior_best={vs_prior:.2f}x;"
         f"vs_tuned={us_tuned / us_fused:.2f}x;"
         f"vs_dense={us_dense / us_fused:.2f}x")

    # beyond-paper parametric-geometry demo: the csnn_wide config swaps
    # the first conv layer to a 5x5 window (25 interlace banks) and runs
    # the identical event pipeline — planning, AEQ interlacing, banked
    # apply all derive their layout from the layer geometry.  The k=5
    # correctness claim is CI-enforced here: the event-driven pipeline
    # must stay bit-exact vs the dense frame-based oracle, so the queues
    # are sized truncation-free (capacity = H*W; the dense oracle has no
    # overflow-drop semantics to compare against).
    wcfg = csnn_wide.FULL
    wparams = init_params(jax.random.PRNGKey(2), wcfg)
    wh, ww = wcfg.input_hw
    wplan = plan_network(wcfg, capacity=wh * ww, channel_block=8,
                         batch_tile=batch, event_par=None)
    wsp = encode_input(imgs, wcfg)
    wide_fn = jax.jit(lambda s: snn_apply_batched(
        wparams, s, wcfg, wplan, collect_stats=False))
    wide_dense = jax.jit(jax.vmap(
        lambda s: snn_apply_dense(wparams, s, wcfg)))
    assert np.array_equal(np.asarray(wide_fn(wsp)),
                          np.asarray(wide_dense(wsp))), \
        "5x5 event pipeline must be bit-exact vs the dense oracle"
    us_wide = timeit(wide_fn, wsp) / batch
    us_wide_dense = timeit(wide_dense, wsp) / batch
    emit("table5/wide_5x5", us_wide,
         f"geometry={wplan.layers[0].geometry.describe()};"
         f"event_par={[lp.event_par for lp in wplan.layers]};"
         f"vs_dense={us_wide_dense / us_wide:.2f}x")

    # async serving engine: requests submitted one at a time, flushed on
    # batch/deadline thresholds; compile excluded via warmup
    engine = CSNNEngine(params, cfg, plan,
                        CSNNServeConfig(max_batch=batch, max_delay_ms=20.0))
    engine.warmup()
    reqs = list(imgs)
    engine.run_requests(reqs)  # engine-loop warmup pass
    pre = dict(engine.stats)   # stats accumulate; report the timed run only
    t0 = time.perf_counter()
    engine.run_requests(reqs)
    us_engine = 1e6 * (time.perf_counter() - t0) / batch
    emit("table5/async_engine", us_engine,
         f"batch={batch};tile={plan.batch_tile};"
         f"flushes_full={engine.stats['flushes_full'] - pre['flushes_full']};"
         f"vs_batched={us_batched / us_engine:.2f}x")

    # continuous batching under a bursty Poisson arrival trace: the same
    # request/arrival schedule replayed through the run-to-completion
    # engine and the slot-level-refill engine (median of 3 replays each).
    # The mean inter-arrival gap is set to one flush's measured service
    # time, so the offered load sits at the knee where batches are
    # genuinely partial: the run-to-completion engine sits out flush
    # deadlines and pads whole-T pipelines while slots idle; slot-level
    # refill admits every arrival at the next t_chunk boundary and packs
    # the active slots into occupancy buckets (the always-fed PE array of
    # the paper, as a serving property).
    n_req = 18
    flush_s = batch * us_engine / 1e6  # one padded whole-T flush
    gaps = np.random.default_rng(1).exponential(scale=flush_s, size=n_req)
    arrivals = np.cumsum(gaps) - gaps[0]  # first request arrives at t=0
    trace = [jnp.asarray(xte[i % batch]) for i in range(n_req)]

    def replay(eng):
        async def _drive():
            async def one(delay, img):
                await asyncio.sleep(delay)
                return await eng.submit(img)

            async with eng:
                t0 = time.perf_counter()
                res = await asyncio.gather(
                    *[one(float(d), img) for d, img in zip(arrivals, trace)])
                dt = time.perf_counter() - t0
            return np.stack(res), dt

        return asyncio.run(_drive())

    def median_replay(eng, reps=3):
        """Median makespan over ``reps`` identical replays, plus the
        per-replay stats delta (stats accumulate across replays)."""
        pre = dict(eng.stats)
        outs = [replay(eng) for _ in range(reps)]
        logits = outs[0][0]
        assert all(np.array_equal(lg, logits) for lg, _ in outs)
        per_rep = {k: (eng.stats[k] - pre[k]) / reps for k in pre
                   if isinstance(pre[k], (int, float))}
        return logits, sorted(dt for _, dt in outs)[reps // 2], per_rep

    rtc = CSNNEngine(params, cfg, plan,
                     CSNNServeConfig(max_batch=batch, max_delay_ms=20.0))
    rtc.warmup()
    logits_rtc, dt_rtc, st_rtc = median_replay(rtc)
    us_rtc = 1e6 * dt_rtc / n_req
    emit("table5/async_engine_poisson", us_rtc,
         f"n={n_req};full={st_rtc['flushes_full']:.1f};"
         f"deadline={st_rtc['flushes_deadline']:.1f};"
         f"padded={st_rtc['padded_slots']:.1f}")

    cont = CSNNEngine(params, cfg, plan,
                      CSNNServeConfig(max_batch=batch, max_delay_ms=20.0,
                                      continuous=True, t_chunk=1))
    cont.warmup()
    logits_cont, dt_cont, st_cont = median_replay(cont)
    us_cont = 1e6 * dt_cont / n_req
    assert np.array_equal(logits_cont, logits_rtc), \
        "continuous engine must be bit-exact vs run-to-completion"
    emit("table5/continuous_poisson", us_cont,
         f"n={n_req};chunks={st_cont['chunks']:.1f};"
         f"refills={st_cont['refills']:.1f};"
         f"slot_util={cont.slot_utilization:.0%};"
         f"vs_async_engine={us_rtc / us_cont:.2f}x")

    if json_out:
        write_bench_json("table5")


if __name__ == "__main__":
    main(json_out="--json" in __import__("sys").argv[1:])
