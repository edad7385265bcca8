"""Serving launcher: batched generation through repro.serve.engine, plus
batched event-driven CSNN inference (the paper workload) as its own arch.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --requests 4 --new-tokens 16

  PYTHONPATH=src python -m repro.launch.serve --arch csnn-paper --smoke \
      --requests 8

  # async micro-batching engine with plan + per-layer event counts:
  PYTHONPATH=src python -m repro.launch.serve --arch csnn-paper --smoke \
      --requests 8 --engine --verbose

  # continuous batching: slot-level refill instead of run-to-completion
  # flushes, with a slot-utilization report:
  PYTHONPATH=src python -m repro.launch.serve --arch csnn-paper --smoke \
      --requests 8 --engine --continuous --t-chunk 1

  # streaming DVS ingestion: requests are raw (t, y, x, polarity) event
  # traces (synthetic moving-edge scenes) admitted bank-scatter-style
  # with no per-frame encode or sort (implies --engine --continuous):
  PYTHONPATH=src python -m repro.launch.serve --arch csnn-paper --smoke \
      --requests 8 --stream
"""
import argparse
import sys
import time


def serve_csnn(args) -> int:
    """Serve a batch of image requests through the planned event pipeline.

    Default mode runs one pre-built batch through ``snn_apply_batched``;
    ``--engine`` routes the same requests through the async micro-batching
    ``CSNNEngine`` (enqueue individually, flush on batch/deadline);
    ``--stream`` serves raw DVS event traces (synthetic moving-edge
    scenes, 2-polarity) through the continuous engine's streaming
    admission — no per-frame threshold encode, no sort.  Compile time is
    measured separately from steady state (the first timed call used to
    include retrace on shape change); ``--verbose`` prints the derived
    NetworkPlan and per-layer event counts.
    """
    import statistics
    from dataclasses import replace

    import jax
    import jax.numpy as jnp

    from repro.configs import csnn_paper, csnn_wide
    from repro.core.csnn import encode_input, init_params, snn_apply_batched
    from repro.core.plan import plan_network

    # --stream implies --continuous implies --engine
    args.continuous = args.continuous or args.stream
    args.engine = args.engine or args.continuous
    mod = csnn_wide if args.arch == "csnn-wide" else csnn_paper
    cfg = mod.SMOKE if args.smoke else mod.FULL
    if args.stream:  # polarity (OFF/ON) maps onto the 2-channel input path
        cfg = replace(cfg, input_channels=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    h, w = cfg.input_hw
    if args.stream:
        from repro.data.dvs import dvs_moving_edges
        reqs, _ = dvs_moving_edges(args.requests, cfg.t_steps, (h, w),
                                   seed=1)
        n_events = sum(tr.shape[0] for tr in reqs)
    else:
        reqs = list(jax.random.uniform(
            jax.random.PRNGKey(1), (args.requests, h, w, cfg.input_channels)))
    batch_tile = args.batch_tile
    event_par = (None if args.event_par < 0
                 else args.event_par if args.event_par else 1)
    # tuning happens here, before any request is admitted — measured
    # micro-benchmarks (--tune measured) or a plan-cache load (--tune
    # cached) are warmup work, never hot-path work
    t0 = time.perf_counter()
    plan = plan_network(cfg, capacity=args.capacity,
                        channel_block=args.channel_block,
                        batch_tile=batch_tile, event_par=event_par,
                        ingest=args.stream, tune=args.tune)
    if args.tune != "analytic":
        print(f"tune: mode={args.tune} plan derived in "
              f"{time.perf_counter() - t0:.2f} s")
    if args.verbose:
        print(plan)

    if args.engine:
        from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig
        max_batch = -(-args.requests // batch_tile) * batch_tile
        engine = CSNNEngine(params, cfg, plan,
                            CSNNServeConfig(max_batch=max_batch,
                                            max_delay_ms=args.deadline_ms,
                                            continuous=args.continuous,
                                            t_chunk=args.t_chunk,
                                            stream=args.stream))
        compile_s = engine.warmup()
        times = []
        for _ in range(max(args.iters, 1)):
            t0 = time.perf_counter()
            logits = jnp.asarray(engine.run_requests(reqs))
            times.append(time.perf_counter() - t0)
        dt = statistics.median(times)
        steady = f"{args.requests / dt:.1f} samples/s (median of {len(times)})"
        if args.continuous:
            extra = (f"engine: chunks={engine.stats['chunks']} "
                     f"admitted={engine.stats['admitted']} "
                     f"refills={engine.stats['refills']} "
                     f"slot_utilization={engine.slot_utilization:.0%} "
                     f"wait_ms_max={engine.stats['wait_ms_max']:.1f} "
                     f"deadline_misses={engine.stats['deadline_misses']}")
            if args.stream:
                extra += (f"\nstream: events={n_events} "
                          f"({n_events / dt:.0f} events/s admitted)")
        else:
            extra = (f"engine: batches={engine.stats['batches']} "
                     f"full={engine.stats['flushes_full']} "
                     f"deadline={engine.stats['flushes_deadline']} "
                     f"padded_slots={engine.stats['padded_slots']}")
    else:
        fn = jax.jit(lambda s: snn_apply_batched(
            params, s, cfg, plan, collect_stats=False))
        spikes = encode_input(jnp.stack(reqs), cfg)
        t0 = time.perf_counter()
        logits = jax.block_until_ready(fn(spikes))
        compile_s = time.perf_counter() - t0  # first call: compile + run
        times = []
        for _ in range(max(args.iters, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(spikes))
            times.append(time.perf_counter() - t0)
        dt = statistics.median(times)
        steady = f"{args.requests / dt:.1f} samples/s (median of {len(times)})"
        extra = ""

    preds = jnp.argmax(logits, axis=-1)
    for i, p in enumerate(preds.tolist()):
        print(f"req {i}: class {p}")
    print(f"compile: {compile_s:.2f} s (excluded from throughput)")
    mode = ("stream" if args.stream
            else "continuous" if args.engine and args.continuous
            else "engine" if args.engine else "batched")
    print(f"throughput: {steady} "
          f"(batch={args.requests}, T={cfg.t_steps}, "
          f"capacity={args.capacity}, channel_block={args.channel_block}, "
          f"mode={mode})")
    if extra:
        print(extra)
    if args.verbose and not args.stream:
        spikes = encode_input(jnp.stack(reqs), cfg)
        _, stats = jax.jit(lambda s: snn_apply_batched(
            params, s, cfg, plan, collect_stats=True))(spikes)
        for lp, st in zip(plan.layers, stats):
            events = int(jnp.sum(st.in_spike_counts))
            peak = int(jnp.max(st.in_spike_counts))
            print(f"layer {lp.name}: events={events} peak_queue={peak} "
                  f"capacity={lp.capacity} block_e={int(st.event_block)}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--capacity", type=int, default=256,
                    help="AEQ depth per queue (csnn-paper only)")
    ap.add_argument("--channel-block", type=int, default=8,
                    help="output channels per MemPot tile (csnn-paper only)")
    ap.add_argument("--event-par", type=int, default=-1,
                    help="interlaced event-parallel width for csnn plans: "
                         "-1 autotunes per layer (default), 0/1 keeps the "
                         "sequential conv unit, >1 pins the width")
    ap.add_argument("--tune", default="analytic",
                    choices=("analytic", "measured", "cached"),
                    help="plan derivation: closed-form VMEM model "
                         "(analytic), measured micro-benchmark winners "
                         "persisted to the plan cache (measured), or a "
                         "cache load falling back to measuring on a miss "
                         "(cached; REPRO_PLAN_CACHE overrides the path)")
    ap.add_argument("--engine", action="store_true",
                    help="route requests through the async micro-batching "
                         "CSNNEngine (csnn-paper only)")
    ap.add_argument("--continuous", action="store_true",
                    help="with --engine: continuous batching — slot-level "
                         "refill between t_chunk steps instead of "
                         "run-to-completion flushes")
    ap.add_argument("--stream", action="store_true",
                    help="serve raw DVS event traces through the "
                         "continuous engine's streaming admission "
                         "(implies --engine --continuous; csnn-paper only)")
    ap.add_argument("--t-chunk", type=int, default=0,
                    help="continuous-mode refill granularity in time steps "
                         "(0 = plan default; snapped to a divisor of T)")
    ap.add_argument("--batch-tile", type=int, default=8,
                    help="engine pads partial batches to this multiple")
    ap.add_argument("--deadline-ms", type=float, default=10.0,
                    help="engine flush deadline for partial batches")
    ap.add_argument("--iters", type=int, default=3,
                    help="steady-state timing iterations")
    ap.add_argument("--verbose", action="store_true",
                    help="print the NetworkPlan and per-layer event counts")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.arch in ("csnn-paper", "csnn-wide"):
        return serve_csnn(args)

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS
    from repro.models.registry import build_model
    from repro.serve.engine import Engine, ServeConfig

    cfg = ARCHS[args.arch].SMOKE if args.smoke else ARCHS[args.arch].FULL
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    max_seq = args.prompt_len + args.new_tokens + 8
    engine = Engine(model, params, max_seq=max_seq,
                    cfg=ServeConfig(max_new_tokens=args.new_tokens,
                                    temperature=args.temperature))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.requests, args.prompt_len), 0,
                                 cfg.vocab, jnp.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["vision_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (args.requests, cfg.n_vision_tokens, cfg.d_model))
        max_seq += cfg.n_vision_tokens
        engine.max_seq = max_seq
    if cfg.family == "encdec":
        extra["frames"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (args.requests, cfg.enc_frames, cfg.d_model))
    out = engine.generate(prompts, jax.random.PRNGKey(3), extra=extra)
    for i, row in enumerate(out):
        print(f"req {i}: {row.tolist()[args.prompt_len:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
