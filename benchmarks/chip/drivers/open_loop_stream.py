"""Open-loop serving of DVS windows through the continuous stream engine.

Set-up makes a pool of ``pool`` traces from the seed, the weights
(calibrated on the pool's first 256), and a ``CSNNEngine(continuous=True,
stream=True)`` with the cell's ``slots``; it compiles every occupancy
bucket (``warmup``) and serves ``2 x slots`` pool traces once so that the
host paths are warm too.

The window offers ``rate_rps x seconds`` requests on a fixed schedule
through ``submit_nowait``, whatever the engine's progress (open loop).
Every seed gets the same set of gaps, the quantiles of an exponential
distribution at the mix's rate, in an order of its own, and its own
sequence of pool traces.  A request's latency runs from when it was due
to when its logits came back; a request still unanswered a minute after
the window counts as unanswered.  How late the generator sent each
request is reported beside it, with when in the window the latest was
due.  Every answered request goes to the check.
"""
from __future__ import annotations

import asyncio
import sys
from dataclasses import dataclass
from functools import partial

import numpy as np

from benchmarks.chip import inputs, program

ANSWER_WAIT_S = 60.0


@dataclass
class State:
    engine: object
    pool: list
    params: dict


def prepare(ctx) -> State:
    from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig

    cfg, mix = ctx.cell.cfg, ctx.cell.mix
    slots = ctx.cell.own["slots"]
    pool = inputs.make(mix, cfg, mix["pool"], ctx.seed_for("pool"))
    n_cal = cfg["conversion"]["calibration_inputs"]
    c_in = inputs.CHANNELS[mix["input"]]
    params = ctx.reference.make_params(
        ctx.key_for("weights"), inputs.ann_input(mix["input"], pool[:n_cal],
                                                 cfg),
        cfg, c_in)
    net = program.csnn_config(cfg, c_in)
    engine = CSNNEngine(
        params, net, program.plan(net, cfg, slots, ingest=True),
        CSNNServeConfig(max_batch=slots, continuous=True, stream=True))
    ctx.log(f"engine warmup (compile) {engine.warmup():.2f} s")
    return State(engine=engine, pool=pool, params=params)


def schedule(rate: float, seconds: float, seed: int):
    """(due offsets in seconds, sorted) for ``rate x seconds`` requests."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(seed).permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


async def _serve(state: State, ctx, seconds: float, tracer, rate: float):
    engine, pool = state.engine, state.pool
    loop = asyncio.get_running_loop()
    due = schedule(rate, seconds, ctx.seed_for("arrivals"))
    n = due.size
    order = np.random.default_rng(ctx.seed_for("order")).integers(
        0, len(pool), n)
    done_t = np.full(n, np.nan)
    errored = np.zeros(n, bool)
    logits = [None] * n
    late = np.zeros(n)

    def on_done(i, fut):
        if fut.cancelled():
            return
        done_t[i] = loop.time()
        if fut.exception() is not None:
            errored[i] = True
        else:
            logits[i] = fut.result()

    async with engine:
        warm = [engine.submit_nowait(pool[j])
                for j in range(2 * ctx.cell.own["slots"])]
        await asyncio.wait_for(asyncio.gather(*warm), ANSWER_WAIT_S)
        ctx.mark_setup_done()
        window = {}
        t_trace = ctx.trace_span(seconds)
        t0 = loop.time() + 0.005
        futs = []
        for i in range(n):
            target = t0 + due[i]
            delay = target - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            now = loop.time()
            late[i] = now - target
            if tracer is not None:
                _trace_tick(tracer, engine, window, now - t0, t_trace)
            fut = engine.submit_nowait(pool[order[i]])
            fut.add_done_callback(partial(on_done, i))
            futs.append(fut)
        if tracer is not None:
            while "stop" not in window:
                await asyncio.sleep(0.01)
                _trace_tick(tracer, engine, window, loop.time() - t0,
                            t_trace)
        # a minute past the close: the window's end, or the last send where
        # stopping the profiler held the loop past it
        close = max(t0 + seconds, loop.time())
        await asyncio.wait(futs, timeout=close + ANSWER_WAIT_S - loop.time())
        elapsed = loop.time() - t0
    lat = np.where(np.isnan(done_t), loop.time(), done_t) - (t0 + due)
    return lat, due, done_t, errored, logits, late, order, window, elapsed


def _trace_tick(tracer, engine, window, t, span):
    start, stop = span
    if "start" not in window and t >= start:
        tracer.start()
        window["start"] = dict(engine.stats)
    elif "start" in window and "stop" not in window and t >= stop:
        window["stop"] = dict(engine.stats)
        tracer.stop()


def measure(state: State, ctx, seconds: float, tracer=None,
            rate: float | None = None) -> dict:
    rate = ctx.cell.mix["rate_rps"] if rate is None else rate
    lat, due_s, done_t, errored, logits, late, order, window, elapsed = \
        asyncio.run(_serve(state, ctx, seconds, tracer, rate))
    n = lat.size
    bad = np.isnan(done_t) | errored
    ok = np.flatnonzero(~bad)
    lat_ms = lat * 1e3
    worst = int(np.argmax(late))
    print(f"generator lateness: p50 {float(np.percentile(late, 50)) * 1e3!r} ms, "
          f"p95 {float(np.percentile(late, 95)) * 1e3!r} ms, max "
          f"{float(late.max()) * 1e3!r} ms, due {float(due_s[worst])!r} s "
          f"into the window; {int((late > 0.05).sum())} sent over 50 ms "
          f"late, of {n} requests at {rate!r} req/s", file=sys.stderr)
    print(f"served {ok.size}/{n}; unanswered {int(bad.sum())}; window and "
          f"drain {elapsed!r} s", file=sys.stderr)
    counters = {}
    if "stop" in window:
        a, b = window["start"], window["stop"]
        counters = {k: b[k] - a[k] for k in
                    ("chunks", "slot_steps_busy", "slot_steps_total",
                     "retired", "admitted")}
    if not ok.size:
        raise RuntimeError(f"no request of {n} was answered")
    used, where = np.unique(order[ok], return_inverse=True)
    return {
        "e2e": {"latency_p50_ms": float(np.percentile(lat_ms, 50)),
                "latency_p95_ms": float(np.percentile(lat_ms, 95))},
        "attempted": int(n), "unanswered": int(bad.sum()),
        "counters": counters,
        "latency_ms": lat_ms, "due_s": due_s,
        "answers": np.stack([logits[i] for i in ok]),
        "answer_input": where,
        "distinct": [state.pool[j] for j in used],
        "kind": ctx.cell.mix["input"],
    }


def release(state: State) -> None:
    state.engine = None
