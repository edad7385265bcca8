"""Offline classification: one jitted call per batch, back to back.

Set-up makes ``batches`` distinct batches of ``batch`` rows from the
seed and puts them on the device, makes the weights (calibrated
on the first 256 rows), and compiles the call with one first call: every
batch has the one shape.  The call is the program's entry as an offline
user makes it: ``snn_apply_batched``, with the m-TTFS encode inside the
same jitted call when the input is images.

The window cycles through the batches, with one call in flight while the
host waits for the one before, until ``seconds`` have passed; then it
waits for the last.  ``samples_per_s`` is every sample completed over the
time from the first dispatch to the last completion.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import numpy as np

from benchmarks.chip import inputs, program


@dataclass
class State:
    fn: object
    params: dict
    batches: list
    host: object
    batch: int
    kind: str


def make_call(net, plan, encode: bool):
    """The offline user's call, ``(params, batch) -> logits``."""
    from repro.core.csnn import encode_input, snn_apply_batched

    def call(p, x):
        sp = encode_input(x, net) if encode else x
        return snn_apply_batched(p, sp, net, plan, collect_stats=False)
    return call


def prepare(ctx) -> State:
    import jax

    cfg, mix = ctx.cell.cfg, ctx.cell.mix
    if ctx.cell.chips != 1:
        raise ValueError("offline_batch drives one chip")
    kind = mix["input"]
    b = mix["batch"]
    host = inputs.make(mix, cfg, mix["batches"] * b, ctx.seed_for("inputs"))
    n_cal = cfg["conversion"]["calibration_inputs"]
    c_in = inputs.CHANNELS[kind]
    params = ctx.reference.make_params(
        ctx.key_for("weights"), inputs.ann_input(kind, host[:n_cal], cfg),
        cfg, c_in)
    net = program.csnn_config(cfg, c_in)
    plan = program.plan(net, cfg, b)

    call = make_call(net, plan, kind == "images")
    batches = [jax.device_put(host[k * b:(k + 1) * b], ctx.devices[0])
               for k in range(mix["batches"])]
    fn = jax.jit(call)
    t = time.perf_counter()
    jax.block_until_ready(fn(params, batches[0]))  # every batch: one shape
    ctx.log(f"compile + first call {time.perf_counter() - t:.2f} s "
            f"(batch {b})")
    return State(fn=fn, params=params, batches=batches, host=host, batch=b,
                 kind=kind)


def measure(state: State, ctx, seconds: float, tracer=None) -> dict:
    fn, params, batches, b = state.fn, state.params, state.batches, state.batch
    span = ctx.trace_span(seconds)
    ctx.mark_setup_done()
    outs, pending, window = [], deque(), {}
    i = 0
    t0 = time.perf_counter()
    while True:
        k = i % len(batches)
        pending.append((k, fn(params, batches[k])))
        i += 1
        if len(pending) > 1:
            k0, out = pending.popleft()
            out.block_until_ready()
            outs.append((k0, out))
        t = time.perf_counter() - t0
        if tracer is not None:
            if "start" not in window and t >= span[0]:
                tracer.start()
                window["start"] = len(outs)
            elif ("start" in window and "stop" not in window
                  and t >= span[1]):
                window["stop"] = len(outs)
                tracer.stop()
        if t >= seconds and (tracer is None or "stop" in window):
            break
    while pending:
        k0, out = pending.popleft()
        out.block_until_ready()
        outs.append((k0, out))
    elapsed = time.perf_counter() - t0
    counters = {}
    if "stop" in window:
        calls = window["stop"] - window["start"]
        counters = {"calls": calls, "samples": calls * b}
    which = np.asarray([k for k, _ in outs])
    logits = np.concatenate([np.asarray(o) for _, o in outs])
    rows = np.concatenate([np.arange(k * b, (k + 1) * b) for k in which])
    return {
        "e2e": {"samples_per_s": len(outs) * b / elapsed},
        "attempted": len(outs) * b, "unanswered": 0,
        "counters": counters,
        "answers": logits,
        "answer_input": rows,
        "distinct": state.host,
        "kind": state.kind,
    }


def release(state: State) -> None:
    state.fn = state.batches = None
