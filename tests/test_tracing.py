"""The program's own tracing (serve/csnn_engine.py, core/csnn.py,
core/scheduler.py):

* the stream engine's host spans, read back from a profiled CPU run with
  ``jax.profiler.ProfileData``: every round runs admit, pack, dispatch,
  backlog, wait and readout in that order, each request's ``submit`` and
  ``encode`` spans carry its ``rid``, submits from other coroutines nest
  inside ``engine.wait`` or ``engine.idle``, and the spans leave almost
  none of the loop thread's time unnamed;
* the named device scopes in the compiled HLO's ``op_name`` metadata of
  the chunk step, the offline call and the engine's bucket step;
* the admission-wait histogram: it counts every admitted request, its
  interpolated p95 lies within one bucket of the exact one, and a
  snapshot of ``engine.stats`` does not move with the engine.
"""
import asyncio
import bisect
import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSNNConfig, ConvSpec, FCSpec, encode_input,
                        init_params, plan_network, snn_apply_batched)
from repro.core.aeq import StreamState
from repro.core.csnn import init_state, snn_step_chunk
from repro.data.dvs import dvs_moving_edges
from repro.serve import csnn_engine
from repro.serve.csnn_engine import (ADMIT_WAIT_EDGES_MS, CSNNEngine,
                                     CSNNServeConfig, wait_quantile_ms)

jax.config.update("jax_platform_name", "cpu")

CFG = CSNNConfig(input_hw=(12, 12), input_channels=2,
                 layers=(ConvSpec(8), ConvSpec(8, pool=3), FCSpec(10)),
                 t_steps=4)
ROUND = ["engine.admit", "engine.pack", "engine.dispatch", "engine.backlog",
         "engine.wait", "engine.readout"]
N_REQ = 10


def _engine(slots=4, t_chunk=1, **plan_kwargs):
    params = init_params(jax.random.PRNGKey(0), CFG)
    plan = plan_network(CFG, capacity=144, channel_block=8,
                        batch_tile=slots, event_par=None, ingest=True,
                        **plan_kwargs)
    engine = CSNNEngine(params, CFG, plan, CSNNServeConfig(
        max_batch=slots, continuous=True, stream=True, t_chunk=t_chunk))
    return engine, params, plan


def _traces(n=N_REQ):
    return dvs_moving_edges(n, CFG.t_steps, CFG.input_hw, seed=3)[0]


async def _staggered(engine, traces, gap_s=0.002):
    """Submit from a coroutine of its own, ``gap_s`` apart, so that the
    submits land while the engine waits for a chunk or for work."""
    async with engine:
        futs = []
        for tr in traces:
            futs.append(engine.submit_nowait(tr))
            await asyncio.sleep(gap_s)
        return await asyncio.gather(*futs)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiled run of a tiny stream engine: (engine, host lines),
    each line a list of (name, start_ns, end_ns, stats) on one thread."""
    from jax.profiler import ProfileData
    engine, _, _ = _engine()
    engine.warmup()
    logdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        asyncio.run(_staggered(engine, _traces()))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(ev.name.split("#")[0], ev.start_ns,
                          ev.start_ns + ev.duration_ns, dict(ev.stats))
                         for ev in line.events
                         if ev.name.startswith("engine.")]
                if spans:
                    lines.append(sorted(spans, key=lambda s: (s[1], -s[2])))
    return engine, lines


def _loop_thread(lines):
    (loop,) = [ln for ln in lines
               if any(n == "engine.admit" for n, *_ in ln)]
    return loop


def _top_level(spans):
    """The spans no other span of the list covers."""
    out, end = [], -math.inf
    for span in spans:
        if span[1] >= end:
            out.append(span)
            end = span[2]
    return out


def test_every_round_runs_its_phases_in_order(profiled):
    engine, lines = profiled
    top = _top_level(_loop_thread(lines))
    names = [n for n, *_ in top]
    rounds = [i for i, n in enumerate(names) if n == "engine.dispatch"]
    assert len(rounds) == engine.stats["chunks"] > 0
    for i in rounds:
        assert names[i - 2:i + 4] == ROUND, names[i - 2:i + 4]
    # between rounds the thread only admits or waits for work
    assert set(names) <= set(ROUND) | {"engine.idle", "engine.submit"}


def test_submit_and_encode_share_the_request_number(profiled):
    engine, lines = profiled
    spans = [s for ln in lines for s in ln]
    submits = sorted(st["rid"] for n, _, _, st in spans
                     if n == "engine.submit")
    encodes = sorted(st["rid"] for n, _, _, st in spans
                     if n == "engine.encode")
    assert submits == encodes == list(range(N_REQ))
    assert engine.stats["requests"] == N_REQ


def test_submits_nest_in_wait_or_idle(profiled):
    _, lines = profiled
    loop = _loop_thread(lines)
    rounds = [sp for sp in _top_level(loop) if sp[0] != "engine.submit"]
    nested = 0
    for n, s, e, _ in loop:
        if n != "engine.submit" or s < rounds[0][1]:
            continue  # before the engine's first round
        (parent,) = [p for p in rounds if p[1] <= s and e <= p[2]]
        assert parent[0] in ("engine.wait", "engine.idle")
        nested += 1
    assert nested > 0


def test_spans_tile_the_loop_thread(profiled):
    _, lines = profiled
    top = [s for s in _top_level(_loop_thread(lines))
           if s[0] != "engine.submit"]
    run = top[-1][2] - top[0][1]
    covered = sum(e - s for _, s, e, _ in top)
    # what is left is the few microseconds between one span's exit and
    # the next one's entry
    assert (run - covered) / run < 0.02, (run, covered)


# ----------------------------------------------------- device scopes
UNIT = re.compile(r"encode|head|conv\d+|compact|conv_unit|threshold|handoff"
                  r"|engine\.\w+")


def _scopes(compiled_text):
    """Every scope path (named segments of an op_name, in order) of the
    compiled HLO text."""
    paths = set()
    for op_name in re.findall(r'op_name="([^"]+)"', compiled_text):
        for path in op_name.split(";"):
            segs = [s for s in path.split("/") if UNIT.fullmatch(s)]
            paths.add("/".join(segs))
    return paths


def _has(paths, layer, unit):
    return any(re.search(rf"(^|/){layer}/(.*/)?{unit}(/|$)", p)
               for p in paths)


# the units each variant's conv layer runs: the dense conv and the fused
# handoff consume their input without a compaction pass
UNITS = {"dense-mxu": ("conv_unit", "threshold"),
         "fused-handoff": ("conv_unit", "threshold")}


def _expect_conv_units(paths, plan):
    """Each conv layer's scopes hold the units of its resolved variant,
    and a ``dense-mxu`` layer compacts nothing."""
    for lp in plan.layers:
        variant = lp.resolve_variant()
        for unit in UNITS.get(variant, ("compact", "conv_unit",
                                        "threshold")):
            assert _has(paths, lp.name, unit), (lp.name, variant, unit,
                                                sorted(paths))
        if variant == "dense-mxu":
            assert not _has(paths, lp.name, "compact"), (lp.name,
                                                         sorted(paths))


@pytest.mark.parametrize("variant", [None, "fused-handoff", "banked-jax"])
def test_chunk_step_scopes(variant):
    _, params, plan = _engine(variant=variant)
    chunk = StreamState(banks=jnp.zeros((2, 1, 2, 9, 4, 4), jnp.bool_))
    state = init_state(params, CFG, plan, 2)
    fn = jax.jit(lambda st, sp: snn_step_chunk(params, st, sp, CFG, plan))
    paths = _scopes(fn.lower(state, chunk).compile().as_text())
    _expect_conv_units(paths, plan)
    if variant is None:  # the streamed conv0 keeps its banks
        assert [lp.resolve_variant() for lp in plan.layers] == [
            "banked-jax", "dense-mxu"]
    elif variant == "fused-handoff":
        # conv0 builds its own queues and conv1's as handoff carriers
        assert _has(paths, "conv0", "handoff")
    assert "head" in paths


def test_offline_call_scopes():
    cfg = CSNNConfig(input_hw=(12, 12),
                     layers=(ConvSpec(8), ConvSpec(8, pool=3), ConvSpec(4),
                             FCSpec(10)), t_steps=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = plan_network(cfg, capacity=144, channel_block=4, batch_tile=2,
                        event_par=None)
    fn = jax.jit(lambda p, x: snn_apply_batched(
        p, encode_input(x, cfg), cfg, plan, collect_stats=False))
    paths = _scopes(fn.lower(params, jnp.zeros((2, 12, 12, 1)))
                    .compile().as_text())
    # the 4x4 conv2's queue is too shallow for event parallelism
    assert [lp.resolve_variant() for lp in plan.layers] == [
        "dense-mxu", "dense-mxu", "sequential"]
    _expect_conv_units(paths, plan)
    assert {"encode", "head"} <= paths


def test_bucket_step_scopes():
    engine, _, plan = _engine()
    state = init_state(engine._params, CFG, plan, 4)
    idx = np.full(2, 4, np.int32)
    chunk = StreamState(banks=jnp.zeros((2, 1, 2, 9, 4, 4), jnp.bool_))
    paths = _scopes(engine._step.lower(state, idx, chunk,
                                       np.zeros(2, bool)).compile().as_text())
    _expect_conv_units(paths, plan)
    assert {"engine.gather", "engine.reset", "engine.scatter",
            "head"} <= paths


# -------------------------------------------- admission-wait histogram
def test_wait_buckets_cover_the_range_at_under_ten_percent():
    edges = np.asarray(ADMIT_WAIT_EDGES_MS)
    assert edges[0] == pytest.approx(0.05) and edges[-1] >= 60e3
    assert np.all(edges[1:] / edges[:-1] <= 1.1)


def test_wait_quantile_interpolates_inside_its_bucket():
    e = ADMIT_WAIT_EDGES_MS
    counts = [0] * (len(e) + 1)
    assert math.isnan(wait_quantile_ms(counts, 0.95))
    counts[1] = 4  # four waits in [e[0], e[1])
    assert wait_quantile_ms(counts, 0.5) == pytest.approx((e[0] + e[1]) / 2)
    counts[-1] = 4  # and four of 60 s or more: read as the top edge
    assert wait_quantile_ms(counts, 1.0) == e[-1]


def test_wait_histogram_counts_every_admission(monkeypatch):
    """A scripted run: 3 bursts of requests into 2 slots, so most wait
    several rounds.  The exact waits are the values the engine bins."""
    engine, _, _ = _engine(slots=2)
    engine.warmup()
    exact = []
    real = bisect.bisect_right

    def record(edges, x):
        if edges is ADMIT_WAIT_EDGES_MS:
            exact.append(x)
        return real(edges, x)

    monkeypatch.setattr(csnn_engine.bisect, "bisect_right", record)
    before = dict(engine.stats)
    traces = _traces(12)

    async def bursts():
        async with engine:
            futs = []
            for k in range(0, 12, 4):
                futs += [engine.submit_nowait(tr) for tr in traces[k:k + 4]]
                await asyncio.sleep(0.01)
            return await asyncio.gather(*futs)

    asyncio.run(bursts())
    hist = engine.stats["admit_wait_hist"]
    # the snapshot taken before the run did not move with the engine
    assert sum(before["admit_wait_hist"]) == 0
    assert sum(hist) == len(exact) == engine.stats["admitted"] == 12
    got = wait_quantile_ms(hist, 0.95)
    want = float(np.percentile(exact, 95))
    assert abs(real(ADMIT_WAIT_EDGES_MS, got)
               - real(ADMIT_WAIT_EDGES_MS, want)) <= 1, (got, want)
