"""Continuous batching (slot-level refill) + serving-path bugfixes.

Contracts pinned here (serve/csnn_engine.py):

* the continuous engine's per-request logits are bit-exact vs the
  run-to-completion engine and match the planned batched pipeline (to
  the stated logits tolerance where the batch shapes differ) — slot
  rows are per-sample independent, so a request sees the same T-step
  computation whichever slots its neighbours occupy;
* requests admitted mid-flight (while other slots are mid-T-step) are
  counted as refills and still come back exact;
* shutdown drains cleanly: requests enqueued around ``_STOP`` (e.g.
  ``submit_nowait`` racing ``__aexit__``) are served or failed, never
  left hanging, and stop-triggered flushes are not miscounted as
  deadline flushes;
* ``CSNNEngine()`` without a serve config no longer aliases one shared
  mutable ``CSNNServeConfig`` instance across engines;
* ``run_requests([])`` returns an empty (0, n_classes) array instead of
  crashing in ``np.stack``.
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSNNConfig, ConvSpec, FCSpec, encode_input,
                        init_params, plan_network, snn_apply_batched)
from repro.core.csnn import LOGITS_ATOL, LOGITS_RTOL
from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig

jax.config.update("jax_platform_name", "cpu")

CFG = CSNNConfig(input_hw=(8, 8),
                 layers=(ConvSpec(4), ConvSpec(4, pool=2), FCSpec(3)),
                 t_steps=4)


def _setup(seed=0, n=4, **serve_kwargs):
    params = init_params(jax.random.PRNGKey(seed), CFG)
    plan = plan_network(CFG, capacity=64, channel_block=2, batch_tile=4)
    engine = CSNNEngine(params, CFG, plan, CSNNServeConfig(**serve_kwargs))
    imgs = jnp.asarray(np.random.default_rng(seed)
                       .random((n, 8, 8, 1)).astype(np.float32))
    return params, plan, engine, imgs


class TestContinuousBitExact:
    def test_wave_matches_direct_batched(self):
        params, plan, engine, imgs = _setup(
            n=7, max_batch=4, continuous=True, slots=4, t_chunk=2)
        got = engine.run_requests(list(imgs))
        want = snn_apply_batched(params, encode_input(imgs, CFG), CFG, plan,
                                 collect_stats=False)
        np.testing.assert_array_equal(got, np.asarray(want))

    def test_matches_run_to_completion_engine(self):
        params, plan, rtc, imgs = _setup(n=6, max_batch=4, max_delay_ms=20.0)
        cont = CSNNEngine(params, CFG, plan,
                          CSNNServeConfig(max_batch=4, continuous=True,
                                          slots=4, t_chunk=1))
        np.testing.assert_array_equal(cont.run_requests(list(imgs)),
                                      rtc.run_requests(list(imgs)))

    def test_refill_preserves_per_request_logits(self):
        """Requests arriving while earlier ones are mid-T-step join free
        slots (counted as refills) and still come back exact (to the
        stated logits tolerance: the 7 requests ran on a 2-slot table,
        the reference as one batch of 7).

        Deterministic staggering: the follow-up requests are submitted
        only once the first chunk is observed in flight (each chunk
        yields to the event loop while it waits out the device), so the
        admission is guaranteed to happen mid-T-step — no wall-clock
        timing involved.
        """
        params, plan, engine, imgs = _setup(
            n=7, max_batch=2, continuous=True, slots=2, t_chunk=1)
        engine.warmup()

        async def staggered():
            async with engine:
                first = engine.submit_nowait(imgs[0])
                while engine.stats["chunks"] == 0:  # first chunk in flight
                    await asyncio.sleep(0)
                rest = [engine.submit_nowait(imgs[i]) for i in range(1, 7)]
                return await asyncio.gather(first, *rest)

        got = np.stack(asyncio.run(staggered()))
        want = snn_apply_batched(params, encode_input(imgs, CFG), CFG, plan,
                                 collect_stats=False)
        np.testing.assert_allclose(got, np.asarray(want), rtol=LOGITS_RTOL,
                                   atol=LOGITS_ATOL)
        assert engine.stats["refills"] > 0
        assert engine.stats["admitted"] == engine.stats["retired"] == 7

    def test_slot_utilization_and_chunk_stats(self):
        params, plan, engine, imgs = _setup(
            n=4, max_batch=4, continuous=True, slots=4, t_chunk=2)
        engine.run_requests(list(imgs))
        assert engine.stats["chunks"] == CFG.t_steps // 2
        assert 0.0 < engine.slot_utilization <= 1.0

    def test_warmup_compiles_buckets(self):
        params, plan, engine, imgs = _setup(
            n=4, max_batch=4, continuous=True, slots=4)
        assert engine.warmup() > 0.0
        assert engine._buckets == [1, 2, 4]


class TestShutdownDrain:
    @pytest.mark.parametrize("continuous", [False, True])
    def test_submits_racing_aexit_are_not_lost(self, continuous):
        """Futures for requests enqueued just before (or racing) _STOP must
        resolve — previously they hung forever."""
        params, plan, engine, imgs = _setup(
            n=3, max_batch=4, max_delay_ms=500.0, continuous=continuous)

        async def race():
            async with engine:
                return [engine.submit_nowait(imgs[i]) for i in range(3)]

        futs = asyncio.run(race())
        assert all(f.done() for f in futs)
        served = [f for f in futs if f.exception() is None]
        assert served, "drain must serve (or explicitly fail) the leftovers"
        want = np.asarray(snn_apply_batched(
            params, encode_input(imgs, CFG), CFG, plan, collect_stats=False))
        for i, f in enumerate(futs):
            if f.exception() is None:
                np.testing.assert_allclose(np.asarray(f.result()), want[i],
                                           rtol=LOGITS_RTOL, atol=LOGITS_ATOL)

    def test_stop_flush_not_counted_as_deadline(self):
        """A stop-triggered partial flush increments flushes_stop, not
        flushes_deadline (which used to misreport)."""
        params, plan, engine, imgs = _setup(n=2, max_batch=8,
                                            max_delay_ms=10_000.0)

        async def drive():
            async with engine:
                futs = [engine.submit_nowait(imgs[i]) for i in range(2)]
                return futs

        futs = asyncio.run(drive())
        assert all(f.done() and f.exception() is None for f in futs)
        assert engine.stats["flushes_stop"] >= 1
        assert engine.stats["flushes_deadline"] == 0

    def test_concurrent_submits_during_shutdown(self):
        """Submitters overlapping __aexit__ either get served or see the
        engine-stopped error; nothing hangs."""
        params, plan, engine, imgs = _setup(n=4, max_batch=4,
                                            max_delay_ms=1.0)
        results = []

        async def drive():
            async def submitter(i):
                await asyncio.sleep(0.001 * i)
                try:
                    results.append(await engine.submit(imgs[i % 4]))
                except RuntimeError:
                    results.append(None)

            async with engine:
                tasks = [asyncio.create_task(submitter(i)) for i in range(4)]
                await asyncio.sleep(0.02)
            await asyncio.gather(*tasks, return_exceptions=True)

        asyncio.run(asyncio.wait_for(drive(), timeout=30.0))
        assert len(results) == 4


class TestFlusherCrashSafety:
    @pytest.mark.parametrize("continuous", [False, True])
    def test_bad_request_fails_future_instead_of_hanging(self, continuous):
        """A request that crashes the flusher loop (here: wrong image
        geometry) must surface as an exception on the future / context
        exit, never as an eternal hang."""
        params, plan, engine, _ = _setup(max_batch=4, max_delay_ms=5.0,
                                         continuous=continuous)
        bad = jnp.zeros((10, 10, 1))  # engine is configured for 8x8

        async def drive():
            fut = None
            try:
                async with engine:
                    fut = engine.submit_nowait(bad)
                    await fut
            except Exception:
                pass
            return fut

        fut = asyncio.run(asyncio.wait_for(drive(), timeout=60.0))
        assert fut is not None and fut.done()
        assert fut.exception() is not None


class TestServeConfigDefault:
    def test_engines_do_not_share_default_config(self):
        params = init_params(jax.random.PRNGKey(0), CFG)
        plan = plan_network(CFG, batch_tile=8)
        e1 = CSNNEngine(params, CFG, plan)
        e2 = CSNNEngine(params, CFG, plan)
        assert e1.serve_cfg is not e2.serve_cfg
        e1.serve_cfg.max_batch = 64
        assert e2.serve_cfg.max_batch == 8
        assert CSNNServeConfig().max_batch == 8

    def test_default_signature_is_none(self):
        import inspect
        sig = inspect.signature(CSNNEngine.__init__)
        assert sig.parameters["serve_cfg"].default is None


class TestEmptyRequests:
    @pytest.mark.parametrize("continuous", [False, True])
    def test_run_requests_empty(self, continuous):
        params, plan, engine, _ = _setup(max_batch=4, continuous=continuous)
        out = engine.run_requests([])
        assert out.shape == (0, 3)
        assert engine.stats["requests"] == 0
