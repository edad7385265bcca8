"""Async CSNN serving engine: flush semantics + padding + launcher smoke.

The engine's contract (serve/csnn_engine.py): requests flush when either
``max_batch`` are pending (size flush) or the oldest has waited
``max_delay_ms`` (deadline flush); partial batches pad to the plan's
``batch_tile`` with zero images, and every request's logits match
running the batched pipeline directly on the un-padded requests (to the
stated logits tolerance, ``csnn.LOGITS_RTOL``/``LOGITS_ATOL``).
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CSNNConfig, ConvSpec, FCSpec, encode_input,
                        init_params, plan_network, snn_apply_batched)
from repro.core.csnn import LOGITS_ATOL, LOGITS_RTOL
from repro.serve.csnn_engine import CSNNEngine, CSNNServeConfig

jax.config.update("jax_platform_name", "cpu")

CFG = CSNNConfig(input_hw=(8, 8),
                 layers=(ConvSpec(4), ConvSpec(4, pool=2), FCSpec(3)),
                 t_steps=3)


def _setup(seed=0, n=4, max_batch=4, delay_ms=50.0):
    params = init_params(jax.random.PRNGKey(seed), CFG)
    plan = plan_network(CFG, capacity=64, channel_block=2,
                        batch_tile=max_batch)
    engine = CSNNEngine(params, CFG, plan,
                        CSNNServeConfig(max_batch=max_batch,
                                        max_delay_ms=delay_ms))
    imgs = jnp.asarray(np.random.default_rng(seed)
                       .random((n, 8, 8, 1)).astype(np.float32))
    return params, plan, engine, imgs


class TestFlushSemantics:
    def test_size_flush_on_full_batch(self):
        """max_batch requests already queued flush immediately as one full
        batch — no deadline wait, no padding."""
        params, plan, engine, imgs = _setup(n=4, max_batch=4)
        logits = engine.run_requests(list(imgs))
        assert logits.shape == (4, 3)
        assert engine.stats["flushes_full"] == 1
        assert engine.stats["flushes_deadline"] == 0
        assert engine.stats["padded_slots"] == 0

    def test_deadline_flush_on_partial_batch(self):
        """A single request must come back after ~max_delay_ms even though
        the batch never fills."""
        params, plan, engine, imgs = _setup(n=1, max_batch=8, delay_ms=30.0)

        async def drive():
            async with engine:
                return await asyncio.wait_for(engine.submit(imgs[0]),
                                              timeout=30.0)

        logits = asyncio.run(drive())
        assert logits.shape == (3,)
        assert engine.stats["flushes_deadline"] == 1
        assert engine.stats["flushes_full"] == 0

    def test_partial_batch_pads_to_tile(self):
        """3 requests with tile 4 pad one zero slot; the padded slot never
        leaks into results."""
        params, plan, engine, imgs = _setup(n=3, max_batch=4, delay_ms=20.0)
        logits = engine.run_requests(list(imgs))
        assert logits.shape == (3, 3)
        assert engine.stats["padded_slots"] == 1
        assert engine.stats["batches"] == 1

    def test_logits_bit_exact_vs_direct_batched(self):
        """Engine results == running the planned batched pipeline directly
        on the un-padded requests (zero-pad samples are independent), to
        the stated logits tolerance: the padded batch changes the head's
        contraction shape, and with it the float reduction order."""
        params, plan, engine, imgs = _setup(n=3, max_batch=4, delay_ms=20.0)
        got = engine.run_requests(list(imgs))
        want = snn_apply_batched(params, encode_input(imgs, CFG), CFG, plan,
                                 collect_stats=False)
        np.testing.assert_allclose(got, np.asarray(want), rtol=LOGITS_RTOL,
                                   atol=LOGITS_ATOL)

    def test_multiple_waves_reuse_engine(self):
        params, plan, engine, imgs = _setup(n=4, max_batch=4)
        first = engine.run_requests(list(imgs))
        second = engine.run_requests(list(imgs))
        np.testing.assert_array_equal(first, second)
        assert engine.stats["batches"] == 2
        assert engine.stats["requests"] == 8

    def test_warmup_precompiles_tile_shapes(self):
        params, plan, engine, imgs = _setup(n=4, max_batch=4)
        compile_s = engine.warmup()
        assert compile_s > 0.0 and engine.stats["compile_s"] == compile_s

    def test_submit_outside_context_raises(self):
        params, plan, engine, imgs = _setup()
        try:
            engine.submit_nowait(imgs[0])
        except RuntimeError as e:
            assert "not running" in str(e)
        else:
            raise AssertionError("expected RuntimeError")

    def test_max_batch_must_align_to_tile(self):
        params = init_params(jax.random.PRNGKey(0), CFG)
        plan = plan_network(CFG, batch_tile=4)
        try:
            CSNNEngine(params, CFG, plan, CSNNServeConfig(max_batch=6))
        except ValueError as e:
            assert "batch_tile" in str(e)
        else:
            raise AssertionError("expected ValueError")


class TestServeLauncher:
    def test_csnn_engine_smoke(self, capsys):
        """launch/serve.py --arch csnn-paper --engine end-to-end: compile
        time reported separately, per-layer events with --verbose."""
        from repro.launch.serve import main
        rc = main(["--arch", "csnn-paper", "--smoke", "--requests", "3",
                   "--engine", "--batch-tile", "4", "--verbose",
                   "--capacity", "64", "--iters", "1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "compile:" in out and "throughput:" in out
        assert "NetworkPlan" in out
        assert "layer conv0: events=" in out
        assert "padded_slots=1" in out

    def test_csnn_batched_smoke(self, capsys):
        from repro.launch.serve import main
        rc = main(["--arch", "csnn-paper", "--smoke", "--requests", "2",
                   "--capacity", "64", "--iters", "1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "mode=batched" in out and "compile:" in out
