"""Ahead-of-time compiles for a TPU v5e chip, at the served shapes.

The TPU compiler is installed next to jax, and it compiles for a chip
that is described rather than attached.  These tests hand it every
Pallas entry point at the shapes the paper network's plan gives them, and
the jitted ``snn_apply_batched`` step at B=64: what Mosaic or XLA would
refuse on the chip (unaligned tiling, unsupported primitives, relayouts,
VMEM overflow) fails here, at no chip time.  A compile that passes says
nothing about results or speed — the chip smoke run checks those.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.  All TPU-compile tests live in this one file so
the fixture loads the library in one worker only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import csnn_paper
from repro.core.csnn import init_params, snn_apply_batched
from repro.core.plan import plan_network
from repro.kernels.event_conv import kernel as ec
from repro.kernels.threshold_pool.kernel import threshold_pool_pallas

CFG = csnn_paper.FULL
BATCH = 64


def served_plan(event_par):
    """The serving launcher's plan for the paper network at B=64, with
    queues covering the whole fmap (``event_par=None`` autotunes the
    interlaced width, 1 keeps the sequential conv unit)."""
    h, w = CFG.input_hw
    return plan_network(CFG, capacity=h * w, channel_block=8,
                        batch_tile=BATCH, event_par=event_par)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def compile_for(sharding, fn, *avals):
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in avals]
    return jax.jit(fn).lower(*args).compile()


LAYERS = [lp.name for lp in served_plan(None).layers]


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("entry", ["event_conv_pallas",
                                   "event_conv_pallas_batched",
                                   "event_conv_pallas_interlaced",
                                   "event_conv_pallas_interlaced_batched"])
def test_event_conv_compiles(one_chip, entry, layer):
    interlaced = "interlaced" in entry
    lp = served_plan(None if interlaced else 1).layer(layer)
    if interlaced:
        assert lp.event_par > 1
    hp, wp, cb = lp.vm_tile
    kh, kw = lp.geometry.window
    lead = (BATCH,) if entry.endswith("batched") else ()
    kwargs = dict(block_e=lp.block_e, interpret=False)
    if interlaced:
        kwargs["event_par"] = lp.event_par
    fn = getattr(ec, entry)
    compiled = compile_for(
        one_chip, lambda v, c, va, k: fn(v, c, va, k, **kwargs),
        jax.ShapeDtypeStruct(lead + (hp, wp, cb), jnp.float32),
        jax.ShapeDtypeStruct(lead + (lp.queue_depth, 2), jnp.int32),
        jax.ShapeDtypeStruct(lead + (lp.queue_depth,), jnp.bool_),
        jax.ShapeDtypeStruct((kh, kw, cb), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("emit", [False, True])
def test_threshold_pool_compiles(one_chip, layer, emit):
    """Each conv layer's threshold unit at its output shape, C padded to
    the lane width by the ops wrapper, H and W to the pool window; with
    ``emit`` the fused emission into the next layer's carrier, at a lossy
    capacity so the in-kernel rank truncation is compiled too."""
    plan = served_plan(None)
    lp = plan.layer(layer)
    h, w = lp.in_hw
    if lp.pool:
        h, w = h + -h % lp.pool, w + -w % lp.pool
    c = 128
    ph, pw = lp.out_hw
    compiled = compile_for(
        one_chip,
        lambda v, b, f: threshold_pool_pallas(
            v, b, f, v_t=1.0, pool=lp.pool, interpret=False,
            emit_capacity=(ph * pw) // 4 if emit else None),
        jax.ShapeDtypeStruct((h, w, c), jnp.float32),
        jax.ShapeDtypeStruct((c,), jnp.float32),
        jax.ShapeDtypeStruct((h, w, c), jnp.int8))
    assert "tpu_custom_call" in compiled.as_text()


def test_served_batched_step_compiles(one_chip):
    """The jitted serving step: ``snn_apply_batched`` over the whole
    paper network at B=64 under the autotuned (banked) plan."""
    plan = served_plan(None)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    h, w = CFG.input_hw
    spikes = jax.ShapeDtypeStruct((BATCH, CFG.t_steps, h, w,
                                   CFG.input_channels), jnp.bool_)
    leaves, tree = jax.tree_util.tree_flatten(params)

    def step(sp, *flat):
        p = jax.tree_util.tree_unflatten(tree, flat)
        return snn_apply_batched(p, sp, CFG, plan, collect_stats=False)

    compiled = compile_for(one_chip, step, spikes, *leaves)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 2**30   # fits next to the weights
