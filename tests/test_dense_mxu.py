"""The ``dense-mxu`` conv unit (core/scheduler.py ``_run_chunk_dense``).

* the selection rule (``LayerPlan.runs_dense``): an unpinned
  ``event_par > 1`` layer on the jax backend takes the dense conv only
  with a lossless queue, the float32 datapath and no raw-stream
  ingestion;
* results: spike maps equal the dense oracle's wherever the oracle's
  membrane stayed more than 1e-5 from ``v_t``; logits within
  ``LOGITS_RTOL/ATOL`` of ``snn_apply_dense`` and of the pinned
  ``banked-jax`` plan, with the same per-layer event counts;
* chunked against monolithic, ``vmap(snn_apply)`` against
  ``snn_apply_batched``, and a pinned ``dense-mxu`` streamed input layer
  against the same events binned into frames.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aeq import StreamState
from repro.core.csnn import (LOGITS_ATOL, LOGITS_RTOL, CSNNConfig, ConvSpec,
                             FCSpec, encode_input, init_params, init_state,
                             snn_apply, snn_apply_batched, snn_apply_dense,
                             snn_readout, snn_step_chunk)
from repro.core.plan import plan_conv_layer, plan_network
from repro.core.scheduler import (init_conv_carry,
                                  run_conv_layer_batched_chunk)
from repro.data.dvs import dvs_moving_edges, events_to_banks, events_to_frames

jax.config.update("jax_platform_name", "cpu")

CFG = CSNNConfig(input_hw=(10, 10),
                 layers=(ConvSpec(8), ConvSpec(8, pool=3), ConvSpec(4),
                         FCSpec(3)),
                 t_steps=4)
FLIP_EPS = 1e-5


def _plan(**kw):
    return plan_network(CFG, capacity=100, channel_block=4, event_par=4,
                        **kw)


def _inputs(seed, n=3):
    params = init_params(jax.random.PRNGKey(seed), CFG)
    img = np.random.default_rng(seed).random((n, 10, 10, 1))
    return params, encode_input(jnp.asarray(img, jnp.float32), CFG)


# ------------------------------------------------------------ selection
@pytest.mark.parametrize("kw,backend,want", [
    ({}, "jax", "dense-mxu"),
    ({"capacity": 64}, "jax", "banked-jax"),         # lossy queue
    ({"sat_bits": 8}, "jax", "banked-jax"),
    ({"sat_bits": 16}, "jax", "banked-jax"),
    ({"ingest_capacity": 256, "ingest_depth": 1}, "jax", "banked-jax"),
    ({}, "pallas", "interlaced-pallas"),
    ({"variant": "banked-jax"}, "jax", "banked-jax"),  # pinned
    ({"event_par": 1}, "jax", "sequential"),
], ids=["lossless", "lossy", "sat8", "sat16", "ingest", "pallas", "pinned",
        "sequential"])
def test_selection_rule(kw, backend, want):
    args = dict(capacity=100, event_par=4) | kw
    lp = plan_conv_layer(0, "conv0", (10, 10), 2, 8, **args)
    assert lp.resolve_variant(backend) == want
    assert lp.runs_dense(backend) == (want == "dense-mxu")
    if backend == "jax":  # the plan line names what runs
        assert f"variant={want}" in repr(lp)


@pytest.mark.parametrize("kw", [{"capacity": 64}, {"sat_bits": 8}])
def test_pinned_dense_needs_lossless_float(kw):
    args = dict(capacity=100, event_par=4, variant="dense-mxu") | kw
    with pytest.raises(ValueError, match="dense-mxu"):
        plan_conv_layer(0, "conv0", (10, 10), 1, 8, **args)


# ------------------------------------------------------------- results
def _membrane_trace(x, w, b):
    """(T, B, H, W, C) oracle membrane after each step's bias."""
    def step(vm, x_t):
        u = jax.lax.conv_general_dilated(
            x_t.astype(jnp.float32), w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        vm = vm + u + b
        return vm, vm
    b_sz, _, h, wd, _ = x.shape
    vm0 = jnp.zeros((b_sz, h, wd, w.shape[-1]), jnp.float32)
    return jax.lax.scan(step, vm0, jnp.swapaxes(x, 0, 1))[1]


def test_layer_spikes_match_oracle_away_from_threshold():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((3, 4, 10, 10, 8)) < 0.3)
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 16)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(16,)) * 0.05, jnp.float32)
    lp = plan_conv_layer(1, "conv1", (10, 10), 8, 16, capacity=100,
                         channel_block=8, event_par=4)
    assert lp.resolve_variant() == "dense-mxu"
    got, _, st = run_conv_layer_batched_chunk(
        x, w, b, 1.0, lp, init_conv_carry(lp, 3))
    vm = _membrane_trace(x, w, b)                       # (T, B, H, W, C)
    want = jnp.swapaxes(jax.lax.cummax((vm > 1.0).astype(jnp.int32),
                                       axis=0) > 0, 0, 1)
    near = jnp.swapaxes(jax.lax.cummax(
        (jnp.abs(vm - 1.0) <= FLIP_EPS).astype(jnp.int32), axis=0) > 0, 0, 1)
    far = ~np.asarray(near)
    assert far.mean() > 0.99
    np.testing.assert_array_equal(np.asarray(got)[far], np.asarray(want)[far])
    assert 0.05 < float(np.mean(np.asarray(got))) < 0.95
    np.testing.assert_array_equal(
        np.asarray(st.in_spike_counts),
        np.asarray(x).sum(axis=(2, 3)).astype(np.int32))


@pytest.mark.parametrize("seed", [0, 1])
def test_logits_match_dense_oracle_and_banked(seed):
    params, sp = _inputs(seed)
    plan = _plan()
    assert [lp.resolve_variant() for lp in plan.layers] == ["dense-mxu"] * 3
    got, st = snn_apply_batched(params, sp, CFG, plan)
    banked, sb = snn_apply_batched(params, sp, CFG,
                                   _plan(variant="banked-jax"))
    dense = jax.vmap(lambda s: snn_apply_dense(params, s, CFG))(sp)
    for want in (dense, banked):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=LOGITS_RTOL, atol=LOGITS_ATOL)
    for a, b in zip(st, sb):
        np.testing.assert_array_equal(np.asarray(a.in_spike_counts),
                                      np.asarray(b.in_spike_counts))
        np.testing.assert_array_equal(np.asarray(a.out_spike_counts),
                                      np.asarray(b.out_spike_counts))
        np.testing.assert_allclose(np.asarray(a.in_sparsity),
                                   np.asarray(b.in_sparsity), rtol=1e-6)


@pytest.mark.parametrize("t_chunk", [1, 2])
def test_chunked_matches_monolithic(t_chunk):
    params, sp = _inputs(3, n=2)
    whole = snn_apply_batched(params, sp, CFG, _plan(), collect_stats=False)
    plan = _plan(t_chunk=t_chunk)
    state = init_state(params, CFG, plan, 2)
    for k in range(0, CFG.t_steps, t_chunk):
        state = snn_step_chunk(params, state, sp[:, k:k + t_chunk], CFG,
                               plan)
    np.testing.assert_array_equal(np.asarray(snn_readout(params, state, CFG)),
                                  np.asarray(whole))


def test_vmap_single_sample_matches_batched():
    params, sp = _inputs(4)
    plan = _plan()
    one, st1 = jax.vmap(lambda s: snn_apply(params, s, CFG, plan))(sp)
    many, stb = snn_apply_batched(params, sp, CFG, plan)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(many))
    for a, b in zip(st1, stb):
        np.testing.assert_array_equal(np.asarray(a.in_spike_counts),
                                      np.asarray(b.in_spike_counts))
        np.testing.assert_array_equal(np.asarray(a.out_spike_counts),
                                      np.asarray(b.out_spike_counts))


def test_pinned_dense_streamed_layer_matches_binned():
    cfg = CSNNConfig(input_hw=(12, 12), input_channels=2,
                     layers=(ConvSpec(8), ConvSpec(8, pool=3), FCSpec(10)),
                     t_steps=4)
    traces, _ = dvs_moving_edges(3, cfg.t_steps, cfg.input_hw, seed=5)
    params = init_params(jax.random.PRNGKey(5), cfg)
    plan = plan_network(cfg, capacity=144, channel_block=8, event_par=4,
                        t_chunk=2, ingest=True, variant="dense-mxu")
    banks = jnp.asarray(np.stack([
        events_to_banks(tr, cfg.t_steps, cfg.input_hw) for tr in traces]))
    frames = jnp.asarray(np.stack([
        events_to_frames(tr, cfg.t_steps, cfg.input_hw) for tr in traces]))
    out = []
    for streamed in (True, False):
        state = init_state(params, cfg, plan, 3)
        for k in range(0, cfg.t_steps, 2):
            x = banks[:, k:k + 2] if streamed else frames[:, k:k + 2]
            state = snn_step_chunk(params, state,
                                   StreamState(banks=x) if streamed else x,
                                   cfg, plan)
        out.append(np.asarray(snn_readout(params, state, cfg)))
    np.testing.assert_array_equal(out[0], out[1])
