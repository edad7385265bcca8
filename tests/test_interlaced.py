"""Memory-interlaced event-parallel convolution (ISSUE 5).

Pins the interlace layout contracts promised by core/aeq.py and the
bit-exactness of every event-parallel variant vs the sequential conv
unit:

* AEQ column segments: each segment is contiguous and exhaustive, every
  event in segment s has s = 3(i%3)+(j%3), and any two events of one
  segment have non-overlapping 3x3 neighbourhoods (the hazard-freedom
  invariant the parallel kernels rely on) — property-tested.
* ``segment_pad``: event_par-aligned groups are column-homogeneous and
  replaying the padded queue sequentially is a no-op.
* ``build_bank_masks``: the sort-free banked compaction keeps exactly the
  queue's kept events (capacity truncation included).
* banked jax path and ``event_conv_pallas_interlaced{,_batched}``:
  bit-exact vs the sequential kernels for float32/int16/int8 across
  event_par widths, single and batched.
* plan: ``event_par`` autotuned/snapped alongside ``block_e``; the full
  pipeline with an event_par plan reproduces the sequential plan's
  logits bit for bit (monolithic and chunked).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.aeq import (build_aeq, build_aeq_batched, build_bank_masks,
                            interlace, interlaced_capacity, scatter_aeq,
                            segment_pad)
from repro.core.csnn import (CSNNConfig, ConvSpec, FCSpec, encode_input,
                             init_params, init_state, snn_apply_batched,
                             snn_readout, snn_step_chunk)
from repro.core.event_conv import (apply_events, apply_events_banked,
                                   apply_events_banked_batched,
                                   apply_events_batched, pad_vm)
from repro.core.plan import plan_network
from repro.kernels.event_conv import ops
from repro.kernels.event_conv.kernel import (
    event_conv_pallas, event_conv_pallas_batched,
    event_conv_pallas_interlaced, event_conv_pallas_interlaced_batched)
from repro.kernels.runtime import resolve_interpret

jax.config.update("jax_platform_name", "cpu")

SMOKE = CSNNConfig(input_hw=(10, 10),
                   layers=(ConvSpec(4), ConvSpec(4, pool=3), FCSpec(3)),
                   t_steps=4)


def _col(coords):
    return (coords[:, 0] % 3) * 3 + coords[:, 1] % 3


# ----------------------------------------------------------- column segments
class TestColumnSegments:
    @pytest.mark.slow
    @given(st.integers(3, 24), st.integers(3, 24), st.floats(0.05, 1.0),
           st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_segments_contiguous_exhaustive_and_hazard_free(
            self, h, w, density, seed):
        rng = np.random.default_rng(seed)
        fmap = jnp.asarray(rng.random((h, w)) < density)
        cap = max(1, (h * w) * 2 // 3)  # exercise truncation too
        q = build_aeq(fmap, cap)
        coords = np.asarray(q.coords)
        valid = np.asarray(q.valid)
        so, sc = np.asarray(q.seg_offsets), np.asarray(q.seg_counts)
        # exhaustive + contiguous: segments tile the valid prefix exactly
        assert sc.sum() == valid.sum()
        assert (so == np.concatenate([[0], np.cumsum(sc)[:-1]])).all()
        for s in range(9):
            seg = coords[so[s]:so[s] + sc[s]]
            assert valid[so[s]:so[s] + sc[s]].all()
            assert (_col(seg) == s).all()
            # hazard freedom: same-column events never overlap 3x3 windows
            for a in range(len(seg)):
                for b in range(a + 1, len(seg)):
                    di = abs(int(seg[a, 0]) - int(seg[b, 0]))
                    dj = abs(int(seg[a, 1]) - int(seg[b, 1]))
                    assert di > 2 or dj > 2, (seg[a], seg[b])

    def test_batched_segments_match_single(self):
        rng = np.random.default_rng(7)
        fmaps = jnp.asarray(rng.random((6, 11, 9)) < 0.4)
        bq = build_aeq_batched(fmaps, 50)
        for n in range(6):
            q = build_aeq(fmaps[n], 50)
            np.testing.assert_array_equal(np.asarray(bq.seg_offsets[n]),
                                          np.asarray(q.seg_offsets))
            np.testing.assert_array_equal(np.asarray(bq.seg_counts[n]),
                                          np.asarray(q.seg_counts))

    def test_raster_queue_has_no_segments(self):
        q = build_aeq(jnp.ones((5, 5), bool), 25, interlaced=False)
        assert q.seg_offsets is None and q.seg_counts is None


# ---------------------------------------------------------------- segment_pad
class TestSegmentPad:
    @pytest.mark.slow
    @given(st.integers(3, 20), st.integers(3, 20), st.floats(0.1, 1.0),
           st.sampled_from([2, 4, 8]), st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_groups_homogeneous_order_preserved(self, h, w, density, par, seed):
        rng = np.random.default_rng(seed)
        fmap = jnp.asarray(rng.random((h, w)) < density)
        q = build_aeq(fmap, h * w)
        qp = segment_pad(q, par)
        assert qp.capacity == interlaced_capacity(q.capacity, par)
        coords = np.asarray(q.coords)[np.asarray(q.valid)]
        pc, pv = np.asarray(qp.coords), np.asarray(qp.valid)
        np.testing.assert_array_equal(pc[pv], coords)  # order preserved
        for g in range(qp.capacity // par):
            grp = pc[g * par:(g + 1) * par][pv[g * par:(g + 1) * par]]
            if len(grp):
                assert (_col(grp) == _col(grp[:1])).all()

    def test_sequential_replay_of_padded_queue_is_exact(self):
        rng = np.random.default_rng(3)
        fmap = jnp.asarray(rng.random((9, 9)) < 0.6)
        kernel = jnp.asarray(rng.normal(size=(3, 3, 2)).astype(np.float32))
        vm = pad_vm(jnp.zeros((9, 9, 2), jnp.float32))
        q = build_aeq(fmap, 81)
        qp = segment_pad(q, 4)
        a = apply_events(vm, q, kernel)
        b = apply_events(vm, qp, kernel)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_requires_interlaced_queue(self):
        q = build_aeq(jnp.ones((4, 4), bool), 16, interlaced=False)
        with pytest.raises(ValueError, match="interlaced queue"):
            segment_pad(q, 4)


# ------------------------------------------------------------------ bank masks
class TestBankMasks:
    @pytest.mark.slow
    @given(st.integers(3, 20), st.integers(3, 20), st.floats(0.0, 1.0),
           st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_masks_equal_banked_queue_scatter(self, h, w, density, seed):
        """Sort-free banked compaction keeps exactly the queue's events —
        including which events a full queue drops."""
        rng = np.random.default_rng(seed)
        fmap = jnp.asarray(rng.random((h, w)) < density)
        cap = max(1, (h * w) // 2)
        q = build_aeq(fmap, cap)
        want = interlace(jnp.pad(scatter_aeq(q, (h, w)), ((1, 1), (1, 1))))
        got = build_bank_masks(fmap[None], cap)
        np.testing.assert_array_equal(np.asarray(got.masks[0]),
                                      np.asarray(want))
        assert int(got.count[0]) == int(q.count)
        np.testing.assert_array_equal(np.asarray(got.seg_counts[0]),
                                      np.asarray(q.seg_counts))


# ------------------------------------------------- banked jax path exactness
def _int_gen(rng, lo, hi):
    return lambda size: rng.integers(lo, hi, size)


class TestBankedApplyBitExact:
    @pytest.mark.parametrize("dtype,gen", [
        ("float32", None), ("int16", (-20000, 20000)), ("int8", (-90, 91))])
    def test_single_queue_all_dtypes(self, dtype, gen):
        rng = np.random.default_rng(11)
        dt = jnp.dtype(dtype)
        for (h, w, density, cap, c) in [(12, 12, 0.4, 64, 4), (9, 7, 1.0, 63, 2),
                                        (28, 28, 0.15, 128, 8), (5, 5, 0.9, 8, 3)]:
            fmap = jnp.asarray(rng.random((h, w)) < density)
            if gen is None:
                kernel = jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32))
                vm = pad_vm(jnp.asarray(
                    rng.normal(size=(h, w, c)).astype(np.float32)))
            else:
                kernel = jnp.asarray(rng.integers(*gen, (3, 3, c)), dt)
                vm = pad_vm(jnp.asarray(rng.integers(*gen, (h, w, c)), dt))
            q = build_aeq(fmap, cap)
            masks = build_bank_masks(fmap[None], cap).masks[0]
            a = apply_events(vm, q, kernel)
            b = apply_events_banked(vm, masks, kernel)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_batched_queues(self):
        rng = np.random.default_rng(12)
        b, h, w, c, cap = 5, 10, 13, 4, 60
        fmaps = jnp.asarray(rng.random((b, h, w)) < 0.5)
        kernel = jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32))
        vm = jax.vmap(pad_vm)(jnp.asarray(
            rng.normal(size=(b, h, w, c)).astype(np.float32)))
        q = build_aeq_batched(fmaps, cap)
        a = apply_events_batched(vm, q.coords, q.valid, q.count, kernel)
        masks = build_bank_masks(fmaps, cap).masks
        out = apply_events_banked_batched(vm, masks, kernel)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(out))

    def test_int8_saturation_order(self):
        """Per-event saturation semantics survive the banked path."""
        fmap = jnp.ones((6, 6), bool)
        kernel = jnp.full((3, 3, 1), 100, jnp.int8)  # saturates after 2 events
        q = build_aeq(fmap, 36)
        vm = pad_vm(jnp.zeros((6, 6, 1), jnp.int8))
        a = apply_events(vm, q, kernel)
        masks = build_bank_masks(fmap[None], 36).masks[0]
        b = apply_events_banked(vm, masks, kernel)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(np.asarray(b).max()) == 127


# -------------------------------------------- pallas interlaced kernels exact
class TestPallasInterlacedBitExact:
    @pytest.mark.parametrize("dtype,lohi", [
        ("float32", None), ("int16", (-20000, 20000)), ("int8", (-90, 91))])
    @pytest.mark.parametrize("event_par", [2, 4, 8])
    def test_single_vs_sequential(self, dtype, lohi, event_par):
        rng = np.random.default_rng(event_par)
        dt = jnp.dtype(dtype)
        h, w, c, cap = 12, 11, 4, 64
        fmap = jnp.asarray(rng.random((h, w)) < 0.5)
        if lohi is None:
            kernel = jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32))
            vm = pad_vm(jnp.asarray(rng.normal(size=(h, w, c)).astype(np.float32)))
        else:
            kernel = jnp.asarray(rng.integers(*lohi, (3, 3, c)), dt)
            vm = pad_vm(jnp.asarray(rng.integers(*lohi, (h, w, c)), dt))
        qp = segment_pad(build_aeq(fmap, cap), event_par)
        a = event_conv_pallas(vm, qp.coords, qp.valid, kernel,
                              block_e=qp.capacity)
        b = event_conv_pallas_interlaced(vm, qp.coords, qp.valid, kernel,
                                         block_e=qp.capacity,
                                         event_par=event_par)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("event_par", [2, 4])
    def test_column_boundary_fallback_on_unpadded_queue(self, event_par):
        """Groups straddling column boundaries take the sequential body
        and stay exact (the raw, non-segment-padded layout)."""
        rng = np.random.default_rng(5)
        h, w, c = 9, 9, 2
        fmap = jnp.asarray(rng.random((h, w)) < 0.9)
        kernel = jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32))
        vm = pad_vm(jnp.zeros((h, w, c), jnp.float32))
        q = build_aeq(fmap, 80)
        pad = -q.capacity % event_par
        coords = jnp.pad(q.coords, ((0, pad), (0, 0)))
        valid = jnp.pad(q.valid, (0, pad))
        a = event_conv_pallas(vm, coords, valid, kernel,
                              block_e=coords.shape[0])
        b = event_conv_pallas_interlaced(vm, coords, valid, kernel,
                                         block_e=coords.shape[0],
                                         event_par=event_par)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_batched_vs_sequential(self):
        rng = np.random.default_rng(9)
        b, h, w, c, cap, par = 3, 10, 11, 4, 48, 4
        fmaps = jnp.asarray(rng.random((b, h, w)) < 0.5)
        kernel = jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32))
        vm = jax.vmap(pad_vm)(jnp.asarray(
            rng.normal(size=(b, h, w, c)).astype(np.float32)))
        qp = segment_pad(build_aeq_batched(fmaps, cap), par)
        a = event_conv_pallas_batched(vm, qp.coords, qp.valid, kernel,
                                      block_e=qp.capacity)
        out = event_conv_pallas_interlaced_batched(
            vm, qp.coords, qp.valid, kernel, block_e=qp.capacity,
            event_par=par)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(out))

    def test_ops_wrapper_dispatch_matches_sequential(self):
        rng = np.random.default_rng(2)
        h, w, c = 10, 11, 4
        fmap = jnp.asarray(rng.random((h, w)) < 0.5)
        kernel = jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32))
        vm = jnp.zeros((h, w, c), jnp.float32)
        q = build_aeq(fmap, 48)
        a = ops.event_conv(vm, q, kernel, block_e=None)
        b = ops.event_conv(vm, q, kernel, block_e=None, event_par=4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- ops validation
class TestOpsValidation:
    def test_block_e_not_multiple_of_event_par(self):
        q = build_aeq(jnp.ones((6, 6), bool), 36)
        vm = jnp.zeros((6, 6, 2), jnp.float32)
        k = jnp.zeros((3, 3, 2), jnp.float32)
        with pytest.raises(ValueError, match="multiple of event_par"):
            ops.event_conv(vm, q, k, block_e=6, event_par=4)

    def test_mismatched_valid_shape(self):
        q = build_aeq(jnp.ones((6, 6), bool), 36)
        bad = q._replace(valid=q.valid[:-1])
        vm = jnp.zeros((6, 6, 2), jnp.float32)
        k = jnp.zeros((3, 3, 2), jnp.float32)
        with pytest.raises(ValueError, match="does not match event coords"):
            ops.event_conv(vm, bad, k)

    def test_batched_queue_count_mismatch(self):
        q = build_aeq_batched(jnp.ones((3, 6, 6), bool), 36)
        vm = jnp.zeros((2, 6, 6, 2), jnp.float32)
        k = jnp.zeros((3, 3, 2), jnp.float32)
        with pytest.raises(ValueError, match="queue count mismatch"):
            ops.event_conv_batched(vm, q, k)

    def test_raw_kernel_error_mentions_ops_wrappers(self):
        vm = jnp.zeros((8, 8, 2), jnp.float32)
        k = jnp.zeros((3, 3, 2), jnp.float32)
        coords = jnp.zeros((30, 2), jnp.int32)
        valid = jnp.zeros((30,), bool)
        with pytest.raises(ValueError, match="ops.py wrappers"):
            event_conv_pallas(vm, coords, valid, k, block_e=64)


# ----------------------------------------------------------- plan integration
class TestPlanEventPar:
    def test_autotune_records_event_par_and_snaps_block_e(self):
        plan = plan_network(CSNNConfig(), capacity=256, channel_block=8,
                            event_par=None)
        for lp in plan.layers:
            assert lp.event_par >= 1
            assert lp.event_par & (lp.event_par - 1) == 0  # power of two
            if lp.event_par > 1:
                assert lp.block_e % lp.event_par == 0
                assert lp.queue_depth % lp.block_e == 0
                assert lp.queue_depth == interlaced_capacity(lp.capacity,
                                                             lp.event_par)
            else:
                assert lp.queue_depth == lp.capacity
        # the paper net's 28x28 layers are deep enough for full width
        assert plan.layers[0].event_par == 8

    def test_default_plans_stay_sequential(self):
        plan = plan_network(CSNNConfig(), capacity=256)
        assert all(lp.event_par == 1 for lp in plan.layers)

    def test_per_layer_event_par_sequence(self):
        plan = plan_network(SMOKE, capacity=64, event_par=[4, 1])
        assert [lp.event_par for lp in plan.layers] == [4, 1]

    def test_shallow_queue_autotunes_to_sequential(self):
        plan = plan_network(SMOKE, capacity=8, event_par=None)
        assert all(lp.event_par == 1 for lp in plan.layers)


class TestPipelineBitExact:
    @pytest.mark.parametrize("sat_bits", [None, 8, 16])
    def test_event_par_plan_matches_sequential_plan(self, sat_bits):
        rng = np.random.default_rng(0)
        params = init_params(jax.random.PRNGKey(0), SMOKE)
        sp = encode_input(jnp.asarray(
            rng.random((3, 10, 10, 1)), jnp.float32), SMOKE)
        seq = plan_network(SMOKE, capacity=100, sat_bits=sat_bits)
        par = plan_network(SMOKE, capacity=100, sat_bits=sat_bits,
                           event_par=4)
        a, sa = snn_apply_batched(params, sp, SMOKE, seq)
        b, sb = snn_apply_batched(params, sp, SMOKE, par)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for la, lb in zip(sa, sb):
            np.testing.assert_array_equal(np.asarray(la.in_spike_counts),
                                          np.asarray(lb.in_spike_counts))
        assert int(sb[0].event_par) == 4
        assert int(sa[0].event_par) == 1

    def test_chunked_event_par_matches_monolithic(self):
        rng = np.random.default_rng(1)
        params = init_params(jax.random.PRNGKey(1), SMOKE)
        sp = encode_input(jnp.asarray(
            rng.random((2, 10, 10, 1)), jnp.float32), SMOKE)
        plan = plan_network(SMOKE, capacity=100, event_par=4, t_chunk=2)
        whole = plan_network(SMOKE, capacity=100, event_par=4)
        a = snn_apply_batched(params, sp, SMOKE, whole, collect_stats=False)
        state = init_state(params, SMOKE, plan, 2)
        for k in range(0, SMOKE.t_steps, plan.chunk_steps):
            state = snn_step_chunk(params, state,
                                   sp[:, k:k + plan.chunk_steps], SMOKE, plan)
        b = snn_readout(params, state, SMOKE)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_vmap_single_sample_matches_batched(self):
        rng = np.random.default_rng(2)
        params = init_params(jax.random.PRNGKey(2), SMOKE)
        sp = encode_input(jnp.asarray(
            rng.random((3, 10, 10, 1)), jnp.float32), SMOKE)
        plan = plan_network(SMOKE, capacity=100, event_par=4)
        from repro.core.csnn import snn_apply
        a = jax.vmap(lambda s: snn_apply(params, s, SMOKE, plan,
                                         collect_stats=False))(sp)
        b = snn_apply_batched(params, sp, SMOKE, plan, collect_stats=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ interpret switch
class TestInterpretSwitch:
    def test_explicit_argument_wins(self):
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False

    def test_explicit_wins_over_tpu_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_interpret(True) is True

    def test_tpu_backend_resolves_to_compiled(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_interpret() is False

    def test_backend_default_on_cpu(self):
        assert resolve_interpret() is True  # suite is CPU-pinned
