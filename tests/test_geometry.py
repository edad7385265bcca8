"""Parametric k x k geometry (generalized interlaced event pipeline).

Property suite sweeping k in {1, 3, 5} x raster/interlaced orders x
int8/float32 datapaths, plus the 5x5 end-to-end differential and the
plan-cache geometry invalidation:

* ``ConvGeometry`` invariants: bank count, halo, congruence column map,
  and the even-window/stride rejections.
* queue-compaction equivalence: at full capacity both orders keep
  exactly the fmap's event set (``scatter_aeq`` inverts ``build_aeq``),
  interlaced queues are grouped by column s = kw*(i%kh) + (j%kw), and
  replaying either order through the sequential event conv produces the
  same membrane.
* banked-apply bit-exactness: the sort-free banked path equals both the
  sequential per-event walk (bit for bit) and the dense ``lax.conv``
  reference, for every geometry and dtype.
* ``csnn_wide`` end to end: the 5x5 first-layer net's event pipeline is
  bit-exact vs the dense frame-based oracle.
* plan cache: the v2 fingerprint carries explicit kh/kw/stride per
  layer, so a winner cached for the 3x3 net can never be replayed onto
  a 5x5 plan, and pre-geometry (version-1) cache files read as empty.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import csnn_wide
from repro.core.aeq import build_aeq, build_bank_masks, scatter_aeq
from repro.core.csnn import (CSNNConfig, ConvSpec, FCSpec, encode_input,
                             init_params, snn_apply_batched, snn_apply_dense)
from repro.core.event_conv import (apply_events, apply_events_banked,
                                   crop_vm, dense_conv, pad_vm)
from repro.core.geometry import GEOM_3X3, ConvGeometry
from repro.core.plan import plan_network

jax.config.update("jax_platform_name", "cpu")

GEOMS = [ConvGeometry(1, 1), GEOM_3X3, ConvGeometry(5, 5)]


def _spikes(rng, h, w, density):
    return jnp.asarray(rng.random((h, w)) < density)


class TestConvGeometry:
    def test_derived_quantities(self):
        for g, banks, halo in [(GEOMS[0], 1, (0, 0)), (GEOMS[1], 9, (1, 1)),
                               (GEOMS[2], 25, (2, 2))]:
            assert g.n_banks == banks
            assert g.halo == halo
            assert g.padded_hw(10, 8) == (10 + 2 * halo[0], 8 + 2 * halo[1])
        assert GEOM_3X3 == ConvGeometry(3, 3, 1)
        # strided geometries plan (ceil-div output) but are rejected by
        # the event pipeline (require_event_compatible, tested below)
        assert ConvGeometry(3, 3, 2).out_hw(9, 7) == (5, 4)
        assert ConvGeometry(5, 5).out_hw(9, 7) == (9, 7)

    def test_column_map_is_congruence(self):
        for g in GEOMS:
            cols = {g.column_index_py(i, j)
                    for i in range(3 * g.kh) for j in range(3 * g.kw)}
            assert cols == set(range(g.n_banks))
            # periodicity: the map only sees (i mod kh, j mod kw)
            assert g.column_index_py(5 * g.kh + 1 % g.kh, 7 * g.kw) \
                == g.column_index_py(1 % g.kh, 0)

    def test_rejections(self):
        for bad in [dict(kh=2, kw=3), dict(kh=3, kw=4), dict(kh=0, kw=1),
                    dict(kh=3, kw=3, stride=0)]:
            with pytest.raises(ValueError):
                ConvGeometry(**bad)
        with pytest.raises(ValueError):
            ConvGeometry(3, 3, 2).require_event_compatible("test")


class TestQueueCompaction:
    @given(st.sampled_from(GEOMS), st.booleans(), st.integers(5, 16),
           st.integers(5, 16), st.floats(0.0, 1.0), st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_both_orders_keep_the_fmap_event_set(self, geom, interlaced, h,
                                                 w, density, seed):
        rng = np.random.default_rng(seed)
        fmap = _spikes(rng, h, w, density)
        q = build_aeq(fmap, h * w, interlaced=interlaced, geometry=geom)
        np.testing.assert_array_equal(np.asarray(scatter_aeq(q, (h, w))),
                                      np.asarray(fmap))
        coords = np.asarray(q.coords)[np.asarray(q.valid)]
        if interlaced:  # grouped by interlace column, raster within
            keys = [(geom.column_index_py(i, j), i, j) for i, j in coords]
        else:           # plain raster order
            keys = [(i, j) for i, j in coords]
        assert keys == sorted(keys)

    @given(st.sampled_from(GEOMS), st.sampled_from(["int8", "float32"]),
           st.integers(5, 14), st.integers(5, 14), st.floats(0.1, 0.9),
           st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_replay_order_equivalence(self, geom, dt, h, w, density, seed):
        """Interlaced and raster queues drive the sequential event conv
        to the same membrane: compaction reorders events, never changes
        the applied work.  Integer adds commute exactly; float taps pick
        up reassociation ULPs, so the float case is allclose."""
        rng = np.random.default_rng(seed)
        fmap = _spikes(rng, h, w, density)
        if dt == "float32":
            kern = jnp.asarray(
                rng.standard_normal((geom.kh, geom.kw, 2)), jnp.float32)
        else:  # |tap| <= 3 keeps every k=5 cell within int8 (25*3 < 127)
            kern = jnp.asarray(rng.integers(-3, 4, (geom.kh, geom.kw, 2)),
                               jnp.int8)
        vm0 = pad_vm(jnp.zeros((h, w, 2), kern.dtype), geom)
        out = [np.asarray(crop_vm(apply_events(
            vm0, build_aeq(fmap, h * w, interlaced=il, geometry=geom),
            kern), geom)) for il in (True, False)]
        if dt == "float32":
            np.testing.assert_allclose(out[0], out[1], rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(out[0], out[1])


class TestBankedApplyVsDense:
    @given(st.sampled_from(GEOMS), st.sampled_from(["int8", "float32"]),
           st.integers(5, 14), st.integers(5, 14), st.floats(0.0, 1.0),
           st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_banked_bit_exact_vs_sequential_and_dense(self, geom, dt, h, w,
                                                      density, seed):
        rng = np.random.default_rng(seed)
        fmap = _spikes(rng, h, w, density)
        c = 2
        if dt == "float32":
            kern = jnp.asarray(rng.standard_normal((geom.kh, geom.kw, c)),
                               jnp.float32)
        else:
            kern = jnp.asarray(rng.integers(-3, 4, (geom.kh, geom.kw, c)),
                               jnp.int8)
        vm0 = pad_vm(jnp.zeros((h, w, c), kern.dtype), geom)
        masks = build_bank_masks(fmap[None], h * w, geom).masks[0]
        banked = np.asarray(crop_vm(
            apply_events_banked(vm0, masks, kern), geom))
        seq = np.asarray(crop_vm(apply_events(
            vm0, build_aeq(fmap, h * w, geometry=geom), kern), geom))
        np.testing.assert_array_equal(banked, seq)
        if dt == "float32":
            np.testing.assert_allclose(
                banked, np.asarray(dense_conv(fmap, kern)),
                rtol=1e-5, atol=1e-5)
        else:  # non-saturating regime: integer paths agree exactly
            np.testing.assert_array_equal(
                banked,
                np.asarray(dense_conv(
                    fmap, kern.astype(jnp.int32))).astype(np.int8))


class TestWideEndToEnd:
    def test_csnn_wide_bit_exact_vs_dense_oracle(self):
        """The 5x5 first-layer net runs the whole planned event pipeline
        and lands bit-exact on the dense frame-based oracle (queues sized
        truncation-free: the oracle has no overflow-drop semantics)."""
        cfg = csnn_wide.SMOKE
        h, w = cfg.input_hw
        params = init_params(jax.random.PRNGKey(0), cfg)
        x = (jax.random.uniform(jax.random.PRNGKey(1),
                                (2, h, w, cfg.input_channels))
             < 0.4).astype(jnp.float32)
        spikes = encode_input(x, cfg)
        plan = plan_network(cfg, capacity=h * w, channel_block=4,
                            event_par=None, variant="banked-jax")
        assert plan.layers[0].geometry.window == (5, 5)
        assert plan.layers[0].geometry.n_banks == 25
        assert plan.layers[1].geometry == GEOM_3X3  # mixed-geometry net
        got = snn_apply_batched(params, spikes, cfg, plan,
                                collect_stats=False)
        want = jax.vmap(lambda s: snn_apply_dense(params, s, cfg))(spikes)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestPlanCacheGeometryKey:
    def test_geometry_change_invalidates_cached_winner(self, tmp_path):
        from repro.tune.cache import (CACHE_VERSION, PlanCache, cache_key,
                                      env_descriptor, geometry_descriptor)

        assert CACHE_VERSION == 3  # v3: fused-handoff on the candidate axis
        cfg3 = CSNNConfig(input_hw=(12, 12),
                          layers=(ConvSpec(8), ConvSpec(8, pool=3),
                                  FCSpec(10)),
                          t_steps=4)
        cfg5 = CSNNConfig(input_hw=(12, 12),
                          layers=(ConvSpec(8, kernel=5),
                                  ConvSpec(8, pool=3), FCSpec(10)),
                          t_steps=4)
        base = dict(capacity=64, channel_block=4)
        env = env_descriptor()
        g3, g5 = geometry_descriptor(cfg3, base), geometry_descriptor(cfg5,
                                                                      base)
        # the fingerprint carries the explicit window, not just a label
        assert g3["layers"][0] | {"kernel": 5, "kh": 5, "kw": 5,
                                  "n_banks": 25} == g5["layers"][0]
        assert g5["layers"][0]["stride"] == 1
        k3, k5 = cache_key(g3, env), cache_key(g5, env)
        assert k3 != k5
        cache = PlanCache(tmp_path / "plan_cache.json")
        cache.put(k3, {"geometry": g3, "env": env,
                       "winners": {"layers": []}})
        assert cache.get(k3) is not None
        # the 3x3 winner can never be replayed onto the 5x5 plan
        assert cache.get(k5) is None

    def test_version1_cache_files_read_as_empty(self, tmp_path):
        """Pre-geometry (version-1) caches are invalidated wholesale: the
        old schema had no per-layer window fields, so its winners are
        untrustworthy under parametric geometry."""
        import json

        from repro.tune.cache import PlanCache

        path = tmp_path / "plan_cache.json"
        entry = {"geometry": {}, "env": {}, "winners": {}}
        path.write_text(json.dumps(
            {"version": 1, "entries": {"deadbeef": entry}}))
        assert PlanCache(path).get("deadbeef") is None
