"""Measured plan autotuner + persistent plan cache (ISSUE 8).

The tuner is a pure scheduling choice — every candidate (block_e,
event_par, kernel variant, capacity sharing, t_chunk, stream finalize)
is bit-exact — so these tests pin the *machinery*: a measured run times
candidates and persists winners; a warm-cache ``tune="cached"`` load
rebuilds the identical plan with ZERO measurement runs (the
``measurement_runs()`` counter is the proof); geometry changes miss the
cache; corrupt files and stale/tampered entries are rejected and fall
back to measuring; and tuned plans stay bit-exact vs analytic plans
across dtypes, batching, and chunking.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.csnn import (ConvSpec, CSNNConfig, FCSpec, init_params,
                             snn_apply_batched)
from repro.core.plan import plan_network
from repro.tune import (CACHE_VERSION, PlanCache, TuneConfig, cache_key,
                        default_cache_path, env_descriptor,
                        geometry_descriptor, measurement_runs)

jax.config.update("jax_platform_name", "cpu")

CFG = CSNNConfig(input_hw=(10, 10),
                 layers=(ConvSpec(4), ConvSpec(4, pool=3), FCSpec(3)),
                 t_steps=2)
KW = dict(capacity=32, channel_block=4, batch_tile=2)
# smallest honest tuning run: one timed invocation per candidate
TC = TuneConfig(batch=2, warmup=0, iters=1, max_block_candidates=2)


def _spikes(seed=3, batch=2, density=0.3):
    rng = np.random.default_rng(seed)
    h, w = CFG.input_hw
    return jnp.asarray(
        (rng.random((batch, CFG.t_steps, h, w, CFG.input_channels))
         < density).astype(np.float32))


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """One measured tuning run, shared by every test that needs a warm
    cache (tuning compiles ~a dozen candidates; do it once)."""
    path = tmp_path_factory.mktemp("cache") / "plan_cache.json"
    n0 = measurement_runs()
    plan = plan_network(CFG, **KW, tune="measured", tune_config=TC,
                        cache_path=path)
    return SimpleNamespace(path=path, plan=plan,
                           measured=measurement_runs() - n0)


# ------------------------------------------------------ measured + cached
class TestMeasuredAndCached:
    def test_measured_run_times_candidates_and_persists(self, warm):
        assert warm.measured > 0
        data = json.loads(warm.path.read_text())
        assert data["version"] == CACHE_VERSION
        (entry,) = data["entries"].values()
        assert set(entry) >= {"geometry", "env", "winners",
                              "occupancy_capacities", "measured_us"}
        # per-layer winners recorded for every conv layer
        assert len(entry["winners"]["layers"]) == len(warm.plan.layers)

    def test_cache_hit_performs_zero_measurement_runs(self, warm):
        """ISSUE 8 acceptance: the second ``plan_network(tune="cached")``
        with a warm cache must never touch the timing path."""
        n0 = measurement_runs()
        plan2 = plan_network(CFG, **KW, tune="cached", tune_config=TC,
                             cache_path=warm.path)
        assert measurement_runs() == n0
        assert plan2 == warm.plan

    def test_geometry_change_invalidates_the_entry(self, warm):
        """Same cache file, different capacity request -> different key
        -> a miss that re-measures (never a silent wrong-plan hit)."""
        n0 = measurement_runs()
        plan = plan_network(CFG, capacity=64, channel_block=4, batch_tile=2,
                            tune="cached", tune_config=TC,
                            cache_path=warm.path)
        assert measurement_runs() > n0
        assert all(lp.capacity <= 64 for lp in plan.layers)
        assert len(json.loads(warm.path.read_text())["entries"]) == 2

    def test_tuned_plan_is_bit_exact_vs_analytic(self, warm):
        params = init_params(jax.random.PRNGKey(0), CFG)
        sp = _spikes()
        analytic = plan_network(CFG, **KW)
        out_a = snn_apply_batched(params, sp, CFG, analytic,
                                  collect_stats=False)
        out_t = snn_apply_batched(params, sp, CFG, warm.plan,
                                  collect_stats=False)
        assert np.array_equal(np.asarray(out_a), np.asarray(out_t))


# --------------------------------------------------- rejection + fallback
class TestRejection:
    def test_corrupt_cache_file_reads_as_empty(self, tmp_path):
        path = tmp_path / "cache.json"
        path.write_text("{ not json !!")
        assert PlanCache(path).get("anything") is None

    def test_wrong_version_reads_as_empty(self, tmp_path):
        path = tmp_path / "cache.json"
        path.write_text(json.dumps(
            {"version": CACHE_VERSION + 1, "entries": {"k": {}}}))
        assert PlanCache(path).get("k") is None

    def test_truncated_entry_is_a_miss_not_a_crash(self, tmp_path):
        path = tmp_path / "cache.json"
        path.write_text(json.dumps(
            {"version": CACHE_VERSION,
             "entries": {"k": {"geometry": {}}}}))  # no env/winners
        assert PlanCache(path).get("k") is None

    def test_tampered_resolved_values_reject_and_remeasure(self, warm,
                                                           tmp_path):
        """A stale entry (resolved values that no longer reproduce under
        the current snapping rules) must fail the fixed-point check and
        fall back to measuring — and the re-measure heals the entry."""
        path = tmp_path / "tampered.json"
        data = json.loads(warm.path.read_text())
        key = min(data["entries"])  # deterministic pick
        data["entries"] = {key: data["entries"][key]}
        data["entries"][key]["winners"]["resolved"][0]["queue_depth"] += 1
        path.write_text(json.dumps(data))
        n0 = measurement_runs()
        plan = plan_network(CFG, **KW, tune="cached", tune_config=TC,
                            cache_path=path)
        assert measurement_runs() > n0  # rejected -> re-measured
        # the healed entry now loads with zero measurement runs and
        # reproduces the re-measured plan exactly (winners may differ
        # from warm.plan — timings this small are noise — but the
        # cached rebuild must be a fixed point of whatever was written)
        n1 = measurement_runs()
        plan2 = plan_network(CFG, **KW, tune="cached", tune_config=TC,
                             cache_path=path)
        assert measurement_runs() == n1
        assert plan2 == plan


# --------------------------------------------------------- cache key + env
class TestCacheKey:
    BASE = dict(capacity=32, channel_block=4, batch_tile=2)

    def test_key_is_deterministic_and_geometry_sensitive(self):
        env = env_descriptor("jax", None)
        geom = geometry_descriptor(CFG, self.BASE)
        assert cache_key(geom, env) == cache_key(
            geometry_descriptor(CFG, dict(self.BASE)), env)
        other = geometry_descriptor(CFG, dict(self.BASE, capacity=64))
        assert cache_key(other, env) != cache_key(geom, env)

    def test_dtype_is_part_of_the_environment(self):
        assert (env_descriptor("jax", None)["dtype"]
                != env_descriptor("jax", 8)["dtype"])

    def test_unresolved_stats_refuse_to_fingerprint(self):
        with pytest.raises(ValueError, match="stats"):
            geometry_descriptor(CFG, dict(self.BASE,
                                          stats=[np.ones((2, 2))]))

    def test_env_var_overrides_default_path(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "pc.json"))
        assert default_cache_path() == tmp_path / "pc.json"

    def test_default_path_is_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv("REPRO_PLAN_CACHE", raising=False)
        repo = Path(__file__).resolve().parents[1]
        assert default_cache_path() == repo / ".plan_cache" / "plan_cache.json"


# ------------------------------------------------ device peaks + crosscheck
class TestDevicePeaks:
    def test_unknown_kind_raises(self):
        from repro.launch.roofline import device_peaks
        with pytest.raises(ValueError, match="no peak for 'cpu'"):
            device_peaks("cpu")

    def test_v5e_entry(self):
        from repro.launch.roofline import V5E, device_peaks
        assert device_peaks(V5E).bf16_flops == 197e12
        assert device_peaks(V5E).hbm_bw == 819e9

    def test_no_model_time_without_a_peak(self, caplog):
        from repro.tune.crosscheck import model_microseconds
        with caplog.at_level("INFO", logger="repro.tune"):
            assert model_microseconds("", "no such chip") is None
            assert model_microseconds("", "no such chip") is None
        assert [r.getMessage() for r in caplog.records].count(
            "tune: no peak for no such chip; winners are ranked by "
            "measurement alone, with no roofline cross-check") == 1


# ------------------------------------------- plan-level variant validation
class TestVariantValidation:
    def test_unknown_variant_rejected(self):
        with pytest.raises(ValueError, match="variant"):
            plan_network(CFG, **KW, variant="fused-marvel")

    def test_interlaced_requires_parallel_width(self):
        with pytest.raises(ValueError, match="event_par"):
            plan_network(CFG, **KW, variant="interlaced-pallas",
                         event_par=1)

    def test_unknown_stream_finalize_rejected(self):
        with pytest.raises(ValueError, match="stream_finalize"):
            plan_network(CFG, **KW, ingest=True, stream_finalize="bogus")

    def test_unknown_tune_mode_rejected(self):
        with pytest.raises(ValueError, match="psychic"):
            plan_network(CFG, **KW, tune="psychic")


# ----------------------- bit-exactness across the whole tunable plan space
class TestPlanSpaceBitExact:
    """Every knob the tuner can turn is a pure scheduling choice: the
    pinned-variant / chunked / dtype plans below span the search space
    and must all produce the analytic plan's exact outputs."""

    @pytest.mark.parametrize("sat_bits", [None, 8])
    def test_pinned_variants_chunked_and_dtypes(self, sat_bits):
        params = init_params(jax.random.PRNGKey(1), CFG)
        sp = _spikes(seed=5)
        ref = plan_network(CFG, **KW, sat_bits=sat_bits)
        out_ref = np.asarray(snn_apply_batched(
            params, sp, CFG, ref, collect_stats=False))
        tuned_like = [
            plan_network(CFG, **KW, sat_bits=sat_bits,
                         variant="banked-jax", event_par=4),
            plan_network(CFG, **KW, sat_bits=sat_bits, per_layer=False,
                         t_chunk=1),
            plan_network(CFG, **KW, sat_bits=sat_bits, block_e=8,
                         t_chunk=2),
        ]
        for plan in tuned_like:
            out = np.asarray(snn_apply_batched(
                params, sp, CFG, plan, collect_stats=False))
            assert np.array_equal(out, out_ref), plan


# --------------------------------------------------- streamed finalization
class TestIngestTuning:
    def test_ingest_tune_picks_a_stream_finalize(self, tmp_path):
        """Stage 3 ranks rank-compaction vs sort-rebuild head to head on
        ingest plans and pins the winner on layer 0 (satellite 2)."""
        path = tmp_path / "cache.json"
        plan = plan_network(CFG, **KW, ingest=True, tune="measured",
                            tune_config=TC, cache_path=path)
        assert plan.layers[0].stream_finalize in ("ranks", "sort")
        (entry,) = json.loads(path.read_text())["entries"].values()
        assert entry["winners"]["stream_finalize"] in ("ranks", "sort")
        assert any(k.startswith("stream_finalize/")
                   for k in entry["measured_us"])
