"""Versioned on-disk plan cache for the measured autotuner.

One JSON file holds every tuned network this machine has measured,
keyed by a digest of (layer geometry + planning knobs, vm dtype,
requested backend, XLA backend, device kind, jax version) — the exact
set of inputs that can change which schedule wins.  Location resolves
``cache_path`` arg > ``REPRO_PLAN_CACHE`` env var >
``<repo>/.plan_cache/plan_cache.json`` (git-ignored; nothing is written
outside the checkout unless asked).

Entries store the *winning knobs* (block_e / event_par / variant per
layer, per_layer capacity sharing, t_chunk, stream_finalize), never a
pickled plan: on load the plan is rebuilt through ``plan_network`` and
must reproduce the recorded resolved values bit-for-bit (fixed-point
check), pass ``NetworkPlan.validate`` against the caller's config, and
pass the ``repro.analysis`` contract auditor — any mismatch (a stale
entry written by an older snapping rule, a hand-edited file, a corrupt
write) rejects the entry and falls back to measuring.  Writes are
atomic (tmp file + ``os.replace``) so a crashed tune never corrupts
previously cached winners.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
from pathlib import Path
from typing import Optional

# Bump whenever the winners schema or the knob-resolution rules change in
# a way that invalidates old entries wholesale.  v2: parametric conv
# geometry — layer fingerprints carry explicit kh/kw/stride, so winners
# measured under the hardwired-3x3 schema can never be replayed onto a
# plan with a different window.  v3: the fused-handoff kernel variant
# joined the candidate axis and the None stream_finalize default became
# fmap-size-dependent — winners measured without the fused candidate (or
# recorded under the old always-"ranks" default) are stale.
CACHE_VERSION = 3

ENV_VAR = "REPRO_PLAN_CACHE"
_DEFAULT = (Path(__file__).resolve().parents[3] / ".plan_cache"
            / "plan_cache.json")


def default_cache_path() -> Path:
    """``REPRO_PLAN_CACHE`` override or the in-checkout default location."""
    env = os.environ.get(ENV_VAR)
    return Path(env).expanduser() if env else _DEFAULT


def geometry_descriptor(cfg, base: dict) -> dict:
    """JSON-serializable description of everything that shapes the plan
    search space: the network geometry plus the caller's planning knobs.

    ``base`` must already have ``stats`` resolved to explicit capacities
    (spike-count arrays are not serializable and two runs with different
    calibration data must not collide on one key).
    """
    from repro.core.csnn import ConvSpec
    if base.get("stats") is not None:
        raise ValueError("resolve stats to explicit capacities before "
                         "fingerprinting (arrays are not cache keys)")
    from repro.core.geometry import ConvGeometry
    layers = []
    for spec in cfg.layers:
        if isinstance(spec, ConvSpec):
            geom = ConvGeometry(spec.kernel, spec.kernel)
            layers.append({"kind": "conv", "channels": spec.channels,
                           "kernel": spec.kernel, "pool": spec.pool,
                           "kh": geom.kh, "kw": geom.kw,
                           "stride": geom.stride,
                           "n_banks": geom.n_banks})
        else:
            layers.append({"kind": "fc", "features": spec.features})

    def plain(v):
        return list(v) if isinstance(v, (list, tuple)) else v

    return {
        "input_hw": list(cfg.input_hw),
        "input_channels": cfg.input_channels,
        "t_steps": cfg.t_steps,
        "layers": layers,
        "capacity": plain(base.get("capacity")),
        "channel_block": plain(base.get("channel_block")),
        "sat_bits": base.get("sat_bits"),
        "batch_tile": base.get("batch_tile"),
        "per_layer": base.get("per_layer"),
        "fc_capacity": base.get("fc_capacity"),
        "t_chunk": base.get("t_chunk"),
        "vmem_budget": base.get("vmem_budget"),
        "ingest": bool(base.get("ingest")
                       or base.get("ingest_capacity") is not None),
        "ingest_capacity": base.get("ingest_capacity"),
    }


def env_descriptor(backend: str = "jax",
                   sat_bits: Optional[int] = None) -> dict:
    """The execution environment half of the cache key: a winner measured
    on one device kind / backend / jax version says nothing about
    another."""
    import jax
    return {
        "jax": jax.__version__,
        "xla_backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "backend": backend,
        "dtype": {None: "float32", 16: "int16", 8: "int8"}[sat_bits],
    }


def cache_key(geometry: dict, env: dict) -> str:
    """sha256 over the canonical JSON of (version, geometry, env)."""
    blob = json.dumps({"version": CACHE_VERSION, "geometry": geometry,
                       "env": env}, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


class PlanCache:
    """Dict-of-entries JSON store with atomic writes and lenient reads.

    A missing, unreadable, non-JSON, or wrong-``version`` file reads as
    empty (a cache must never be able to break planning); ``get`` also
    rejects entries missing the required fields, so a truncated or
    hand-mangled entry is a miss, not a crash.
    """

    def __init__(self, path: Optional[os.PathLike | str] = None):
        self.path = Path(path) if path is not None else default_cache_path()

    def _load(self) -> dict:
        try:
            data = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return {}
        if (not isinstance(data, dict)
                or data.get("version") != CACHE_VERSION
                or not isinstance(data.get("entries"), dict)):
            return {}
        return data["entries"]

    def get(self, key: str) -> Optional[dict]:
        entry = self._load().get(key)
        if not isinstance(entry, dict):
            return None
        if not all(k in entry for k in ("geometry", "env", "winners")):
            return None  # truncated/corrupt entry == miss
        return entry

    def put(self, key: str, entry: dict) -> Path:
        entries = self._load()
        entries[key] = entry
        self.path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.path.parent,
                                   prefix=self.path.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": CACHE_VERSION, "entries": entries},
                          f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return self.path
