"""Find a cell's pieces by name: nothing here changes when a cell, a
configuration, a traffic mix or a per-layer metric is added.

* ``BENCHMARK.json`` (checkout root): the cells, their configuration and
  traffic names, and the metrics;
* ``configs/<config>.json``: the configuration as it runs; its
  ``reference`` names the plain reference in ``reference/<name>.py``;
* ``traffic/<traffic>.json``: the traffic mix, read by the general
  generator (``inputs.py``) and by the driver it names,
  ``drivers/<driver>.py``;
* ``workloads/<workload>.json``: what is the cell's own: how its entry
  is held (slots) and the limits of its correctness check;
* ``metrics/<metric>.py``: one per-layer metric's reader.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path("benchmarks") / "chip"


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    own: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path


def load_module(path: Path):
    """Import one plugin file by path (its name may hold dots); a file
    already imported is reused."""
    path = Path(path).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    tag = hashlib.sha256(str(path).encode()).hexdigest()[:12]
    name = f"bench_{path.stem.replace('.', '_').replace('-', '_')}_{tag}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    return json.loads(path.read_text())


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of the checkout at ``root``."""
    bench_dir = root / BENCH_DIR
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    (conf,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=w["chips"], cfg=_json(root / conf["file"]),
                mix=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
                own=_json(bench_dir / "workloads" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def derive_seed(seed: int, stream: str) -> int:
    """A 64-bit seed of its own for each use of the run's ``--seed``."""
    digest = hashlib.sha256(f"{seed}/{stream}".encode()).digest()
    return int.from_bytes(digest[:8], "little")


def prng_key(seed: int, stream: str):
    """A JAX threefry key from ``derive_seed`` (any size of ``seed``)."""
    import jax
    import numpy as np
    s = derive_seed(seed, stream)
    return jax.random.wrap_key_data(
        np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32))
