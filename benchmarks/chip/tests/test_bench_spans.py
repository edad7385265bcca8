"""``spans.py`` on small traces recorded on a TPU v5e:

* ``data/spans.xplane.pb`` (``record_spans.py``): a tiny stream engine
  serving eight requests, then one offline call, with the program's host
  spans and device scopes;
* ``data/fixture.xplane.pb`` (``record_trace.py``): recorded before the
  program had either.

Phases and scopes are checked against a brute-force count on a
1-microsecond grid, each cell labelled by what holds its centre; the
wire decoder's ``tf_op`` against TensorFlow's own parser of the format;
and ``trace.reduce`` against what it read from the older trace before
the spans were added (``data/fixture.reduce.json``).
"""
from __future__ import annotations

import bisect
import json
from pathlib import Path

import numpy as np
import pytest

import bench_fixture  # noqa: F401  (puts the repo on sys.path)

DATA = Path(__file__).parent / "data"
SPANS = DATA / "spans.xplane.pb"
OLD = DATA / "fixture.xplane.pb"
US = 1000  # ns


def _raw(path):
    """(window, device-0 ops [(name, start, end)], engine spans of each
    host thread [[(name, start, end)]]) read straight from the file."""
    from jax.profiler import ProfileData

    from benchmarks.chip import trace
    window, ops, threads = None, [], []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            spans = []
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == trace.WINDOW_SPAN:
                    window = iv
                elif plane.name == "/device:TPU:0" and \
                        line.name == trace.OPS_LINE:
                    ops.append((ev.name, *iv))
                elif ev.name.startswith("engine."):
                    spans.append((ev.name.split("#")[0], *iv))
            if spans:
                threads.append(spans)
    return window, ops, threads


def _cells(window, intervals):
    """Grid cells (1 us, from the window's start) whose centre an
    interval holds."""
    w0, w1 = window
    grid = np.zeros(int((w1 - w0) // US), bool)
    for s, e in intervals:
        lo = max(0, int(np.ceil((s - w0) / US - 0.5)))
        hi = min(grid.size, int(np.ceil((e - w0) / US - 0.5)))
        grid[lo:hi] = True
    return grid


def _near(points, intervals):
    """How many of the sorted ``points`` fall inside ``intervals``."""
    return sum(bisect.bisect_right(points, e) - bisect.bisect_left(points, s)
               for s, e in intervals)


@pytest.fixture(scope="module")
def spans_read():
    from benchmarks.chip import spans
    return spans.read(str(SPANS))


def test_phases_match_a_grid_count(spans_read):
    from benchmarks.chip import spans
    red = spans_read
    window, ops, threads = _raw(SPANS)
    loop = max(threads, key=lambda t: sum(n == "engine.dispatch"
                                          for n, *_ in t))
    busy = _cells(window, [(s, e) for _, s, e in ops])
    # innermost wins: paint the longest spans first
    label = np.full(busy.size, spans.NO_SPAN, object)
    for name, s, e in sorted(loop, key=lambda sp: sp[1] - sp[2]):
        label[_cells(window, [(s, e)])] = name
    ends = sorted([t for _, s, e in loop for t in (s, e)]
                  + [t for _, s, e in ops for t in (s, e)])
    assert set(label) <= set(red["phases"])
    for name, got in red["phases"].items():
        mine = [(s, e) for n, s, e in loop if n == name]
        # each boundary inside the phase's spans moves a count by < 1 us
        tol = 1e-6 * (_near(ends, mine) + 2) if mine else 1e-6 * len(ends)
        assert got["host_s"] == pytest.approx(
            (label == name).sum() * 1e-6, abs=tol), name
        assert got["idle_s"] == pytest.approx(
            ((label == name) & ~busy).sum() * 1e-6, abs=tol), name
    # the phases partition the window, and their idle time the device's
    assert sum(p["host_s"] for p in red["phases"].values()) == \
        pytest.approx(red["window_s"], abs=1e-9)
    assert sum(p["idle_s"] for p in red["phases"].values()) == \
        pytest.approx(red["window_s"] - red["busy_s"], abs=1e-9)
    assert red["rounds"] == sum(n == "engine.dispatch" and
                                window[0] <= s < window[1]
                                for n, s, _ in loop) > 0
    for phase in ("engine.admit", "engine.pack", "engine.dispatch",
                  "engine.wait", "engine.readout", "engine.encode",
                  "engine.submit"):
        assert red["phases"][phase]["host_s"] > 0, phase


def test_scopes_match_a_grid_count(spans_read):
    from benchmarks.chip import spans
    red = spans_read
    window, ops, _ = _raw(SPANS)
    labels = spans.op_labels(ops, spans.tf_ops(str(SPANS))["/device:TPU:0"])
    by_label = {}
    for labs, (_, s, e) in zip(labels, ops):
        for lab in labs:
            by_label.setdefault(lab, []).append((s, e))
    assert set(red["scopes"]) == set(by_label)
    for lab, ivs in by_label.items():
        grid = _cells(window, ivs)
        assert red["scopes"][lab] == pytest.approx(
            grid.sum() * 1e-6, abs=2e-6 * len(ivs)), lab
    # the stream engine's chunk step and the offline call, unit by unit
    for lab in ("engine.gather", "engine.scatter", "encode", "head",
                "conv0/compact", "conv0/conv_unit", "conv0/threshold",
                "conv1/compact", "conv1/conv_unit", "conv1/threshold"):
        assert red["scopes"].get(lab, 0) > 0, lab
    # nearly all of the device's time is under a named scope
    assert red["scopes"][spans.SCOPED] >= 0.95 * red["busy_s"]


def test_a_trace_without_spans_or_scopes_reads_empty():
    """The trace of a program that has neither (``fixture.xplane.pb``): no
    phase, no scope, and no error."""
    from benchmarks.chip import spans
    red = spans.read(str(OLD))
    assert red["phases"] == {} and red["scopes"] == {}
    assert red["rounds"] == 0 and red["busy_s"] > 0


def _pb2_tf_ops(path):
    from benchmarks.chip.trace import DEVICE_PLANE
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in xs.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = {}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if stat_names.get(st.metadata_id) != "tf_op":
                    continue
                op = (st.str_value if st.WhichOneof("value") == "str_value"
                      else stat_names[st.ref_value])
                ops[md.name] = op if ops.get(md.name, op) == op else None
        out[plane.name] = ops
    return out


@pytest.mark.parametrize("path", [SPANS, OLD], ids=["spans", "fixture"])
def test_wire_decoder_reads_what_tensorflow_reads(path):
    from benchmarks.chip import spans
    got = spans.tf_ops(str(path))
    assert got == _pb2_tf_ops(path)
    assert sum(len(v) for v in got.values()) > 0


def test_trace_reduce_reads_what_it_read_before():
    from benchmarks.chip import trace
    red = json.loads(json.dumps(trace.reduce(str(OLD))))
    assert red == json.loads((DATA / "fixture.reduce.json").read_text())


@pytest.mark.parametrize("spans_in, want", [
    # (start, end, name) on one thread, in [0, 10]
    ([], [(0, 10, None)]),
    ([(2, 8, "a")], [(0, 2, None), (2, 8, "a"), (8, 10, None)]),
    ([(1, 9, "a"), (3, 4, "b"), (4, 6, "c")],
     [(0, 1, None), (1, 3, "a"), (3, 4, "b"), (4, 6, "c"), (6, 9, "a"),
      (9, 10, None)]),
    ([(-5, 3, "a"), (2, 3, "b"), (7, 20, "c")],
     [(0, 2, "a"), (2, 3, "b"), (3, 7, None), (7, 10, "c")]),
])
def test_innermost(spans_in, want):
    from benchmarks.chip.spans import innermost
    assert innermost(spans_in, 0, 10) == want


def test_op_labels_of_loops_and_unnamed_fusions():
    """A loop's own operation takes the scopes its body shares; an
    unnamed operation in a loop, its loop's; an unnamed operation that
    no loop encloses, the layer its neighbours share."""
    from benchmarks.chip.spans import SCOPED, op_labels
    tf_op = {"a": "jit(f)/conv1/while/body/closed_call/conv_unit/mul:",
             "b": "jit(f)/conv1/while/body/closed_call/threshold/gt:",
             "c": "jit(f)/conv1/compact/sort:",
             "d": "jit(f)/head/dot:"}
    ops = [("c", 0, 10),
           ("fusion.1", 10, 12),       # between conv1 ops: conv1
           ("while.1", 12, 60),        # encloses conv_unit and threshold
           ("while.2", 13, 40),        # encloses conv_unit only
           ("a", 14, 20), ("dus", 20, 25), ("a", 25, 39),
           ("b", 41, 50),
           ("fusion.2", 60, 61),       # between conv1 and head: none
           ("d", 61, 70)]
    got = [sorted(lab) for lab in op_labels(ops, tf_op)]
    conv_unit = sorted({"conv1", "conv_unit", "conv1/conv_unit", SCOPED})
    assert got == [sorted({"conv1", "compact", "conv1/compact", SCOPED}),
                   sorted({"conv1", SCOPED}),
                   sorted({"conv1", SCOPED}),
                   conv_unit, conv_unit, conv_unit, conv_unit,
                   sorted({"conv1", "threshold", "conv1/threshold", SCOPED}),
                   [],
                   sorted({"head", SCOPED})]


def test_scope_labels():
    from benchmarks.chip.spans import SCOPED, scope_labels
    path = ("jit(step_bucket)/conv1/while/body/closed_call/while/body/"
            "closed_call/conv_unit/while/body/closed_call/select_n:")
    assert scope_labels(path) == {"conv1", "conv_unit", "conv1/conv_unit",
                                  SCOPED}
    assert scope_labels("jit(f)/engine.gather/gather:;jit(f)/head/dot:") \
        == {"engine.gather", "head", SCOPED}
    assert scope_labels("jit(<lambda>)/while/body/gather:") == set()
