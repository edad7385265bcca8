"""The program's own spans and scopes in a profiler trace, over its window.

``read`` takes the ``.xplane.pb`` that ``trace.Tracer`` wrote and gives,
over the ``bench.window`` span:

* ``phases``: for each host span of the serving engine (``engine.*``,
  ``serve/csnn_engine.py``), the time the engine's loop thread spent with
  it as the innermost engine span (``host_s``), and the part of that time
  in which the first device traced ran no operation (``idle_s``).
  ``NO_SPAN`` holds the loop thread's time under no engine span.  Empty
  where the trace holds no engine span;
* ``rounds``: the engine's chunk dispatches (``engine.dispatch`` spans)
  that start in the window;
* ``scopes``: for each named device scope (``jax.named_scope`` in
  ``core/csnn.py``, ``core/scheduler.py`` and the engine's chunk step),
  the union of the intervals of the first device's operations whose
  ``tf_op`` path holds it, in seconds.  A unit of a conv layer appears
  both alone (``conv_unit``: every layer's) and under its layer
  (``conv1/conv_unit``).  ``SCOPED`` is the union over every operation
  under a top-level scope (``encode``, ``conv{i}``, ``head``,
  ``engine.*``).  Empty where no operation names a scope;
* ``window_s`` and ``busy_s`` (the first device's).

Device times and host spans are on the profiler's one clock.  Device
operations are matched to scopes by path segment, not by prefix: an
operation inside a loop body carries ``…/while/body/closed_call/…`` in
its path.  ``jax.profiler.ProfileData`` shows no event metadata, so the
``tf_op`` of each operation comes from a small decoder of the protobuf
wire format (``tf_ops``) that reads the device planes' event metadata,
joined to the operations by name.
"""
from __future__ import annotations

import re

from benchmarks.chip.trace import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, _union

ENGINE = "engine."
NO_SPAN = "no engine span"
# phases in which the loop thread does host work of the engine's own; the
# rest (engine.wait, engine.idle) wait for the device or for requests
HOST_PHASES = ("engine.submit", "engine.admit", "engine.encode",
               "engine.pack", "engine.dispatch", "engine.backlog",
               "engine.readout")
TOP_SCOPE = re.compile(r"encode|conv\d+|head|engine\.\w+")
UNIT_SCOPE = re.compile(r"compact|conv_unit|threshold|handoff")
SCOPED = "scoped"


# ------------------------------------------------ protobuf wire format
def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of each field of the message in
    ``buf[start:end]``: an int for a varint, ``(start, end)`` of the
    payload for a length-delimited field, raw bytes for a fixed one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, span):
    """(key, value span) of one entry of a protobuf map field."""
    key = value = None
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def tf_ops(path: str) -> dict:
    """``{plane name: {event metadata name: tf_op}}`` for each device plane
    of the XSpace in ``path``; operations without a ``tf_op`` are left out,
    and a name whose metadata entries disagree maps to None.

    Fields read (tsl/profiler/protobuf/xplane.proto): ``XSpace.planes``
    (1); ``XPlane.name`` (2), ``.event_metadata`` (4), ``.stat_metadata``
    (5); ``XEventMetadata.name`` (2), ``.stats`` (5); ``XStatMetadata.name``
    (2); ``XStat.metadata_id`` (1), ``.str_value`` (5), ``.ref_value`` (7).
    """
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for pnum, v in _fields(buf, *plane):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 4:
                events.append(_map_entries(buf, v)[1])
            elif pnum == 5:
                sid, span = _map_entries(buf, v)
                for snum, sv in _fields(buf, *span):
                    if snum == 2:
                        stat_names[sid] = _text(buf, sv)
        if name is None or not DEVICE_PLANE.match(name):
            continue
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        ops = {}
        for span in events:
            op_name, op = None, None
            for enum, ev in _fields(buf, *span):
                if enum == 2:
                    op_name = _text(buf, ev)
                elif enum == 5:
                    op = _tf_op(buf, ev, tf_op_ids, stat_names) or op
            if op_name is not None and op is not None:
                ops[op_name] = op if ops.get(op_name, op) == op else None
        out[name] = ops
    return out


def _tf_op(buf, span, tf_op_ids, stat_names):
    """The string value of one ``XStat`` if it is a ``tf_op``."""
    sid = value = None
    for num, v in _fields(buf, *span):
        if num == 1:
            sid = v
        elif num == 5:
            value = _text(buf, v)
        elif num == 7:
            value = stat_names.get(v)
    return value if sid in tf_op_ids else None


# ------------------------------------------------------------ intervals
def _segments(path: str) -> tuple:
    """The named scopes of one ``tf_op`` path, outermost first."""
    return tuple(seg for seg in path.split("/")
                 if TOP_SCOPE.fullmatch(seg) or UNIT_SCOPE.fullmatch(seg))


def _prefix(a: tuple, b: tuple) -> tuple:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


def _labels(segments: tuple) -> set:
    labels, layer = set(), None
    for seg in segments:
        labels.add(seg)
        if seg.startswith("conv") and TOP_SCOPE.fullmatch(seg):
            layer = seg
        elif layer is not None and UNIT_SCOPE.fullmatch(seg):
            labels.add(f"{layer}/{seg}")
    if any(TOP_SCOPE.fullmatch(seg) for seg in segments):
        labels.add(SCOPED)
    return labels


def scope_labels(tf_op: str) -> set:
    """The scopes an operation's ``tf_op`` names: each top-level scope and
    unit in its path, and each unit under its conv layer.  Fused
    operations join several paths with ``;``."""
    return set().union(*(_labels(_segments(p)) for p in tf_op.split(";")))


def op_labels(ops, tf_op_of) -> list:
    """The scope labels of each of ``ops`` ((name, start, end) of one
    device line): from its ``tf_op``; else from the scopes that every
    operation it encloses shares; else from the operation that encloses
    it; else, where no operation encloses it, from the top-level scope
    (a conv layer, ``head``, ...) that the nearest operations before and
    after it on the line share, without a unit.

    On a TPU a loop's own operation (``%while``) carries no ``tf_op``:
    its time holds its body's operations, which mostly do, and the
    loop's overhead between them.  Fusions that the compiler builds
    round an instruction of its own, as where it unrolls a short loop,
    carry none either.  The device runs one operation at a time, so such
    a fusion sits among the operations of its layer, though not always
    among those of its unit: the units of a layer interleave."""
    paths = [None] * len(ops)
    labels = [None] * len(ops)
    parent = [None] * len(ops)
    for i, (name, _, _) in enumerate(ops):
        op = tf_op_of.get(name)
        if op:
            parts = [_segments(p) for p in op.split(";")]
            paths[i] = parts[0]
            for part in parts[1:]:
                paths[i] = _prefix(paths[i], part)
            labels[i] = scope_labels(op)
    stack = []  # [op index, common path of what it encloses]

    def close(i, inner):
        if paths[i] is None and inner is not None:
            paths[i], labels[i] = inner, _labels(inner)
        if stack and paths[i] is not None:
            up = stack[-1]
            up[1] = paths[i] if up[1] is None else _prefix(up[1], paths[i])

    order = sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2]))
    for i in order:
        while stack and ops[stack[-1][0]][2] <= ops[i][1]:
            close(*stack.pop())
        parent[i] = stack[-1][0] if stack else None
        stack.append([i, None])
    while stack:
        close(*stack.pop())
    for i in order:  # enclosing operations come first
        if paths[i] is None and parent[i] is not None \
                and paths[parent[i]] is not None:
            paths[i] = paths[parent[i]]
            labels[i] = _labels(paths[i])
    before, last = {}, None
    for i in order:
        if paths[i] is not None:
            last = paths[i]
        elif parent[i] is None and last is not None:
            before[i] = last
    after = None
    for i in reversed(order):
        if paths[i] is not None:
            after = paths[i]
        elif i in before and after is not None:
            labels[i] = _labels(tuple(
                seg for seg in _prefix(before[i], after)
                if TOP_SCOPE.fullmatch(seg)))
    return [lab or set() for lab in labels]


def innermost(spans, lo, hi):
    """[(start, end, name)] pieces of [lo, hi] by the innermost of
    ``spans`` (properly nested (start, end, name) on one thread) that
    covers them; None where none does."""
    pieces, stack, cur = [], [], lo

    def emit(a, b, name):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            pieces.append((a, b, name))

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            emit(cur, end, name)
            cur = max(cur, end)
        emit(cur, s, stack[-1][1] if stack else None)
        cur = max(cur, s)
        stack.append((min(e, stack[-1][0]) if stack else e, n))
    while stack:
        end, name = stack.pop()
        emit(cur, end, name)
        cur = max(cur, end)
    emit(cur, hi, None)
    return pieces


def _overlap(pieces, intervals):
    """Per piece name, the time its pieces share with ``intervals``
    (sorted, disjoint [start, end])."""
    out, j = {}, 0
    for a, b, name in pieces:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k, t = j, 0
        while k < len(intervals) and intervals[k][0] < b:
            t += min(b, intervals[k][1]) - max(a, intervals[k][0])
            k += 1
        out[name] = out.get(name, 0) + t
    return out


def read(path: str, devices=None) -> dict:
    """Phases and scopes of the window marked ``bench.window`` in
    ``path`` (see the module docstring); ``devices``: the device ids that
    may be read (default: every TPU plane), of which the first is."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, threads, dev_planes = None, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if devices is None or int(m.group(1)) in devices:
                dev_planes[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    name = ev.name.split("#")[0]
                    if name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif name.startswith(ENGINE):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, name))
                if spans:
                    threads.append(spans)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    if not dev_planes:
        raise RuntimeError("no device operation in the trace")
    w0, w1 = window
    dev = min(dev_planes)
    ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
           for line in dev_planes[dev].lines if line.name == OPS_LINE
           for ev in line.events]
    labels = op_labels(ops, tf_ops(path).get(dev_planes[dev].name, {}))
    ops = [(lab, name.split(" = ")[0], max(s, w0), min(e, w1))
           for lab, (name, s, e) in zip(labels, ops)]
    ops = [op for op in ops if op[3] > op[2]]
    busy = _union([[s, e] for *_, s, e in ops])
    idle, prev = [], w0
    for s, e in busy:
        if s > prev:
            idle.append([prev, s])
        prev = e
    if w1 > prev:
        idle.append([prev, w1])

    by_label, unscoped = {}, {}
    for labs, name, s, e in ops:
        for label in labs:
            by_label.setdefault(label, []).append([s, e])
        if SCOPED not in labs:
            unscoped[name] = unscoped.get(name, 0) + (e - s) / 1e9
    scopes = {label: sum(e - s for s, e in _union(iv)) / 1e9
              for label, iv in sorted(by_label.items())}

    phases, rounds = {}, 0
    if threads:
        # the loop thread: the one that runs the engine's rounds
        loop = max(threads, key=lambda sp: sum(
            n == "engine.dispatch" for *_, n in sp))
        pieces = innermost(loop, w0, w1)
        host, under_idle = {}, _overlap(pieces, idle)
        for a, b, name in pieces:
            host[name] = host.get(name, 0) + (b - a)
        phases = {name or NO_SPAN: {"host_s": host[name] / 1e9,
                                    "idle_s": under_idle.get(name, 0) / 1e9}
                  for name in sorted(host, key=lambda n: n or "")}
        rounds = sum(n == "engine.dispatch" and w0 <= s < w1
                     for s, _, n in loop)
    return {"window_s": (w1 - w0) / 1e9, "device": dev,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "phases": phases, "rounds": rounds, "scopes": scopes,
            "unscoped": sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]}


def table(red: dict) -> str:
    """The phases and scopes of ``read``'s result as lines of text."""
    w = red["window_s"]
    lines = [f"window {w!r} s, device {red['device']} busy "
             f"{red['busy_s']!r} s, engine rounds {red['rounds']}"]
    if red["phases"]:
        lines.append(f"{'engine phase (innermost)':<28}{'host ms':>12}"
                     f"{'device idle ms':>16}{'share of idle':>15}")
        idle = w - red["busy_s"]
        for name, p in red["phases"].items():
            lines.append(f"{name:<28}{1e3 * p['host_s']:>12.3f}"
                         f"{1e3 * p['idle_s']:>16.3f}"
                         f"{100 * p['idle_s'] / idle if idle else 0:>14.1f}%")
    if red["scopes"]:
        lines.append(f"{'device scope':<28}{'device ms':>12}"
                     f"{'share of busy':>16}")
        for name, s in red["scopes"].items():
            share = 100 * s / red["busy_s"] if red["busy_s"] else 0
            lines.append(f"{name:<28}{1e3 * s:>12.3f}{share:>15.1f}%")
        lines.append("operations under no scope, longest first: " + ", ".join(
            f"{name} {1e3 * s:.3f} ms" for name, s in red["unscoped"]))
    return "\n".join(lines)
