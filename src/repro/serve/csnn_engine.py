"""Async micro-batching + continuous-batching engine for event-driven
CSNN inference.

Serving shape of the paper workload: requests (single images) arrive one
at a time; the batched event pipeline (``snn_apply_batched``) only pays
off when many samples share one fused queue compaction and one conv-unit
launch per (t, c_in, channel-block) step.  The engine bridges the two
with two scheduling modes:

**Micro-batching (default)** — ``submit`` enqueues a request and awaits
its logits; a background flusher collects requests and flushes a
micro-batch when either ``max_batch`` requests are pending (size flush)
or the oldest request has waited ``max_delay_ms`` (deadline flush) — the
standard batch/deadline threshold from LLM serving, applied to spike
streams.  Partial batches are padded with zero images up to the plan's
``batch_tile`` multiple, so the jitted pipeline only ever sees a small
fixed set of batch shapes.  Each flush runs to completion: a request
arriving just after a flush starts waits out the whole T-step pipeline.

**Continuous batching (``CSNNServeConfig(continuous=True)``)** — the
serving analogue of the paper's self-timed scheduling, where PEs are
never idle waiting for a frame boundary.  The engine owns a fixed table
of ``slots`` batch rows and one shared :class:`~repro.core.csnn.CSNNState`
carry; the device advances every row by ``t_chunk`` time steps per call
(``snn_step_chunk``).  Between chunks, slots whose request has consumed
all T steps are read out (``snn_readout``), their futures resolve, and
the freed rows are re-zeroed and refilled with newly arrived requests —
mid-flight, without waiting for the other slots.  The host encodes newly
arrived images while the device executes the current chunk
(``jax.block_until_ready`` only happens on readout, never on the
admission path).  Per-request results are bit-exact vs the
run-to-completion engine: state rows are per-sample independent, so a
request sees exactly the same T-step computation whichever slots its
neighbours occupy (tests/test_continuous.py).

**Streaming DVS ingestion (``CSNNServeConfig(stream=True)``, continuous
mode only)** — requests are raw DVS event streams ((N, 4) int32 rows of
(t, y, x, polarity)) instead of images.  Host-side admission becomes a
cheap bank append (``data.dvs.events_to_banks``: one vectorized scatter
into the interlace-column layout) instead of a jitted multi-threshold
encode, and each device chunk receives a
:class:`~repro.core.aeq.StreamState` window whose input queues are
finalized sort-free on device (``aeq.stream_queues``) — no dense frame,
no per-frame sort anywhere on the admission path.  Logits are bit-exact
vs binning the same events into frames and serving those
(tests/test_streaming.py).

Every batch/chunk shape can be pre-compiled with ``warmup()`` so
steady-state latency never includes a retrace.

Observability has three parts:

* **host spans** (``jax.profiler.TraceAnnotation``, recorded only while
  a profiler session runs): ``engine.submit`` (``rid=``) in
  ``submit_nowait``; in the continuous loop ``engine.admit``,
  ``engine.pack``, ``engine.dispatch``, ``engine.backlog``,
  ``engine.wait``, ``engine.readout`` and ``engine.idle`` tile the loop
  thread, and ``engine.encode`` (``rid=``) nests wherever a request is
  encoded.  ``rid`` is the request's number, ``stats["requests"]`` at
  its submit;
* **device scopes** (``jax.named_scope``): ``engine.gather``,
  ``engine.reset`` and ``engine.scatter`` in the chunk step, around the
  model's own ``conv{i}/…``, ``head`` and ``encode`` scopes, so every
  device op names its unit in the trace;
* **counters** in ``engine.stats`` (flush reasons, padded slots, chunk
  counts, slot occupancy, deadline misses) and ``admit_wait_hist``: a
  cumulative histogram of admission wait, submit to slot, over the fixed
  bucket edges ``ADMIT_WAIT_EDGES_MS`` (``wait_quantile_ms`` reads it).
  Every value in ``stats`` is immutable, so ``dict(engine.stats)`` is a
  snapshot, and two snapshots subtract into a window's counts.

tests/test_serve_csnn.py pins the flush semantics,
tests/test_continuous.py the refill semantics, tests/test_tracing.py the
spans, scopes and histogram.
"""
from __future__ import annotations

import asyncio
import bisect
import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.aeq import StreamState
from repro.core.csnn import (CSNNConfig, ConvSpec, encode_input, init_state,
                             snn_apply_batched, snn_readout, snn_step_chunk)
from repro.core.plan import NetworkPlan, plan_network, snap_t_chunk
from repro.data.dvs import events_to_banks

_STOP = object()

# Bucket edges of the admission-wait histogram, in ms: log-spaced from
# 0.05 ms to 60 s, each bucket under 10% wide, so an interpolated
# quantile is within 10% of the exact one.  Bucket 0 holds waits below
# the first edge, bucket k waits in [edge[k-1], edge[k]), the last one
# waits of 60 s or more.
_N_WAIT_BUCKETS = math.ceil(math.log(60e3 / 0.05) / math.log(1.1))
ADMIT_WAIT_EDGES_MS = tuple(
    0.05 * (60e3 / 0.05) ** (k / _N_WAIT_BUCKETS)
    for k in range(_N_WAIT_BUCKETS + 1))


def wait_quantile_ms(counts, q: float) -> float:
    """The ``q`` quantile (0 < q <= 1) of an admission-wait histogram
    (``stats["admit_wait_hist"]``, or the difference of two), interpolated
    linearly inside its bucket; NaN for an empty histogram.  A quantile
    in the overflow bucket reads as its lower edge."""
    total = sum(counts)
    if total == 0:
        return math.nan
    rank, seen = q * total, 0
    for k, n in enumerate(counts):
        if n and seen + n >= rank:
            if k == len(ADMIT_WAIT_EDGES_MS):
                return ADMIT_WAIT_EDGES_MS[-1]
            lo = ADMIT_WAIT_EDGES_MS[k - 1] if k else 0.0
            return lo + (ADMIT_WAIT_EDGES_MS[k] - lo) * (rank - seen) / n
        seen += n


def _n_classes(cfg: CSNNConfig) -> int:
    heads = [s for s in cfg.layers if not isinstance(s, ConvSpec)]
    if not heads:
        raise ValueError("cfg has no FC head layer")
    return heads[-1].features


def _reset_rows(state, mask: jax.Array):
    """Zero every state leaf's rows where ``mask`` (B,) is True — used to
    recycle retired/newly-admitted slots without touching in-flight ones."""
    def zero_rows(leaf):
        m = mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.zeros((), leaf.dtype), leaf)
    return jax.tree_util.tree_map(zero_rows, state)


@dataclasses.dataclass
class CSNNServeConfig:
    max_batch: int = 8          # size-flush threshold (requests per batch)
    max_delay_ms: float = 10.0  # flush deadline (micro-batching) / admission
                                # -wait SLO counted as a deadline miss
                                # (continuous)
    continuous: bool = False    # slot-level refill instead of run-to-completion
    slots: int = 0              # continuous slot-table size (0 = max_batch)
    t_chunk: int = 0            # refill granularity in time steps
                                # (0 = plan.t_chunk, else 1; snapped to a
                                # divisor of T)
    stream: bool = False        # requests are raw DVS event streams (N, 4)
                                # admitted by bank append, not images
                                # (continuous mode only)


class CSNNEngine:
    """Micro/continuous-batching front-end over the planned event pipeline.

    Use as an async context manager::

        engine = CSNNEngine(params, cfg, plan)
        async with engine:
            logits = await engine.submit(image)   # (H, W, C) -> (n_classes,)

    or drive a whole request list synchronously with ``run_requests``.
    ``CSNNServeConfig(continuous=True)`` switches the background loop to
    slot-level refill (see module docstring); submit/await semantics are
    identical and per-request logits are bit-exact across modes.
    """

    def __init__(self, params: dict, cfg: CSNNConfig,
                 plan: Optional[NetworkPlan] = None,
                 serve_cfg: Optional[CSNNServeConfig] = None, *,
                 backend: str = "jax", tune: str = "analytic"):
        # a fresh default per engine: a shared CSNNServeConfig() default
        # instance would alias mutable serving knobs across engines
        if serve_cfg is None:
            serve_cfg = CSNNServeConfig()
        self.cfg = cfg
        # tuning (measured micro-benchmarks or a plan-cache load) happens
        # HERE, at engine construction — i.e. at warmup, never on the
        # request hot path; an explicit plan always wins over `tune`
        self.plan = plan if plan is not None else plan_network(
            cfg, batch_tile=serve_cfg.max_batch, tune=tune)
        self.serve_cfg = serve_cfg
        if serve_cfg.stream and not serve_cfg.continuous:
            raise ValueError(
                "CSNNServeConfig(stream=True) requires continuous=True — "
                "streaming admission rides the slot-level refill loop")
        if (not serve_cfg.continuous
                and serve_cfg.max_batch % self.plan.batch_tile != 0):
            # continuous mode never tile-pads: its batch shape is the slot
            # table, so the micro-batching alignment rule does not apply
            raise ValueError(
                f"max_batch={serve_cfg.max_batch} must be a multiple of the "
                f"plan's batch_tile={self.plan.batch_tile}")
        self._params = params
        self._infer = jax.jit(lambda sp: snn_apply_batched(
            params, sp, cfg, self.plan, collect_stats=False, backend=backend))
        # jitted per-shape: eager multi-threshold encoding costs tens of ms
        # per request, which would dominate the admission path
        self._encode = jax.jit(lambda im: encode_input(im, cfg))
        self._queue: Optional[asyncio.Queue] = None
        self._flusher: Optional[asyncio.Task] = None
        self._inflight: set = set()  # unresolved request futures
        self.stats = {"requests": 0, "batches": 0, "flushes_full": 0,
                      "flushes_deadline": 0, "flushes_stop": 0,
                      "padded_slots": 0, "compile_s": 0.0,
                      # continuous-mode slot table observability
                      "chunks": 0, "admitted": 0, "retired": 0, "refills": 0,
                      "slot_steps_busy": 0, "slot_steps_total": 0,
                      "deadline_misses": 0,
                      "admit_wait_hist": (0,) * (_N_WAIT_BUCKETS + 2)}
        if serve_cfg.continuous:
            self._slots = serve_cfg.slots or serve_cfg.max_batch
            requested = serve_cfg.t_chunk or (
                self.plan.t_chunk if self.plan.t_chunk is not None else 1)
            self._t_chunk = snap_t_chunk(cfg.t_steps, requested)
            # occupancy buckets: the chunk step is compiled once per
            # power-of-two batch size up to the slot count, and each chunk
            # packs the active slots into the smallest bucket that fits.
            # Without this, an idle slot row costs as much as an active one
            # (the dense threshold sweep and queue sort run over the whole
            # compiled batch) and slot-level refill degenerates into the
            # same waste as tile padding; with it, chunk cost scales with
            # occupancy — a lone straggler steps at bucket 1, not S.
            buckets, b = [], 1
            while b < self._slots:
                buckets.append(b)
                b *= 2
            buckets.append(self._slots)

            # one fused call per chunk and bucket: gather the active rows,
            # zero newly admitted ones, step, read the head out, scatter
            # the rows back.  Pad entries of ``idx`` are S — out of bounds,
            # so the gather clamps (harmless duplicate row, never read
            # back) and the scatter drops them.  The readout is a tiny
            # matmul riding along in the chunk's async dispatch window, so
            # retiring a slot never costs an extra dispatch+sync round
            # trip.  The full state is donated: the old carry is dead
            # after every chunk, and the refill loop is dispatch-bound on
            # CPU, so the copies would cost more than the arithmetic.
            def step_bucket(state_full, idx, sp, admit_mask):
                with jax.named_scope("engine.gather"):
                    rows = jax.tree_util.tree_map(lambda l: l[idx],
                                                  state_full)
                with jax.named_scope("engine.reset"):
                    rows = _reset_rows(rows, admit_mask)
                rows = snn_step_chunk(params, rows, sp, cfg, self.plan,
                                      backend=backend)
                with jax.named_scope("engine.scatter"):
                    state_full = jax.tree_util.tree_map(
                        lambda lf, lb: lf.at[idx].set(lb), state_full, rows)
                # readout on the FULL slot table, not the bucket rows: the
                # head contraction must keep one fixed (slots, D) shape —
                # XLA's dot reduction order is shape-dependent, so a
                # bucket-sized readout would drift in the last bit vs the
                # run-to-completion engine (cf. snn_apply_sharded's
                # gathered head)
                logits = snn_readout(params, state_full, cfg)
                return state_full, logits

            self._buckets = buckets
            self._step = jax.jit(step_bucket, donate_argnums=0)

    @property
    def slot_utilization(self) -> float:
        """Busy slot-chunks / total slot-chunks over the engine lifetime —
        the serving analogue of the paper's PE utilization figure."""
        total = self.stats["slot_steps_total"]
        return self.stats["slot_steps_busy"] / total if total else 0.0

    # ------------------------------------------------------------- lifecycle
    async def __aenter__(self) -> "CSNNEngine":
        self._queue = asyncio.Queue()
        self._flusher = asyncio.create_task(self._run_flusher())
        return self

    async def _run_flusher(self) -> None:
        """Run the configured scheduling loop; if it dies, fail every
        in-flight future — a crashed flusher must surface as an error at
        the awaiting callers, never as a silent hang."""
        try:
            if self.serve_cfg.continuous:
                await self._continuous_loop()
            else:
                await self._flush_loop()
        except BaseException as e:
            for fut in list(self._inflight):
                if not fut.done():
                    fut.set_exception(
                        RuntimeError(f"engine flusher died: {e!r}"))
            raise

    async def __aexit__(self, *exc) -> None:
        await self._queue.put(_STOP)
        await self._flusher
        self._queue = self._flusher = None

    def warmup(self) -> float:
        """Compile every shape the engine can emit; returns the seconds
        spent so serving latency can be reported compile-free.  Batch mode
        compiles each multiple of ``batch_tile`` up to ``max_batch``;
        continuous mode compiles the chunk step, readout and slot reset at
        the fixed (slots, t_chunk) shape."""
        h, w = self.cfg.input_hw
        c = self.cfg.input_channels
        t0 = time.perf_counter()
        if self.serve_cfg.continuous:
            state = init_state(self._params, self.cfg, self.plan, self._slots)
            if not self.serve_cfg.stream:  # stream admission never encodes
                self._encode(jnp.zeros((1, h, w, c), jnp.float32))
            geom = self.plan.layers[0].geometry  # layer-0 window shapes the
            for b in self._buckets:  # one compile per occupancy bucket
                idx = np.full(b, self._slots, dtype=np.int32)  # all pads
                if self.serve_cfg.stream:  # stream banks, whatever the kxk
                    chunk = StreamState(banks=jnp.zeros(
                        (b, self._t_chunk, c, geom.n_banks,
                         -(-h // geom.kh), -(-w // geom.kw)), jnp.bool_))
                else:
                    chunk = jnp.zeros((b, self._t_chunk, h, w, c), jnp.bool_)
                state, logits = self._step(state, idx, chunk,
                                           np.zeros(b, dtype=bool))
                jax.block_until_ready(logits)
        else:
            tile = self.plan.batch_tile
            for b in range(tile, self.serve_cfg.max_batch + 1, tile):
                sp = self._encode(jnp.zeros((b, h, w, c), jnp.float32))
                jax.block_until_ready(self._infer(sp))
        self.stats["compile_s"] = time.perf_counter() - t0
        return self.stats["compile_s"]

    # ------------------------------------------------------------- requests
    def submit_nowait(self, image) -> "asyncio.Future":
        """Enqueue one (H, W, C) image; returns a future of its logits."""
        if self._queue is None:
            raise RuntimeError("engine is not running (use `async with`)")
        if self._flusher is not None and self._flusher.done():
            raise RuntimeError("engine flusher is not running (it stopped "
                               "or died); re-enter the context manager")
        rid = self.stats["requests"]
        with TraceAnnotation("engine.submit", rid=rid):
            loop = asyncio.get_running_loop()
            fut = loop.create_future()
            self._inflight.add(fut)
            fut.add_done_callback(self._inflight.discard)
            self._queue.put_nowait((jnp.asarray(image), fut, loop.time(),
                                    rid))
            self.stats["requests"] += 1
        return fut

    async def submit(self, image) -> np.ndarray:
        """Enqueue one (H, W, C) image and await its (n_classes,) logits."""
        return await self.submit_nowait(image)

    def run_requests(self, images) -> np.ndarray:
        """Synchronous convenience: serve a request list through the
        engine's own batching loop; returns stacked (N, n_classes) logits."""
        images = list(images)
        if not images:  # nothing to serve; nothing to stack either
            return np.zeros((0, _n_classes(self.cfg)), np.float32)

        async def _drive():
            async with self:
                futs = [self.submit_nowait(img) for img in images]
                return await asyncio.gather(*futs)

        return np.stack(asyncio.run(_drive()))

    # ------------------------------------------- run-to-completion batching
    async def _flush_loop(self) -> None:
        loop = asyncio.get_running_loop()
        max_batch = self.serve_cfg.max_batch
        delay = self.serve_cfg.max_delay_ms / 1e3
        stopping = False
        while not stopping:
            first = await self._queue.get()
            if first is _STOP:
                break
            batch, deadline = [first], loop.time() + delay
            while len(batch) < max_batch:
                timeout = deadline - loop.time()
                if timeout <= 0:
                    break
                try:
                    nxt = await asyncio.wait_for(self._queue.get(), timeout)
                except asyncio.TimeoutError:
                    break
                if nxt is _STOP:
                    stopping = True
                    break
                batch.append(nxt)
            if len(batch) >= max_batch:
                self.stats["flushes_full"] += 1
            elif stopping:  # stop-triggered flush, not a deadline expiry
                self.stats["flushes_stop"] += 1
            else:
                self.stats["flushes_deadline"] += 1
            self._run_batch(batch)
        # Drain on stop: requests enqueued after _STOP (submit_nowait racing
        # __aexit__) are still served instead of leaving their futures
        # hanging forever.
        leftovers = []
        while True:
            try:
                item = self._queue.get_nowait()
            except asyncio.QueueEmpty:
                break
            if item is not _STOP:
                leftovers.append(item)
        for k in range(0, len(leftovers), max_batch):
            self.stats["flushes_stop"] += 1
            self._run_batch(leftovers[k:k + max_batch])

    def _run_batch(self, batch: list) -> None:
        """Pad to the plan's batch tile, run the planned pipeline once,
        resolve every request future."""
        n = len(batch)
        tile = self.plan.batch_tile
        padded = -(-n // tile) * tile
        imgs = jnp.stack([img for img, *_ in batch])
        if padded > n:  # zero images spike nowhere; pure pad slots
            imgs = jnp.concatenate(
                [imgs, jnp.zeros((padded - n,) + imgs.shape[1:], imgs.dtype)])
        logits = np.asarray(jax.block_until_ready(
            self._infer(self._encode(imgs))))
        self.stats["batches"] += 1
        self.stats["padded_slots"] += padded - n
        for i, (_, fut, *_rest) in enumerate(batch):
            if not fut.done():
                fut.set_result(logits[i])

    # ------------------------------------------- continuous slot-level refill
    async def _continuous_loop(self) -> None:
        """Slot table + refill loop (see module docstring).

        Loop invariant: every active slot ``i`` has consumed ``slot_t[i]``
        of its T input steps and the shared ``state`` rows hold exactly
        the carry of those steps; free rows hold garbage and are re-zeroed
        at admission.  The only device sync is the readout when some slot
        finishes — dispatching the next chunk and admitting/encoding new
        arrivals never blocks on the device.
        """
        loop = asyncio.get_running_loop()
        S, tc, T = self._slots, self._t_chunk, self.cfg.t_steps
        h, w = self.cfg.input_hw
        c = self.cfg.input_channels
        geom = self.plan.layers[0].geometry  # shapes the stream bank layout
        state = init_state(self._params, self.cfg, self.plan, S)
        slot_spk = [None] * S   # per-slot (T, H, W, C) encoded inputs (host)
        slot_t = [0] * S        # input steps consumed per slot
        slot_fut = [None] * S
        active = [False] * S
        pending = []            # arrivals awaiting a free slot (lazily encoded)
        stop_seen = False

        stream = self.serve_cfg.stream

        def encoded(item):
            """Lazily encode a pending entry in place: [spk|None, img,
            fut, arrived, rid].  The backlog is encoded in the window right
            after a chunk dispatch (host work concurrent with the
            device's async-dispatched execution); an entry admitted
            before that window pays its encode here, on demand.

            Stream mode skips the jitted threshold encode entirely: the
            payload is a raw (N, 4) event trace, scattered straight into
            the (T, C, 9, HB, WB) interlace-column banks — a single
            vectorized numpy assignment per request."""
            if item[0] is None:
                with TraceAnnotation("engine.encode", rid=item[4]):
                    if stream:
                        item[0] = events_to_banks(
                            np.asarray(item[1]), T, (h, w), c,
                            geometry=geom)
                    else:
                        item[0] = np.asarray(
                            self._encode(jnp.asarray(item[1])[None])[0],
                            dtype=bool)
            return item[0]

        def drain_nowait():
            nonlocal stop_seen
            while True:
                try:
                    item = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    return
                if item is _STOP:
                    stop_seen = True
                else:
                    pending.append([None, *item])

        # Each phase of a round is one span, and the spans tile the loop
        # thread: whatever the thread does between chunks is named.
        while True:
            with TraceAnnotation("engine.admit"):
                drain_nowait()
                # ---- admission: refill free slots; re-zero their rows
                midflight = any(active[j] and slot_t[j] > 0
                                for j in range(S))
                admit = np.zeros(S, dtype=bool)
                now = loop.time()
                waits = []
                for i in range(S):
                    if active[i] or not pending:
                        continue
                    entry = pending.pop(0)
                    spk = encoded(entry)
                    fut, arrived = entry[2], entry[3]
                    slot_spk[i], slot_t[i], slot_fut[i] = spk, 0, fut
                    active[i], admit[i] = True, True
                    waits.append((now - arrived) * 1e3)
                    if midflight:  # joined while others are mid-T-step
                        self.stats["refills"] += 1
                if waits:
                    hist = list(self.stats["admit_wait_hist"])
                    for wait_ms in waits:
                        hist[bisect.bisect_right(ADMIT_WAIT_EDGES_MS,
                                                 wait_ms)] += 1
                        if wait_ms > self.serve_cfg.max_delay_ms:
                            self.stats["deadline_misses"] += 1
                    self.stats["admit_wait_hist"] = tuple(hist)
                    self.stats["admitted"] += len(waits)
                n_active = sum(active)
                if n_active == 0 and stop_seen and not pending:
                    drain_nowait()  # serve submits racing __aexit__, like
                    if not pending:  # the micro-batching drain does
                        break
                    continue
            if n_active == 0:
                with TraceAnnotation("engine.idle"):  # wait for work or stop
                    item = await self._queue.get()
                    if item is _STOP:
                        stop_seen = True
                    else:
                        pending.append([None, *item])
                continue
            with TraceAnnotation("engine.pack"):
                # ---- advance the active slots by one chunk, packed into
                # the smallest compiled occupancy bucket (pad rows carry
                # idx == S: clamped on gather, dropped on scatter)
                act = [i for i in range(S) if active[i]]
                b = next(bb for bb in self._buckets if bb >= n_active)
                idx = np.full(b, S, dtype=np.int32)
                chunk = np.zeros(
                    (b, tc, c, geom.n_banks, -(-h // geom.kh),
                     -(-w // geom.kw))
                    if stream else (b, tc, h, w, c), dtype=bool)
                admit_b = np.zeros(b, dtype=bool)
                for j, i in enumerate(act):
                    idx[j] = i
                    chunk[j] = slot_spk[i][slot_t[i]:slot_t[i] + tc]
                    admit_b[j] = admit[i]
                sp = jnp.asarray(chunk)
                if stream:
                    sp = StreamState(banks=sp)
            with TraceAnnotation("engine.dispatch"):
                # fused gather + admit-reset + chunk step + readout +
                # scatter, async dispatch
                state, logits_dev = self._step(state, idx, sp, admit_b)
                self.stats["chunks"] += 1
                self.stats["slot_steps_busy"] += n_active
                self.stats["slot_steps_total"] += b
            with TraceAnnotation("engine.backlog"):
                # ---- overlap: encode the waiting backlog on this thread
                # while the async-dispatched chunk executes on the device ...
                drain_nowait()
                for entry in pending:
                    encoded(entry)
            with TraceAnnotation("engine.wait"):
                # ... then pace the loop to the device from a worker thread
                # so the event loop keeps accepting submits during the
                # chunk (blocking here on the loop thread would batch
                # admissions into lockstep waves — the refill would be
                # refill in name only)
                await asyncio.to_thread(jax.block_until_ready, logits_dev)
            with TraceAnnotation("engine.readout"):
                # ---- retire finished slots (the only device sync point)
                finished = []
                for i in range(S):
                    if active[i]:
                        slot_t[i] += tc
                        if slot_t[i] >= T:
                            finished.append(i)
                if finished:
                    # (S, n_classes), slot-indexed
                    logits = np.asarray(logits_dev)
                    for i in finished:
                        if not slot_fut[i].done():
                            slot_fut[i].set_result(logits[i])
                        active[i] = False
                        slot_fut[i] = slot_spk[i] = None
                        self.stats["retired"] += 1
        # Failsafe: anything that slipped in after the final drain check is
        # failed explicitly so no future ever hangs (the drain above makes
        # this window practically unreachable).
        drain_nowait()
        for _, _, fut, *_ in pending:
            if not fut.done():
                fut.set_exception(RuntimeError("engine stopped"))
