"""Async micro-batching request scheduler — single queries in, B-sized
`engine.query_batch` ticks out.

The paper's item-centric workload is served ONLINE: queries arrive one at
a time, but PR 1 made the B-query block the cheap unit of work (the
(n, τ) rank table and (n, d) user matrix are streamed once per block, not
once per query). `MicroBatcher` closes that gap: `submit(q, k, c)`
returns a Future immediately; a dispatcher thread coalesces queued
requests into ticks of up to `max_batch` queries and executes each tick
as ONE `engine.query_batch` call.

Latency-vs-throughput knob
--------------------------
A tick dispatches as soon as any (k, c) group reaches `max_batch` queued
requests, or `max_wait_ms` after the head request arrived — whichever
comes first. Small `max_wait_ms` bounds queueing latency at low offered
load (ticks go out nearly empty); large `max_wait_ms` trades latency for
fill ratio and table-bandwidth amortization (see
`benchmarks/perf_engine.py --serve` for the measured curve). Requests
with different (k, c) never share a tick — those are static arguments of
the compiled batch program — and a FULL group behind a straggler head
dispatches immediately rather than waiting out the head's deadline.

Partial-batch padding
---------------------
Partial ticks are EDGE-PADDED to the compiled `max_batch` shape
(`pad_block`), so every tick reuses one compiled XLA program instead of
retracing per fill level; pad rows are sliced off before the Futures
resolve. Padding is numerically invisible: a batched matmul's output
column (i, j) depends only on the user row i, query column j, and the
accumulation order — not on the other columns' VALUES — so the real
rows of a padded tick are bit-identical to dispatching the unpadded
block directly (asserted per backend in tests/test_serve.py). The one
platform caveat: a width-1 block lowers as a matvec with a different
accumulation order, so `pad_block` never emits width-1 dispatches and
bit-identity holds for every partial fill ≥ 2; a singleton tick is
padded like any other and agrees with direct execution on every
table-derived field (indices, r↓/r↑, R↓_k/R↑_k), with `est` equal to
float accuracy.

Back-pressure
-------------
`max_depth` bounds the queue: a `submit` that would push the queue past
it FAILS FAST with `QueueFull` instead of growing an unbounded backlog
(under sustained overload an unbounded queue turns finite latency into
infinite latency for everyone). Rejections are counted per tick
(`TickStats.rejected` — rejections observed since the previous tick) and
in aggregate (`ServeStats.rejected`, plus the queue-depth high-watermark)
so dashboards can see the overload knee; `benchmarks/perf_engine.py
--serve` sweeps offered load past capacity and reports the column.

Snapshot-pinned ticks
---------------------
When the engine is snapshot-versioned (`repro.index`: mutable engines
publish epoch-versioned `IndexSnapshot`s), every tick PINS one snapshot
(`engine.current_snapshot()`) and dispatches the whole batch against it
via `engine.query_batch_at`, recording the epoch in `TickStats.epoch`.
A concurrent mutation or rebuild hot-swap therefore lands BETWEEN ticks,
never inside one: all futures of a tick resolve against exactly one
index generation (asserted in tests/test_index.py). Engines without
snapshots dispatch through plain `engine.query_batch`.

Per-tick stats (`TickStats`) record queue depth at dispatch, fill ratio,
and per-request latency; `MicroBatcher.stats()` aggregates them into
p50/p99 latency for the serving dashboards.

Deadlines, reject reasons, degrade (PR 9)
-----------------------------------------
`submit(..., deadline_ms=)` attaches a latency budget: an already-expired
submit is rejected at admission, and every tick cut SWEEPS the queue
first, failing expired requests with `DeadlineExceeded` BEFORE they
occupy a tick slot (a request that cannot possibly meet its deadline
must not displace one that can). Every rejection carries a reason label
on the `serve_rejected_total{reason=...}` registry counter: `queue_full`
(max_depth back-pressure), `deadline` (expired at admission or in the
sweep), `shutdown` (submit after close, or queue shed past the bounded
drain of `close(drain_s=...)`), and `degraded` (cache-only rung misses,
see below). `submit` after `close()` raises the typed `SchedulerClosed`
instead of hanging, and `close()` is idempotent.

Passing `degrade=DegradeController(...)` (repro.serve.degrade) arms the
certified degrade ladder: the controller observes queue depth at every
tick cut and the tick dispatches at its current rung — rung 2 widens the
served contract to c_eff = c · widen_c (still a certified
c_eff-approximation, recorded in `TickStats.degrade_level` and audited
at c_eff), rung 3 serves LRU hits only and sheds misses. Fault-injection
sites `serve.dispatch` / `serve.slow_tick` / `serve.transfer`
(repro.serve.faults) live at the top of the dispatch and completion
paths, one flag check each when disabled.

Overlapped pipeline (PR 10)
---------------------------
The hot path is DOUBLE-BUFFERED: a dispatch stage (the dispatcher
thread) and a completion stage (a second thread) connected by a bounded
in-flight queue of ≤ `pipeline_depth` ticks. JAX dispatch is async — the
engine call returns unmaterialized device arrays immediately — so the
old stop-and-wait loop (`device_get` inline in the dispatch path) left
the accelerator idle for the whole host side of every tick: D2H readback,
per-request view splitting, future resolution, stats. Now the dispatcher
cuts and dispatches tick t+1 while tick t's device work is still in
flight; the completion stage performs each tick's SINGLE blocking D2H
(`serve.transfer` span) off the dispatch path and resolves futures from
there, in dispatch (FIFO) order.

Data stays device-resident end-to-end: `submit` keeps queries as HOST
numpy (no per-request H2D), tick assembly stacks and edge-pads in numpy,
and the whole tick pays exactly one H2D through the engine's
`dispatch_batch_at` → backend `dispatch_device` entry (which on
accelerators donates the tick-private block buffer back to XLA). When
the engine's backend composes a `CachingBackend`, the LRU lookup is
folded into the ADMISSION path: a `submit` whose exact (query, k, c) is
cached for the live snapshot resolves immediately and never occupies a
queue or tick slot (`ServeStats.admission_hits`).

Results are BIT-IDENTICAL to synchronous dispatch — the pipeline moves
buffers and threads, never values — and every PR 9 invariant holds with
ticks in flight: a completion-stage failure (e.g. an injected
`serve.transfer` fault) fails exactly that tick's futures typed and
re-credits its reject/expiry attribution to the next cut or the terminal
flush; `close(drain_s=...)` bounds the drain with ≥ 1 tick in flight and
never tears a future; `pipeline_depth=1` degenerates to the synchronous
schedule (the A/B baseline `benchmarks/perf_engine.py --serve
--saturate` measures overlap against). `TickStats.inflight` records the
pipeline occupancy at each dispatch; `ServeStats.overlap_efficiency` is
the fraction of ticks that actually overlapped another.

Wait spans
----------
With tracing on (`repro.obs.trace`), every wait on the serving path is a
`with` span on the thread where it happens, so the profiler trace can
say what the host was doing while the device sat idle: the dispatcher's
`serve.idle` (empty queue), `serve.fill_wait` (holding a partial head
tick for up to `max_wait_ms`) and `serve.pipeline_full` (no in-flight
slot), and, inside the completion stage's `serve.transfer`,
`serve.ready` (the tick's device work) then `serve.d2h` (the copy
alone). Each dispatched tick takes a sequence number, the attr
`tick=<n>` on its `serve.tick`, its requests' `serve.queue_wait`
events, the backend's spans inside the tick and its completion spans.
With tracing off the completion stage makes its single `device_get` as
before; the ready/copy split costs a `block_until_ready` and is made
only while tracing.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import registry as obs
from repro.obs import trace
from repro.serve import faults


def pad_block(qs: jax.Array, max_batch: int) -> jax.Array:
    """Edge-pad a (B, d) query block to the compiled (max_batch, d) shape.

    Pad rows repeat the last real query: their columns are well-defined on
    every backend and are masked out of results by slicing. B = 0 or
    B > max_batch are caller errors, and so is max_batch < 2: the padded
    width is the dispatch width, and a width-1 dispatch lowers as a
    matvec with a different accumulation order — the exact case the
    module's "dispatches never shrink below width 2" bit-identity
    invariant (module doc) exists to rule out.
    """
    if max_batch < 2:
        raise ValueError(f"max_batch must be >= 2 (width-1 dispatches "
                         f"lower as a matvec and break partial-tick "
                         f"bit-identity); got {max_batch}")
    b = qs.shape[0]
    if not 1 <= b <= max_batch:
        raise ValueError(f"block of {b} queries does not fit max_batch="
                         f"{max_batch}")
    if b == max_batch:
        return qs
    return jnp.concatenate(
        [qs, jnp.broadcast_to(qs[-1:], (max_batch - b, qs.shape[1]))])


def _program_count() -> int:
    """Compiled-program count across the query stack (0 if unavailable).

    Deferred import: the counter lives with the elastic backend
    (`repro.core.elastic.compiled_program_count`), whose module this one
    must not import at load time (serve ↔ core layering)."""
    try:
        from repro.core.elastic import compiled_program_count
        return compiled_program_count()
    except Exception:
        return 0


class QueueFull(RuntimeError):
    """`submit` rejected: the queue is at `max_depth` (back-pressure)."""


class SchedulerClosed(RuntimeError):
    """`submit` after `close()`: the scheduler is shut down (reject
    reason `shutdown`). A RuntimeError subclass so pre-PR-9 callers
    catching the old untyped close error keep working."""


class DeadlineExceeded(RuntimeError):
    """The request's `deadline_ms` budget expired before dispatch —
    at admission, in the per-tick queue sweep, or as a queued casualty
    of a bounded drain (reject reason `deadline`)."""

# Reject-reason label values on serve_rejected_total{reason=...}; the
# catalog is closed so dashboards can enumerate it.
REJECT_REASONS = ("queue_full", "deadline", "shutdown", "degraded")


@dataclasses.dataclass(frozen=True)
class TickStats:
    """One dispatched tick, as observed by the scheduler."""

    batch: int                 # real (unpadded) queries in the tick
    queue_depth: int           # queue length when the tick was formed
    fill_ratio: float          # batch / max_batch
    wait_ms: float             # head request's submit → dispatch wait
    latencies_ms: Tuple[float, ...]   # per-request submit → resolve
    rejected: int = 0          # submits rejected since the previous tick
    epoch: Optional[int] = None  # pinned index epoch (snapshot engines)
    # Query-stack XLA programs compiled DURING this tick's dispatch
    # (repro.core.elastic.compiled_program_count delta). Nonzero only on
    # warm-up ticks; a nonzero value on a steady-state tick is the
    # recompile-storm signature the elastic backend exists to kill, and
    # exactly what its p99 spike looks like to a dashboard.
    compiles: int = 0
    # Deadline sweeps attributed to this tick's cut (expired requests
    # shed from the queue before the tick was formed), and the degrade
    # rung the tick was dispatched at (0 = normal; repro.serve.degrade).
    expired: int = 0
    degrade_level: int = 0
    # A terminal record (batch == 0) is flushed at close() when rejects
    # arrived after the last dispatched tick — every rejection is
    # attributed to exactly one TickStats.
    # Pipeline observability (PR 10): the tick's single D2H readback
    # time in the completion stage, and the in-flight tick count at the
    # moment this tick was dispatched (self included — 1 means it did
    # not overlap anything; ≥ 2 is the pipelined steady state).
    transfer_ms: float = 0.0
    inflight: int = 1


@dataclasses.dataclass(frozen=True)
class ServeStats:
    """Aggregate over a MicroBatcher's lifetime (see `stats()`)."""

    ticks: int
    requests: int
    mean_fill: float
    mean_queue_depth: float
    p50_ms: float
    p99_ms: float
    rejected: int = 0          # submits rejected by the max_depth bound
    depth_hwm: int = 0         # queue-depth high-watermark
    expired: int = 0           # requests shed by deadline (admission+sweep)
    # PR 10: submits resolved from the LRU on the admission path (their
    # latencies are pooled into the percentiles; they occupy no tick),
    # and the fraction of dispatched ticks that overlapped ≥ 1 other
    # in-flight tick (the pipeline's utilization signal).
    admission_hits: int = 0
    overlap_efficiency: float = 0.0

    def __str__(self):
        return (f"{self.requests} reqs / {self.ticks} ticks  "
                f"fill {self.mean_fill:.2f}  depth {self.mean_queue_depth:.1f}"
                f" (hwm {self.depth_hwm})  rej {self.rejected}"
                f"  exp {self.expired}  adm {self.admission_hits}"
                f"  ovl {self.overlap_efficiency:.2f}"
                f"  p50 {self.p50_ms:.2f} ms  p99 {self.p99_ms:.2f} ms")


class _Request:
    __slots__ = ("q", "k", "c", "future", "t_submit", "t_deadline")

    def __init__(self, q, k, c, deadline_ms=None):
        self.q = q                      # HOST numpy row (PR 10): queries
        self.k = int(k)                 # stay host-side until the tick's
        self.c = float(c)               # single H2D at assembly
        self.future: Future = Future()
        self.t_submit = time.monotonic()
        # absolute monotonic deadline; None = no latency budget
        self.t_deadline = (None if deadline_ms is None
                           else self.t_submit + float(deadline_ms) / 1e3)

    @property
    def key(self):
        return (self.k, self.c)


class _InflightTick:
    """One dispatched-but-uncompleted tick: the unit the completion stage
    consumes. `res` holds the engine call's UNMATERIALIZED device arrays
    (JAX async dispatch) — nothing here has blocked on the device yet."""

    __slots__ = ("seq", "reqs", "res", "snap", "epoch", "k", "c_eff",
                 "depth", "rejected", "expired", "level", "t_dispatch",
                 "compiles", "inflight")

    def __init__(self, seq, reqs, res, snap, epoch, k, c_eff, depth,
                 rejected, expired, level, t_dispatch, compiles):
        self.seq = seq          # the tick id its spans carry (`tick=`)
        self.reqs = reqs
        self.res = res
        self.snap = snap
        self.epoch = epoch
        self.k = k
        self.c_eff = c_eff
        self.depth = depth
        self.rejected = rejected
        self.expired = expired
        self.level = level
        self.t_dispatch = t_dispatch
        self.compiles = compiles
        self.inflight = 1       # occupancy at dispatch; set at append


class MicroBatcher:
    """Coalesce async single-query submissions into `query_batch` ticks.

    Usage::

        with MicroBatcher(eng, max_batch=16, max_wait_ms=2.0) as mb:
            futs = [mb.submit(q, k=10, c=2.0) for q in queries]
            results = [f.result() for f in futs]     # QueryResult each
            print(mb.stats())

    Thread-safe; one background dispatcher thread. `close()` (or leaving
    the context) drains the queue before the thread exits, so every
    accepted Future resolves.
    """

    def __init__(self, engine, *, max_batch: int = 16,
                 max_wait_ms: float = 2.0, max_depth: Optional[int] = None,
                 auditor=None, degrade=None, pipeline_depth: int = 2):
        # Width 1 is rejected, not padded around: the module's partial-tick
        # bit-identity argument needs every dispatch ≥ 2 wide (matvec
        # lowering caveat, module doc), and a max_batch=1 scheduler could
        # never form a wider tick.
        if max_batch < 2:
            raise ValueError(f"max_batch must be >= 2 (width-1 dispatches "
                             f"lower as a matvec and break partial-tick "
                             f"bit-identity), got {max_batch}")
        if max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0, got {max_wait_ms}")
        if max_depth is not None and max_depth < 1:
            raise ValueError(f"max_depth must be >= 1, got {max_depth}")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        self.engine = engine
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.max_depth = None if max_depth is None else int(max_depth)
        # Ticks allowed in flight (dispatched, not yet completed): 1 is
        # the synchronous schedule, 2 the double-buffered default — the
        # completion stage of tick t overlaps the device work of t+1.
        self.pipeline_depth = int(pipeline_depth)
        # Optional shadow auditor (repro.obs.audit.QualityAuditor): every
        # resolved request is OFFERED to it with the pinned snapshot; the
        # auditor samples and re-scores off-thread, never blocking ticks.
        self.auditor = auditor
        # Optional degrade-ladder controller (repro.serve.degrade): asked
        # for the current rung at every tick cut; None = always rung 0.
        self.degrade = degrade
        reg = obs.get_default()
        self._m_submitted = reg.counter(
            "serve_requests_total", "requests accepted by submit()")
        self._m_rejected = reg.counter(
            "serve_rejected_total", "submits rejected by back-pressure")
        # Per-reason reject counters (same metric name, a `reason` label
        # per REJECT_REASONS value; the unlabelled aggregate above stays
        # for pre-PR-9 dashboards).
        self._m_reject_reason = {
            reason: reg.counter(
                "serve_rejected_total", "rejects by reason",
                labels={"reason": reason})
            for reason in REJECT_REASONS}
        self._m_ticks = reg.counter(
            "serve_ticks_total", "dispatched micro-batch ticks")
        self._m_compiles = reg.counter(
            "serve_compiles_total", "XLA programs compiled during ticks")
        self._m_depth = reg.gauge(
            "serve_queue_depth", "queue length at the last tick cut")
        self._m_latency = reg.histogram(
            "serve_request_latency_ms", "submit → resolve latency")
        self._m_wait = reg.histogram(
            "serve_queue_wait_ms", "submit → dispatch queue wait")
        self._m_inflight = reg.gauge(
            "serve_inflight_ticks",
            "ticks dispatched but not yet completed")
        self._m_transfer = reg.histogram(
            "serve_transfer_ms", "per-tick D2H readback time")
        self._m_admission = reg.counter(
            "serve_admission_hits_total",
            "submits resolved from the LRU at admission")
        self._queue: Deque[_Request] = deque()
        self._cond = threading.Condition()
        self._stop = False
        self._closed = False        # close() already ran (idempotency)
        self._drain_deadline = None  # monotonic bound on close() draining
        self._flush = False
        self._busy = False          # a tick is being dispatched right now
        self._tick_seq = 0          # next tick id (dispatcher thread only)
        self._ticks: List[TickStats] = []
        self._rejected_total = 0
        self._rejected_since_tick = 0
        self._expired_total = 0
        self._expired_since_tick = 0
        self._depth_hwm = 0
        # The pipeline's bounded in-flight queue: dispatch appends,
        # completion peeks/pops FIFO (so futures resolve in dispatch
        # order and flush() sees a tick until it is fully resolved).
        self._inflight: Deque[_InflightTick] = deque()
        self._complete_stop = False
        self._admission_hits = 0
        self._admission_lat: List[float] = []
        # Admission-path LRU (PR 10): when the engine's backend composes
        # a CachingBackend AND the engine is snapshot-versioned, submit
        # probes the cache first — a hit resolves immediately and never
        # occupies a queue or tick slot.
        self._admission_cache = None
        if getattr(engine, "current_snapshot", None) is not None:
            bk = getattr(engine, "_backend", None)
            if bk is not None:
                try:
                    from repro.serve.degrade import find_cache
                    self._admission_cache = find_cache(bk)
                except Exception:
                    self._admission_cache = None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="microbatcher")
        self._complete_thread = threading.Thread(
            target=self._completion_loop, daemon=True,
            name="microbatcher-complete")
        self._thread.start()
        self._complete_thread.start()

    # ------------------------------------------------------------- client
    def submit(self, q: jax.Array, k: int, c: float,
               deadline_ms: Optional[float] = None) -> Future:
        """Enqueue one (d,) query; resolves to its per-query QueryResult
        with HOST (numpy) leaves, leading batch axis already squeezed —
        serving results are client-bound, so the tick is transferred once
        and split into zero-copy row views.

        With `max_depth` set, a submit that finds the queue at the bound
        raises `QueueFull` immediately (fail-fast back-pressure) instead
        of accepting work the scheduler cannot keep up with.

        `deadline_ms` attaches a latency budget relative to NOW: a
        non-positive budget is rejected at admission with
        `DeadlineExceeded`, and a queued request whose budget expires
        before its tick is cut is failed by the per-tick sweep (its
        Future raises `DeadlineExceeded`). After `close()`, submits
        raise `SchedulerClosed` (reject reason `shutdown`).

        PR 10: the query is kept as HOST numpy until tick assembly (no
        per-submit H2D), and when the engine's backend composes a
        CachingBackend an exact LRU hit for the live snapshot resolves
        the Future right here — it never occupies a queue or tick slot
        (`ServeStats.admission_hits`)."""
        q = np.asarray(jax.device_get(q))
        if q.ndim != 1:
            raise ValueError(f"submit expects a (d,) query; got {q.shape}")
        if q.dtype == np.float64:
            # mirror jnp.asarray's default-dtype conversion (x64 off) so
            # host-resident submission changes no tick bytes
            q = q.astype(np.float32)
        if deadline_ms is not None and deadline_ms <= 0:
            # already expired at admission: shed before it can take a
            # queue slot, let alone a tick slot
            with self._cond:
                self._expired_total += 1
                self._expired_since_tick += 1
            self._m_reject_reason["deadline"].inc()
            raise DeadlineExceeded(
                f"deadline_ms={deadline_ms} already expired at submit")
        if self._admission_cache is not None and not self._stop:
            fut = self._admission_probe(q, int(k), float(c))
            if fut is not None:
                return fut
        req = _Request(q, k, c, deadline_ms=deadline_ms)
        with self._cond:
            if self._stop:
                # Not counted into _rejected_total: the dispatcher has
                # (or will have) exited, so no terminal TickStats could
                # attribute it — the labelled counter is the record.
                self._m_reject_reason["shutdown"].inc()
                raise SchedulerClosed("MicroBatcher is closed")
            if (self.max_depth is not None
                    and len(self._queue) >= self.max_depth):
                self._rejected_total += 1
                self._rejected_since_tick += 1
                self._m_rejected.inc()
                self._m_reject_reason["queue_full"].inc()
                raise QueueFull(
                    f"queue at max_depth={self.max_depth}; request rejected "
                    "(fail-fast back-pressure — retry with backoff)")
            self._queue.append(req)
            self._depth_hwm = max(self._depth_hwm, len(self._queue))
            self._cond.notify_all()
        self._m_submitted.inc()
        return req.future

    def _admission_probe(self, q: np.ndarray, k: int,
                         c: float) -> Optional[Future]:
        """LRU probe on the admission path: a resolved Future when the
        exact (query, k, c) is cached for the live snapshot, else None
        (the request then takes the normal queue path). Misses are not
        counted against the cache's hit-rate (`record_miss=False`) —
        they go on to dispatch through the backend, which counts them.
        Probe failures (e.g. an engine mid-teardown) degrade to the
        queue path rather than failing the submit."""
        t0 = time.monotonic()
        try:
            snap = self.engine.current_snapshot()
            res = self._admission_cache.lookup_only(
                snap.rank_table, snap.query_users(), q, k=k, c=c,
                delta=snap.corr, record_miss=False)
        except Exception:
            return None
        if res is None:
            return None
        host = jax.device_get(res)
        lat_ms = (time.monotonic() - t0) * 1e3
        with self._cond:
            self._admission_hits += 1
            self._admission_lat.append(lat_ms)
        self._m_submitted.inc()
        self._m_admission.inc()
        self._m_latency.observe(lat_ms)
        fut: Future = Future()
        fut.set_result(host)
        if self.auditor is not None:
            self.auditor.observe(np.asarray(q), host, k=k, c=c,
                                 snapshot=snap)
        return fut

    def flush(self) -> None:
        """Dispatch everything queued without waiting out `max_wait_ms`,
        and block until all accepted requests have resolved."""
        with self._cond:
            self._flush = True
            self._cond.notify_all()
            while self._queue or self._busy or self._inflight:
                self._cond.wait(timeout=0.05)
            self._flush = False

    def close(self, drain_s: Optional[float] = None) -> None:
        """Drain the queue, then stop the dispatcher thread. Idempotent —
        a second close() returns immediately.

        `drain_s` bounds the drain: queued requests still undispatched
        when the budget runs out are shed (`SchedulerClosed`, reject
        reason `shutdown`) instead of holding up shutdown behind a slow
        engine. The default None drains fully, as before."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stop = True
            if drain_s is not None:
                self._drain_deadline = time.monotonic() + float(drain_s)
            self._cond.notify_all()
        self._thread.join()
        # The dispatcher's exit signalled the completion stage to drain
        # the remaining in-flight ticks and flush the terminal record;
        # joining it makes close() a full barrier (every accepted Future
        # resolved, every reject attributed) exactly as before.
        self._complete_thread.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def stats(self) -> ServeStats:
        """Aggregate tick statistics (p50/p99 over request latencies,
        admission-path hits pooled in)."""
        with self._cond:            # one atomic snapshot of ticks+counters
            ticks = list(self._ticks)
            rejected, hwm = self._rejected_total, self._depth_hwm
            expired = self._expired_total
            adm = self._admission_hits
            adm_lat = list(self._admission_lat)
        if not ticks and not adm_lat:
            return ServeStats(0, 0, 0.0, 0.0, 0.0, 0.0, rejected=rejected,
                              depth_hwm=hwm, expired=expired,
                              admission_hits=adm)
        # The terminal rejection record (batch == 0, no latencies) is an
        # accounting tick: it carries rejects into the aggregate but must
        # not skew the dispatch-shape means or crash the percentiles.
        dispatched = [t for t in ticks if t.batch > 0]
        lats = np.concatenate(
            [np.asarray(t.latencies_ms, dtype=float) for t in ticks]
            + [np.asarray(adm_lat, dtype=float)])
        return ServeStats(
            ticks=len(ticks),
            requests=int(lats.size),
            mean_fill=(float(np.mean([t.fill_ratio for t in dispatched]))
                       if dispatched else 0.0),
            mean_queue_depth=(
                float(np.mean([t.queue_depth for t in dispatched]))
                if dispatched else 0.0),
            p50_ms=float(np.percentile(lats, 50)) if lats.size else 0.0,
            p99_ms=float(np.percentile(lats, 99)) if lats.size else 0.0,
            rejected=rejected,
            depth_hwm=hwm,
            expired=expired,
            admission_hits=adm,
            overlap_efficiency=(
                float(np.mean([t.inflight > 1 for t in dispatched]))
                if dispatched else 0.0),
        )

    @property
    def tick_log(self) -> List[TickStats]:
        with self._cond:
            return list(self._ticks)

    # --------------------------------------------------------- dispatcher
    def _full_key(self):
        """The (k, c) of the first group to reach `max_batch` queued
        requests, or None. Requests with different static args cannot
        share a tick (k/c are compiled into the batch program), but a
        FULL group behind a lone straggler head is dispatchable NOW —
        waiting out the head's deadline would be head-of-line blocking."""
        counts: dict = {}
        for r in self._queue:
            counts[r.key] = counts.get(r.key, 0) + 1
            if counts[r.key] >= self.max_batch:
                return r.key
        return None

    def _sweep_expired(self, now: float) -> List[_Request]:
        """Remove deadline-expired requests from the queue (lock held).
        Returns the shed requests — their futures are failed OUTSIDE the
        lock (`_fail_expired`), so a future callback can never deadlock
        against the scheduler."""
        if not any(r.t_deadline is not None and now >= r.t_deadline
                   for r in self._queue):
            return []
        keep: Deque[_Request] = deque()
        dead: List[_Request] = []
        for r in self._queue:
            if r.t_deadline is not None and now >= r.t_deadline:
                dead.append(r)
            else:
                keep.append(r)
        self._queue = keep
        self._expired_total += len(dead)
        self._expired_since_tick += len(dead)
        return dead

    def _fail_expired(self, reqs: List[_Request]) -> None:
        for r in reqs:
            self._m_reject_reason["deadline"].inc()
            if not r.future.cancelled():
                r.future.set_exception(DeadlineExceeded(
                    "deadline expired before dispatch (per-tick sweep)"))

    def _fail_drained(self, reqs: List[_Request]) -> None:
        for r in reqs:
            self._m_reject_reason["shutdown"].inc()
            if not r.future.cancelled():
                r.future.set_exception(SchedulerClosed(
                    "scheduler closed before dispatch (bounded drain)"))

    def _loop(self):
        while True:
            expired: List[_Request] = []
            drained: List[_Request] = []
            reqs = None
            terminal = False
            with self._cond:
                if not self._queue and not self._stop:
                    with trace.span("serve.idle"):
                        while not self._queue and not self._stop:
                            self._cond.wait()
                now = time.monotonic()
                # Deadline sweep FIRST: an expired request must not be
                # chosen as the head nor occupy a tick slot.
                expired += self._sweep_expired(now)
                if (self._stop and self._queue
                        and self._drain_deadline is not None
                        and now >= self._drain_deadline):
                    # bounded drain exhausted: shed the remainder so
                    # close(drain_s=...) returns promptly; the sheds flow
                    # into the terminal accounting record below
                    drained = list(self._queue)
                    self._queue.clear()
                    self._rejected_total += len(drained)
                    self._rejected_since_tick += len(drained)
                if not self._queue:
                    if self._stop:      # stop requested, queue drained
                        # Hand off to the completion stage: in-flight
                        # ticks may still fail and re-credit their
                        # reject/expiry attribution, so the terminal
                        # accounting record is flushed THERE, after the
                        # pipeline drains (`_completion_loop`).
                        self._complete_stop = True
                        self._cond.notify_all()
                        terminal = True
                    # else: the sweep emptied the queue mid-serve — fail
                    # the shed futures below and go back to waiting
                else:
                    # Pipeline back-pressure: at most `pipeline_depth`
                    # ticks in flight; completion pops wake this wait. A
                    # bounded drain that expires while waiting falls
                    # through (reqs stays None) to the top-of-loop shed
                    # instead of cutting past the depth bound.
                    if len(self._inflight) >= self.pipeline_depth:
                        with trace.span("serve.pipeline_full"):
                            while (len(self._inflight)
                                   >= self.pipeline_depth):
                                if (self._stop
                                        and self._drain_deadline is not None
                                        and time.monotonic()
                                        >= self._drain_deadline):
                                    break
                                self._cond.wait(timeout=0.05)
                    if len(self._inflight) < self.pipeline_depth:
                        # a budget may have lapsed during the slot wait
                        expired += self._sweep_expired(time.monotonic())
                    if (len(self._inflight) < self.pipeline_depth
                            and self._queue):
                        head = self._queue[0]
                        deadline = head.t_submit + self.max_wait_ms / 1e3
                        remaining = deadline - time.monotonic()
                        if (remaining > 0 and self._full_key() is None
                                and not (self._stop or self._flush)):
                            with trace.span("serve.fill_wait"):
                                while remaining > 0:
                                    self._cond.wait(timeout=remaining)
                                    if (self._full_key() is not None
                                            or self._stop or self._flush):
                                        break
                                    remaining = deadline - time.monotonic()
                        # late sweep: a request whose budget ran out
                        # DURING the coalescing wait must not take a
                        # tick slot
                        expired += self._sweep_expired(time.monotonic())
                        if self._queue:
                            # a full group anywhere in the queue outranks
                            # the partial head tick; the head still
                            # dispatches by its deadline
                            key = self._full_key() or self._queue[0].key
                            reqs, rest = [], deque()
                            while self._queue:
                                r = self._queue.popleft()
                                if (r.key == key
                                        and len(reqs) < self.max_batch):
                                    reqs.append(r)
                                else:
                                    rest.append(r)
                            depth = len(reqs) + len(rest)
                            self._queue = rest
                            rejected = self._rejected_since_tick
                            self._rejected_since_tick = 0
                            n_expired = self._expired_since_tick
                            self._expired_since_tick = 0
                            # degrade rung for this tick, from the queue
                            # depth observed at the cut (hysteresis
                            # inside the controller — repro.serve.degrade)
                            level = (self.degrade.on_tick_cut(depth)
                                     if self.degrade is not None else 0)
                            self._busy = True
            if expired:
                self._fail_expired(expired)
            if drained:
                self._fail_drained(drained)
            if terminal:
                return
            if reqs is None:
                continue
            try:
                self._dispatch(reqs, depth, rejected, n_expired, level)
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()

    def _assemble_block(self, reqs: List[_Request]) -> np.ndarray:
        """Host-side tick assembly (PR 10): stack and edge-pad the HOST
        query rows in numpy, so the whole tick pays exactly ONE H2D
        (inside the backend's `dispatch_device`) instead of per-submit
        transfers plus a device-side pad. Pad semantics match
        `pad_block` exactly — same bytes, so bit-identity to the
        synchronous path is preserved."""
        qs = np.stack([r.q for r in reqs])
        b = qs.shape[0]
        if b < self.max_batch:
            qs = np.concatenate(
                [qs, np.broadcast_to(qs[-1:],
                                     (self.max_batch - b, qs.shape[1]))])
        return qs

    def _dispatch(self, reqs: List[_Request], depth: int, rejected: int = 0,
                  expired: int = 0, level: int = 0):
        """DISPATCH stage: assemble, stage, and launch the tick's device
        work, then hand an `_InflightTick` to the completion stage — no
        host sync on this thread (the JAX dispatch returns unmaterialized
        device arrays; `_complete` performs the single blocking D2H)."""
        t_dispatch = time.monotonic()
        seq = self._tick_seq
        self._tick_seq += 1
        k, c = reqs[0].key
        # rung 2+ of the degrade ladder dispatches at a WIDENED contract:
        # the result is a valid c_eff-approximation, reported as such
        # (TickStats.degrade_level) and audited at c_eff (module doc)
        c_eff = (self.degrade.widened_c(c)
                 if self.degrade is not None else c)
        if (level >= 3 and self.degrade is not None
                and self.degrade.cache is not None
                and getattr(self.engine, "current_snapshot", None)
                is not None):
            self._dispatch_cache_only(reqs, depth, rejected, expired,
                                      level, t_dispatch, seq)
            return
        epoch = None
        snap = None
        programs_before = _program_count()
        sp = trace.span("serve.tick", tick=seq, batch=len(reqs),
                        depth=depth, k=k)
        try:
            with sp:
                if faults.ACTIVE is not None:
                    faults.fire("serve.slow_tick")
                    faults.fire("serve.dispatch")
                if trace.is_enabled():
                    # retroactive cross-thread spans: each request's
                    # admission → dispatch queue wait, timed from its
                    # client-thread submit; inside the tick span so the
                    # records attribute to the tick that served them
                    for r in reqs:
                        trace.event("serve.queue_wait", r.t_submit,
                                    t_dispatch - r.t_submit, k=k, tick=seq)
                qs = self._assemble_block(reqs)
                # Pin ONE index snapshot for the whole tick (module doc):
                # a hot-swap concurrent with this dispatch lands between
                # ticks, never inside one.
                snap_fn = getattr(self.engine, "current_snapshot", None)
                dispatch_fn = getattr(self.engine, "dispatch_batch_at",
                                      None)
                if snap_fn is not None:
                    snap = snap_fn()
                    epoch = getattr(snap, "epoch", None)
                    sp.set(epoch=epoch)
                    if dispatch_fn is not None:
                        # the serving entry: one H2D, device handles out,
                        # donation-safe on accelerators
                        res = dispatch_fn(snap, qs, k=k, c=c_eff)
                    else:
                        res = self.engine.query_batch_at(
                            snap, jnp.asarray(qs), k=k, c=c_eff)
                else:
                    res = self.engine.query_batch(jnp.asarray(qs), k=k,
                                                  c=c_eff)
        except Exception as e:                    # propagate to every caller
            for r in reqs:
                if not r.future.cancelled():
                    r.future.set_exception(e)
            # This tick records no TickStats — re-credit the rejects and
            # expiries it was carrying so the NEXT cut (or the terminal
            # flush at close) attributes them instead of dropping them.
            with self._cond:
                self._rejected_since_tick += rejected
                self._expired_since_tick += expired
            return
        # Compile attribution is sampled HERE, not in the completion
        # stage: tracing/compilation happens synchronously on this
        # thread, so the delta cleanly brackets this tick's dispatch even
        # with other ticks in flight.
        tick = _InflightTick(
            seq, reqs, res, snap, epoch, k, c_eff, depth, rejected, expired,
            level, t_dispatch,
            compiles=max(0, _program_count() - programs_before))
        with self._cond:
            self._inflight.append(tick)
            tick.inflight = len(self._inflight)
            self._m_inflight.set(len(self._inflight))
            self._cond.notify_all()

    # --------------------------------------------------------- completion
    def _completion_loop(self):
        """COMPLETION stage: consume in-flight ticks FIFO, each with one
        blocking D2H, and resolve futures — entirely off the dispatch
        path. Exits after the dispatcher signals `_complete_stop` and the
        pipeline drains, flushing the terminal accounting record last (a
        completion-stage failure re-credits rejects, so the terminal
        flush must come after the final tick settles)."""
        while True:
            with self._cond:
                while not self._inflight and not self._complete_stop:
                    self._cond.wait()
                if not self._inflight:          # stopping and drained
                    tail = self._rejected_since_tick
                    self._rejected_since_tick = 0
                    tail_exp = self._expired_since_tick
                    self._expired_since_tick = 0
                    if tail or tail_exp:
                        self._ticks.append(TickStats(
                            batch=0, queue_depth=0, fill_ratio=0.0,
                            wait_ms=0.0, latencies_ms=(),
                            rejected=tail, expired=tail_exp))
                    self._cond.notify_all()
                    return
                # PEEK, don't pop: flush()/close() must keep seeing the
                # tick until its futures are resolved.
                tick = self._inflight[0]
            self._complete(tick)
            with self._cond:
                self._inflight.popleft()
                self._m_inflight.set(len(self._inflight))
                self._cond.notify_all()

    def _complete(self, t: _InflightTick):
        reqs = t.reqs
        t_transfer = time.monotonic()
        try:
            with trace.span("serve.transfer", tick=t.seq, batch=len(reqs),
                            epoch=t.epoch, inflight=t.inflight):
                if faults.ACTIVE is not None:
                    faults.fire("serve.transfer")
                # THE one blocking D2H per tick: futures resolve to HOST
                # (numpy) QueryResults — per-request row views are
                # zero-copy, where B×fields device slices would dominate
                # the tick cost. A deferred dispatch error (async
                # runtime) also surfaces here and is failed typed below.
                if trace.is_enabled():
                    # traced: the wait for the tick's device work and
                    # the copy as spans of their own (module doc)
                    with trace.span("serve.ready", tick=t.seq):
                        jax.block_until_ready(t.res)
                    with trace.span("serve.d2h", tick=t.seq):
                        host = jax.device_get(t.res)
                else:
                    host = jax.device_get(t.res)
        except Exception as e:
            # Fail exactly THIS tick's futures; later in-flight ticks
            # keep completing. Reject/expiry attribution re-credits to
            # the next cut or the terminal flush (PR 9 invariant: every
            # reject lands in exactly one TickStats).
            for r in reqs:
                if not r.future.cancelled():
                    r.future.set_exception(e)
            with self._cond:
                self._rejected_since_tick += t.rejected
                self._expired_since_tick += t.expired
            return
        now = time.monotonic()
        transfer_ms = (now - t_transfer) * 1e3
        tick = TickStats(
            batch=len(reqs), queue_depth=t.depth,
            fill_ratio=len(reqs) / self.max_batch,
            wait_ms=(t.t_dispatch - reqs[0].t_submit) * 1e3,
            latencies_ms=tuple((now - r.t_submit) * 1e3 for r in reqs),
            rejected=t.rejected, epoch=t.epoch,
            compiles=t.compiles,
            expired=t.expired, degrade_level=t.level,
            transfer_ms=transfer_ms, inflight=t.inflight)
        # Record the tick BEFORE resolving futures: a client that wakes
        # from f.result() must already see it in stats()/tick_log.
        with self._cond:
            self._ticks.append(tick)
        self._m_ticks.inc()
        if tick.compiles:
            self._m_compiles.inc(tick.compiles)
        self._m_depth.set(t.depth)
        self._m_transfer.observe(transfer_ms)
        for r in reqs:
            self._m_wait.observe((t.t_dispatch - r.t_submit) * 1e3)
            self._m_latency.observe((now - r.t_submit) * 1e3)
        for i, r in enumerate(reqs):              # pad rows masked out here
            per_q = jax.tree_util.tree_map(lambda x, i=i: x[i], host)
            if not r.future.cancelled():
                r.future.set_result(per_q)
            if self.auditor is not None:
                # audited at the contract actually served (c_eff on
                # degraded ticks) — the accuracy gauge judges the
                # relaxed, REPORTED contract, not the requested one
                self.auditor.observe(np.asarray(r.q), per_q, k=t.k,
                                     c=t.c_eff, snapshot=t.snap)

    def _dispatch_cache_only(self, reqs: List[_Request], depth: int,
                             rejected: int, expired: int, level: int,
                             t_dispatch: float, seq: int):
        """Degrade rung 3: answer LRU hits against the pinned snapshot,
        shed misses with `QueueFull` (reject reason `degraded`).

        A hit is an exact per-query result computed earlier in the SAME
        index generation — the cache invalidates on any snapshot change
        (`CachingBackend._check_epoch`), so its certified (r↓, r↑) bounds
        are as valid as at first compute. Misses shed instead of
        dispatching: rung 3 exists to take the rank table out of the
        serving path entirely."""
        cache = self.degrade.cache
        k, c = reqs[0].key
        c_eff = self.degrade.widened_c(c)
        snap = self.engine.current_snapshot()
        epoch = getattr(snap, "epoch", None)
        rt, users, delta = snap.rank_table, snap.query_users(), snap.corr
        hits: List[Tuple[_Request, object, float]] = []
        misses: List[_Request] = []
        with trace.span("serve.cache_only", tick=seq, batch=len(reqs),
                        depth=depth, k=k, epoch=epoch, level=level):
            for r in reqs:
                row = np.asarray(r.q)       # host already (PR 10 submit)
                # entries may have been cached at the base contract or at
                # the rung-2 widened one — a hit at either serves
                res, c_hit = None, c
                for c_try in ((c, c_eff) if c_eff != c else (c,)):
                    res = cache.lookup_only(rt, users, row, k=k, c=c_try,
                                            delta=delta)
                    if res is not None:
                        c_hit = c_try
                        break
                if res is None:
                    misses.append(r)
                else:
                    hits.append((r, res, c_hit))
            if hits:
                # ONE D2H for the whole rung-3 tick (the per-request
                # device_get here was measurable: B blocking transfers
                # per tick, exactly the pattern PR 10 removes). Cached
                # entries are device-resident per-query QueryResults;
                # device_get over the list batches them.
                hosts = jax.device_get([res for _, res, _ in hits])
                hits = [(r, h, c_hit) for (r, _, c_hit), h
                        in zip(hits, hosts)]
        with self._cond:
            self._rejected_total += len(misses)
        now = time.monotonic()
        tick = TickStats(
            batch=len(hits), queue_depth=depth,
            fill_ratio=len(hits) / self.max_batch,
            wait_ms=(t_dispatch - reqs[0].t_submit) * 1e3,
            latencies_ms=tuple((now - r.t_submit) * 1e3
                               for r, _, _ in hits),
            rejected=rejected + len(misses), epoch=epoch,
            expired=expired, degrade_level=level)
        with self._cond:
            self._ticks.append(tick)
        self._m_ticks.inc()
        self._m_depth.set(depth)
        if misses:
            self._m_rejected.inc(len(misses))
            self._m_reject_reason["degraded"].inc(len(misses))
        for r in misses:
            if not r.future.cancelled():
                r.future.set_exception(QueueFull(
                    "shed at degrade level 3 (cache-only serving): "
                    "no cached result for this query"))
        for r, host, c_hit in hits:
            self._m_wait.observe((t_dispatch - r.t_submit) * 1e3)
            self._m_latency.observe((now - r.t_submit) * 1e3)
            if not r.future.cancelled():
                r.future.set_result(host)
            if self.auditor is not None:
                self.auditor.observe(np.asarray(r.q), host, k=k, c=c_hit,
                                     snapshot=snap)
