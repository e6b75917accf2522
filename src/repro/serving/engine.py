"""Continuous-batching serving engine (DESIGN.md §Serving).

The ROADMAP's north star is serving heavy traffic, and the paper's core
claim — operator-level dataflow execution sustains high utilization across
diverse logical patterns — applies to inference exactly as to training:
requests arrive one at a time with arbitrary patterns, and the engine's job
is to coalesce them into the same pooled micro-batches the trainer runs.

Pieces:

* **Bounded admission queue** — ``submit`` enqueues a request and returns a
  ``concurrent.futures.Future``; a full queue blocks the caller (or raises
  ``queue.Full`` with a timeout), which is the backpressure contract: load
  beyond capacity queues at the CLIENT, not in unbounded engine memory.
* **Batcher thread** — drains the queue into operator-level micro-batches
  with a size/age flush policy: flush as soon as ``max_batch`` requests are
  pending, or when the oldest pending request has waited ``max_wait_ms``.
  One batcher thread by design (mirrors the pipeline's single scheduler
  thread): it owns the params handle, so semantic-cache staging — the same
  ``plan``/``apply_to`` handshake ``data/pipeline.py`` uses for training —
  needs no cross-thread sequencing.
* **Cross-request sharing** — exact-duplicate in-flight requests (same
  ``QueryInstance.key()``) coalesce onto ONE computed row before the batch
  is padded (``coalesced`` counter in ``stats()``), and the executor's plan
  compiler (DESIGN.md §Compiler) CSE-merges identical *subtrees* of the
  distinct queries that remain — duplicate subqueries across concurrent
  requests are computed once per micro-batch. With a ``mat_cache``
  (``core/matcache.py``) the reuse goes CROSS-batch: the batcher consults
  the materialized-row cache before padding, encodes only the misses, and
  duplicate-heavy traffic serves repeat queries off cached rows (version-
  stamped, invalidated on ``update_params`` and KG writes).
* **Signature-bucketed padding** — micro-batches pad to the next power-of-
  two size by repeating the last query (padded rows are computed and
  discarded). Bounding the batch-size set bounds the jit signature set: the
  all-entity scorer sees only pow2 ``B``s, and the executor's per-signature
  compiled encode programs (``PooledExecutor.encode_fn_compiled``) stay hot,
  so a replayed workload runs at ZERO steady-state retraces.
* **Chunked all-entity scoring** — with a semantic store the engine scores
  through ``score_all_chunked`` (streams H_sem from the mmap store in
  bounded chunks; the full ``[E, d_l]`` table never materializes); dense
  mode scores through one process-wide cached jit per model (``scorer_for``
  — also the fix for ``serve_batch`` retracing ``score_all`` per call).
* **Per-request latency accounting** — each future's result carries its
  end-to-end latency; ``stats()`` aggregates p50/p95/p99 over a bounded
  window of completed requests.

Offline/online parity: the engine and the one-shot ``launch/serve.py::
serve_batch`` baseline share the SAME compiled encode programs, the SAME
cached scorer and the SAME ``topk_desc`` — so on identical micro-batch
compositions their per-request top-k is bit-identical, which
``tests/test_serving.py`` asserts on closed- and open-loop replays.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.compile_cache import CompileCache
from repro.core.executor import PooledExecutor
from repro.core.patterns import QueryInstance
from repro.obs.registry import get_registry
from repro.obs.trace import TRACER


class StaleVersionError(RuntimeError):
    """A version-pinned request fell outside the engine's staleness bound.

    Raised synchronously by ``submit`` when the pin is already out of bound
    (or no longer retained) at admission, and set on the future when writes
    land while the request is queued. Typed so clients can distinguish
    load-shedding from real failures and re-submit unpinned (or re-pin to
    ``engine.graph_version``)."""

    def __init__(self, pinned: int, current: int, bound: int):
        super().__init__(
            f"graph version {pinned} is stale: current {current}, "
            f"max_staleness_versions {bound}")
        self.pinned = pinned
        self.current = current
        self.bound = bound


def topk_desc(scores: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest entries per row, descending — argpartition
    (linear in E) followed by an O(k log k) sort of just the survivors."""
    k = min(k, scores.shape[1])
    part = np.argpartition(-scores, k - 1, axis=1)[:, :k]
    part_scores = np.take_along_axis(scores, part, axis=1)
    order = np.argsort(-part_scores, axis=1, kind="stable")
    return np.take_along_axis(part, order, axis=1)


# --------------------------------------------------------------------------
# Process-wide scorer cache (the serve_batch re-jit fix)
# --------------------------------------------------------------------------

class CachedScorer:
    """One jitted ``model.score_all`` with a host-side trace counter.

    The counter bumps only while jax is TRACING the body, so ``traces`` is
    exactly the number of compilations — the regression surface for the old
    ``serve_batch`` bug (``jax.jit(model.score_all)`` rebuilt per call, so
    every batch retraced)."""

    def __init__(self, model, ctx=None):
        self._counter = {"traces": 0}
        counter = self._counter

        def _score(params, q):
            counter["traces"] += 1  # runs at trace time only
            return model.score_all(params, q)

        kwargs = ctx.replicated_out_kwargs() if ctx is not None else {}
        self._fn = jax.jit(_score, **kwargs)

    def __call__(self, params, q):
        return self._fn(params, q)

    @property
    def traces(self) -> int:
        return self._counter["traces"]


_SCORER_CACHE = CompileCache(32, name="score_all_jit")
_SCORER_LOCK = threading.Lock()


def scorer_for(model, ctx=None) -> CachedScorer:
    """Process-wide cached jit of ``model.score_all``.

    Keyed by everything ``score_all`` actually closes over — model class,
    config and entity count (plus the mesh layout when sharded) — so two
    instances of the same zoo family share one compiled program, and
    repeated ``serve_batch`` calls trace exactly once per scorer shape."""
    key = (type(model).__name__, model.cfg,
           getattr(model, "n_entities", None),
           ctx.describe() if ctx is not None and ctx.is_sharded else None)
    with _SCORER_LOCK:
        s = _SCORER_CACHE.get(key)
        if s is None:
            s = _SCORER_CACHE.put(key, CachedScorer(model, ctx))
    return s


def pad_to_bucket(queries: Sequence[QueryInstance]):
    """Pad a micro-batch to the next power-of-two length by repeating the
    last query. Real rows are untouched (pattern-sorted canonicalization and
    pool padding happen downstream in ``prepare`` regardless); the duplicate
    rows are scored and dropped. Returns ``(padded, n_real)``."""
    n = len(queries)
    if n == 0:
        return [], 0
    b = 1 << (n - 1).bit_length()
    return list(queries) + [queries[-1]] * (b - n), n


# --------------------------------------------------------------------------
# Engine
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ServingConfig:
    max_batch: int = 16        # size-triggered flush threshold
    max_wait_ms: float = 5.0   # age-triggered flush: oldest pending request
    queue_depth: int = 256     # bounded admission queue (backpressure)
    top_k: int = 10
    bucket: bool = True        # signature-bucketed (pow2) batch padding
    record_batches: bool = False  # keep a log of (padded batch, results)
    latency_window: int = 8192    # completed-request latencies retained
    # Staleness-bounded serving (DESIGN.md §LiveStore; needs ``kg=``): a
    # version-pinned request is served from its pinned snapshot's params as
    # long as the live graph is at most this many versions ahead; beyond
    # the bound it is SHED with a typed StaleVersionError instead of being
    # silently served stale rows. 0 = pinned requests only survive until
    # the next write.
    max_staleness_versions: int = 0
    # Hot-swap semantics (DESIGN.md §ServingTier): when True, every request
    # is stamped with the params version current at ADMISSION and served on
    # exactly those params even if ``update_params`` lands while it queues —
    # the replica-tier swap contract (in-flight requests complete on the
    # params they were admitted under). Off by default: the single-engine
    # path serves whatever params are current at execute time, unchanged.
    pin_params_on_admit: bool = False


@dataclasses.dataclass
class _Request:
    query: QueryInstance
    top_k: int
    future: Future
    t_submit: float
    # Async-span id threaded submit -> flush -> dispatch -> complete. 0 when
    # tracing is off. Coalesced duplicates keep DISTINCT ids (each opened at
    # its own submit) while sharing one batch/encode/score span.
    trace_id: int = 0
    # Pinned graph version (None = serve at whatever version is current at
    # execute time). Pinned requests are grouped per version by the batcher
    # and served from that version's retained params snapshot.
    pin_version: Optional[int] = None
    # Params version current at admission (``pin_params_on_admit`` only;
    # stays 0 otherwise). Batches group per version so a swap landing
    # mid-queue never mixes old- and new-params rows in one micro-batch.
    params_version: int = 0


@dataclasses.dataclass
class BatchRecord:
    """One executed micro-batch, for offline-oracle replay: the exact padded
    composition the engine ran (duplicate in-flight requests coalesce to one
    computed row first, so ``queries`` holds UNIQUE real rows), plus one
    result per computed real row, in first-submission order. Each logged
    row records the selection at the engine's default ``top_k`` whenever any
    request for that row used it (so fixed-k oracle replay compares
    row-for-row); rows requested ONLY at custom k carry that k."""

    queries: List[QueryInstance]   # padded unique composition as executed
    n_real: int                    # unique real rows (pre-padding)
    flush: str                     # size | age | drain
    results: List[Dict]            # one per real row


class ServingEngine:
    """Async continuous-batching NGDB query service.

    ``submit`` is thread-safe and returns a future; a single batcher thread
    coalesces pending requests into pooled micro-batches and resolves the
    futures. ``sem_cache``/``sem_rows_fn`` switch on out-of-core serving:
    anchor rows are staged into the device hot set on the batcher thread
    before encode, and all-entity scoring streams H_sem via ``sem_rows_fn``
    (e.g. ``SemanticStore.read_rows``) instead of a full-resident table.
    """

    def __init__(self, model, params, executor=None,
                 cfg: Optional[ServingConfig] = None, sem_cache=None,
                 sem_rows_fn=None, ctx=None, started: bool = True,
                 mat_cache=None, latency_window: Optional[int] = None,
                 kg=None, obs_labels: Optional[Dict[str, str]] = None,
                 name: Optional[str] = None):
        self.model = model
        self.params = params
        # ``name`` labels the batcher thread and its tracer lane (replicas
        # pass "replica 0" etc. so lanes stay distinguishable); ``obs_labels``
        # labels every registry metric this engine publishes (e.g.
        # replica="0"). Both default to the historical unlabeled identity.
        self.name = name or "serving"
        self.cfg = cfg or ServingConfig()
        if latency_window is not None:
            # Constructor-level override so callers that never build a
            # ServingConfig can still size the percentile window.
            self.cfg = dataclasses.replace(self.cfg,
                                           latency_window=latency_window)
        if self.cfg.max_batch < 1 or self.cfg.queue_depth < 1:
            raise ValueError("max_batch and queue_depth must be >= 1")
        if self.cfg.latency_window < 1:
            raise ValueError("latency_window must be >= 1")
        self.ctx = ctx
        self.executor = executor or PooledExecutor(model, b_max=256, ctx=ctx)
        if sem_cache is not None and sem_rows_fn is None:
            raise ValueError(
                "out-of-core serving needs sem_rows_fn (e.g. store.read_rows)"
                " to stream H_sem for all-entity scoring")
        self.sem_cache = sem_cache
        self.sem_rows_fn = sem_rows_fn
        # Materialized-subquery cache (core/matcache.py): the batcher
        # consults it BEFORE padding, so a duplicate-of-an-earlier-batch
        # request costs one host row copy instead of a device encode. The
        # engine owns the consult/insert; leave the executor's own
        # ``mat_cache`` unset here or every miss would be double-counted.
        self.mat_cache = mat_cache
        if (mat_cache is not None
                and getattr(self.executor, "mat_cache", None) is not None):
            raise ValueError(
                "pass mat_cache to the engine OR the executor, not both")
        # Live-graph attachment (DESIGN.md §LiveStore): with ``kg`` set the
        # engine tracks the graph's monotonic ``graph_version``, retains the
        # params active at each recent version, and enforces the
        # ``max_staleness_versions`` admission bound for pinned requests.
        # The write listener is held WEAKLY by the KG, so a discarded engine
        # is collected. Out-of-core sem staging mutates a device hot set
        # shared across params snapshots, which version-pinned replay cannot
        # coexist with — explicitly unsupported rather than silently wrong.
        if kg is not None and sem_cache is not None:
            raise ValueError(
                "staleness-bounded serving (kg=...) does not support the "
                "out-of-core sem_cache hot set yet — pass one or the other")
        if self.cfg.max_staleness_versions < 0:
            raise ValueError("max_staleness_versions must be >= 0")
        self.kg = kg
        self._graph_version = kg.graph_version if kg is not None else -1
        self._version_retention = max(self.cfg.max_staleness_versions + 1, 4)
        self._version_params: Dict[int, object] = (
            {self._graph_version: params} if kg is not None else {})
        if kg is not None:
            kg.add_invalidation_listener(self._on_kg_write)
        # Params-version pinning (replica-tier hot swap). Mutually exclusive
        # with the graph-version machinery (one version axis per engine; the
        # replica tier is dense-params) and with sem staging (the device hot
        # set is shared across params snapshots, so admitted-params replay
        # cannot coexist with it) — explicit rather than silently wrong.
        if self.cfg.pin_params_on_admit and (kg is not None
                                             or sem_cache is not None):
            raise ValueError(
                "pin_params_on_admit does not compose with kg= or sem_cache=")
        self._params_version = 0
        self._params_retention = 4
        self._params_by_version: Dict[int, object] = (
            {0: params} if self.cfg.pin_params_on_admit else {})
        self._scorer = scorer_for(model, ctx)
        self._scorer_traces0 = self._scorer.traces
        self._sharing0 = dict(self.executor.sharing_stats())
        self._q: "queue.Queue[_Request]" = queue.Queue(maxsize=self.cfg.queue_depth)
        # Unpack buffer for grouped admissions (``submit_many`` enqueues a
        # whole batch as ONE queue entry); owned by the batcher thread.
        self._pending: "deque[_Request]" = deque()
        self._stop = threading.Event()
        self._closed = False
        self._lock = threading.Lock()
        # Registry metrics (DESIGN.md §Observability): same counters the
        # engine always kept, now visible in process-wide snapshots. The
        # latency ring buffer is a Histogram whose window IS
        # cfg.latency_window, reported as window_n in stats().
        self._metrics = get_registry().group("serving", **(obs_labels or {}))
        self._latency = self._metrics.histogram(
            "latency_ms", window=self.cfg.latency_window)
        self._submitted = self._metrics.counter("submitted")
        self._completed = self._metrics.counter("completed")
        self._batches = self._metrics.counter("batches")
        self._batch_rows = self._metrics.counter("batch_rows")
        self._padded_rows = self._metrics.counter("padded_rows")
        self._coalesced = self._metrics.counter("coalesced")
        self._failures = self._metrics.counter("failures")
        self._flushes = {k: self._metrics.counter("flushes", kind=k)
                         for k in ("size", "age", "drain")}
        self._queue_depth = self._metrics.gauge("queue_depth")
        self._occupancy = self._metrics.gauge("batch_occupancy")
        # §LiveStore counters: requests shed for staleness (typed error, NOT
        # failures) and per-version-lag served counts (lag 0 = current).
        self._stale_sheds = self._metrics.counter("stale_sheds")
        self._version_served: Dict[int, object] = {}
        self._graph_version_gauge = self._metrics.gauge("graph_version")
        self._graph_version_gauge.set(self._graph_version)
        # After a registry-wide reset() the derived deltas (scorer traces,
        # sharing) must re-baseline or they would go negative; the hook is
        # held weakly, so a collected engine takes it along.
        get_registry().on_reset(self._rebaseline)
        self.batch_log: List[BatchRecord] = []
        self._thread: Optional[threading.Thread] = None
        if started:
            self.start()

    def _rebaseline(self) -> None:
        """Registry-reset hook: zero the derived deltas that live outside
        the registry (jit-trace counts, cumulative sharing totals)."""
        self._scorer_traces0 = self._scorer.traces
        self._sharing0 = dict(self.executor.sharing_stats())
        with self._lock:
            for k in list(self._flushes):
                if k not in ("size", "age", "drain"):
                    del self._flushes[k]
            self._version_served = {}

    def _on_kg_write(self, reason: str) -> None:
        """KG write listener (weakly held by the graph): advance the tracked
        graph version and retain the CURRENT params under the new version —
        until incremental maintenance publishes fine-tuned params via
        ``update_params``, the new version serves with the old weights (the
        staleness bound is about ROW consistency, which the version-keyed
        caches own). Old versions age out of retention; a request pinned to
        an evicted version is shed."""
        with self._lock:
            if self.kg is None:
                return
            self._graph_version = self.kg.graph_version
            self._version_params[self._graph_version] = self.params
            while len(self._version_params) > self._version_retention:
                del self._version_params[min(self._version_params)]
        self._graph_version_gauge.set(self._graph_version)

    @property
    def graph_version(self) -> int:
        """The newest graph version this engine has observed (-1 when no
        ``kg`` is attached)."""
        with self._lock:
            return self._graph_version

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"{self.name}-batcher")
        self._thread.start()

    def close(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop accepting requests; by default serve everything already
        admitted (the batcher flushes the tail immediately once the queue
        is empty), then join the batcher thread."""
        with self._lock:
            self._closed = True
        if drain and self._thread is not None and self._thread.is_alive():
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    if self._completed >= self._submitted:
                        break
                time.sleep(0.005)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        # Anything still queued (drain=False or timeout) fails loudly rather
        # than leaving callers blocked on forever-pending futures.
        self._fail_queued()

    def _fail_queued(self) -> None:
        try:
            while True:
                entry = self._q.get_nowait()
                for r in (entry if type(entry) is list else (entry,)):
                    if r.trace_id:
                        TRACER.async_end("request", r.trace_id, failed=True)
                    r.future.set_exception(
                        RuntimeError("serving engine closed"))
                    with self._lock:
                        self._completed += 1
        except queue.Empty:
            pass

    def __enter__(self) -> "ServingEngine":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------ admission
    def submit(self, query: QueryInstance, top_k: Optional[int] = None,
               timeout: Optional[float] = None,
               pin_version: Optional[int] = None) -> Future:
        """Admit one request. Blocks when the admission queue is full
        (bounded-memory backpressure); with ``timeout`` raises ``queue.Full``
        instead. The returned future resolves to the same result dict
        ``serve_batch`` produces, plus ``latency_ms``/``batch_size``.

        ``pin_version`` (needs ``kg=`` at construction) pins the request to
        one graph version: it is served from that version's retained params
        with version-keyed plan/materialized rows — bit-identical replay
        against the pinned snapshot — or shed with ``StaleVersionError``
        when the live graph has moved more than
        ``cfg.max_staleness_versions`` ahead (checked both here at
        admission and again at execute time, since writes can land while
        the request queues)."""
        k = self.cfg.top_k if top_k is None else top_k
        if k < 1:
            raise ValueError(f"top_k must be >= 1, got {k}")
        if pin_version is not None:
            if self.kg is None:
                raise ValueError(
                    "pin_version needs a live graph: construct the engine "
                    "with kg=...")
            with self._lock:
                cur = self._graph_version
                if pin_version < 0 or pin_version > cur:
                    raise ValueError(
                        f"unknown graph version {pin_version} (current {cur})")
                if (cur - pin_version > self.cfg.max_staleness_versions
                        or pin_version not in self._version_params):
                    self._stale_sheds += 1
                    raise StaleVersionError(pin_version, cur,
                                            self.cfg.max_staleness_versions)
        with self._lock:
            if self._closed:
                raise RuntimeError("serving engine is closed")
            self._submitted += 1
            pv = self._params_version if self.cfg.pin_params_on_admit else 0
        trace_id = 0
        if TRACER.enabled:
            trace_id = TRACER.next_id()
            TRACER.async_begin("request", trace_id, pattern=query.pattern,
                               top_k=k)
        r = _Request(query, k, Future(), time.perf_counter(), trace_id,
                     pin_version, pv)
        try:
            self._q.put(r, timeout=timeout)
        except queue.Full:
            with self._lock:
                self._submitted -= 1
            if trace_id:
                TRACER.async_end("request", trace_id, rejected=True)
            raise
        # The queue-depth gauge is refreshed by the batcher at every flush;
        # updating it per admission too would cost a qsize() mutex round
        # trip on the hot path for no extra observability.
        # close() may have stopped the batcher and drained the queue between
        # our _closed check and the put; a straggler landing in the
        # now-unwatched queue must fail, not strand its future forever.
        if self._stop.is_set():
            self._fail_queued()
        return r.future

    def submit_many(self, queries: Sequence[QueryInstance],
                    top_k: Optional[int] = None,
                    timeout: Optional[float] = None) -> List[Future]:
        """Admit a batch as ONE admission action: a single closed-check /
        counter update under the lock and a single queue entry for the whole
        group, so per-request admission costs (lock round trips, queue
        handoffs) are paid once per batch instead of once per query. The
        batcher unpacks the group in order, so batching behavior and results
        are identical to a ``submit`` loop. All requests in a group share
        one admission timestamp and params version — a group admits
        atomically with respect to hot swap. The bounded queue counts a
        group as one entry (one arrival event for backpressure purposes).
        Graph-version pinning stays on the single-request path."""
        if not queries:
            return []
        k = self.cfg.top_k if top_k is None else top_k
        if k < 1:
            raise ValueError(f"top_k must be >= 1, got {k}")
        with self._lock:
            if self._closed:
                raise RuntimeError("serving engine is closed")
            self._submitted += len(queries)
            pv = self._params_version if self.cfg.pin_params_on_admit else 0
        t0 = time.perf_counter()
        group = []
        for q in queries:
            trace_id = 0
            if TRACER.enabled:
                trace_id = TRACER.next_id()
                TRACER.async_begin("request", trace_id, pattern=q.pattern,
                                   top_k=k)
            group.append(_Request(q, k, Future(), t0, trace_id, None, pv))
        try:
            self._q.put(group, timeout=timeout)
        except queue.Full:
            with self._lock:
                self._submitted -= len(group)
            for r in group:
                if r.trace_id:
                    TRACER.async_end("request", r.trace_id, rejected=True)
            raise
        if self._stop.is_set():
            self._fail_queued()
        return [r.future for r in group]

    def queue_depth(self) -> int:
        """Entries currently waiting in the admission queue (the router's
        spill signal — approximate by nature, exact enough for load shaping).
        A grouped admission counts as one entry until the batcher unpacks
        it."""
        return self._q.qsize()

    # -------------------------------------------------------------- batcher
    def _next_request(self, timeout: Optional[float]) -> _Request:
        """Next single request for the batcher: drains the unpack buffer
        first, then the queue; a grouped entry (``submit_many``) refills the
        buffer. ``timeout=None`` means non-blocking. Raises ``queue.Empty``
        exactly like ``Queue.get`` — and only when the buffer is empty, so
        the batcher can never exit with unpacked requests stranded."""
        if self._pending:
            return self._pending.popleft()
        entry = (self._q.get_nowait() if timeout is None
                 else self._q.get(timeout=timeout))
        if type(entry) is list:
            self._pending.extend(entry)
            return self._pending.popleft()
        return entry

    def _run(self) -> None:
        TRACER.set_lane(f"{self.name} batcher")
        while True:
            try:
                first = self._next_request(0.05)
            except queue.Empty:
                if self._stop.is_set():
                    return
                continue
            batch = [first]
            # Age from SUBMIT time, not dequeue time: a request that sat in
            # the admission queue behind a long batch has already spent its
            # wait budget, so the latency bound covers queueing too.
            deadline = first.t_submit + self.cfg.max_wait_ms / 1e3
            flush = "size"
            while len(batch) < self.cfg.max_batch:
                try:
                    # Greedy first: coalesce everything ALREADY queued before
                    # consulting the age deadline — an expired deadline bounds
                    # additional waiting, it must not collapse a backlogged
                    # engine into size-1 batches.
                    batch.append(self._next_request(None))
                    continue
                except queue.Empty:
                    pass
                # Unlocked read: _closed is a GIL-atomic bool that only ever
                # flips False -> True; at worst this loop notices one 50 ms
                # get-timeout late, which close()'s drain wait absorbs —
                # not worth a contended lock acquisition per empty poll.
                if self._closed:
                    flush = "drain"  # tail: don't sit out the age window
                    break
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    flush = "age"
                    break
                try:
                    batch.append(self._next_request(min(remaining, 0.05)))
                except queue.Empty:
                    continue
            self._queue_depth.set(self._q.qsize())
            if TRACER.enabled:
                TRACER.counter("serving_queue_depth", depth=self._q.qsize())
            self._execute(batch, flush)

    def _execute(self, batch: List[_Request], flush: str) -> None:
        batch = self._shed_stale(batch)
        if not batch:
            return
        # Pinned requests are served per pinned version (one params snapshot
        # + one cache keyspace per micro-batch); a mixed flush splits into
        # one group per distinct pin. Unpinned requests (pin None) ride the
        # current-version group. With ``pin_params_on_admit`` the admitted
        # params version splits the same way, so a hot swap landing between
        # dequeue and execute never mixes params generations in one batch.
        groups: Dict[Tuple, List[_Request]] = {}
        for r in batch:
            groups.setdefault((r.pin_version, r.params_version),
                              []).append(r)
        if len(groups) > 1:
            for g in groups.values():
                self._execute_group(g, flush)
            return
        self._execute_group(batch, flush)

    def _shed_stale(self, batch: List[_Request]) -> List[_Request]:
        """Execute-time staleness re-check: writes that landed while a
        pinned request queued can push it out of bound. Shed requests fail
        with the typed error and count as ``stale_sheds`` — never as
        ``failures``, and never through the poison-isolation retry path
        (a shed is deterministic, a solo retry would just shed again)."""
        if self.kg is None:
            return batch
        with self._lock:
            cur = self._graph_version
            bound = self.cfg.max_staleness_versions
            keep: List[_Request] = []
            shed: List[_Request] = []
            for r in batch:
                if (r.pin_version is not None
                        and (cur - r.pin_version > bound
                             or r.pin_version not in self._version_params)):
                    shed.append(r)
                else:
                    keep.append(r)
            self._stale_sheds += len(shed)
            self._completed += len(shed)
        for r in shed:
            if r.trace_id:
                TRACER.async_end("request", r.trace_id, shed=True)
            r.future.set_exception(StaleVersionError(r.pin_version, cur, bound))
        return keep

    def _execute_group(self, batch: List[_Request], flush: str) -> None:
        # Exception, not BaseException: SystemExit/KeyboardInterrupt take
        # the batcher down rather than being swallowed into futures. Within
        # Exception, only recoverable per-request errors (e.g. malformed
        # pattern → KeyError) get poison isolation — MemoryError fails the
        # whole batch at once, never an N-fold solo-retry storm of the same
        # allocation.
        try:
            with TRACER.span("batch", n=len(batch), flush=flush,
                             trace_ids=[r.trace_id for r in batch]):
                results = self._serve(batch, flush)
        except Exception as e:
            if isinstance(e, StaleVersionError):
                # Deterministic shed (pin evicted mid-batch by a concurrent
                # write): typed error, stale_sheds accounting, and NO solo
                # retry — a retry would just shed again.
                for r in batch:
                    if r.trace_id:
                        TRACER.async_end("request", r.trace_id, shed=True)
                    r.future.set_exception(e)
                with self._lock:
                    self._stale_sheds += len(batch)
                    self._completed += len(batch)
                return
            if len(batch) > 1 and not isinstance(e, MemoryError):
                # Isolate the poison request: one malformed query must not
                # fail its co-batched neighbors. Solo retries carry their own
                # flush label so stats/batch_log report what actually ran,
                # not the original batch's trigger.
                for r in batch:
                    self._execute([r], "retry")
                return
            for r in batch:
                # End the span BEFORE resolving the future: a client that
                # snapshots the trace right after its future resolves must
                # never see a dangling request span.
                if r.trace_id:
                    TRACER.async_end("request", r.trace_id, failed=True)
                r.future.set_exception(e)
            with self._lock:
                self._failures += len(batch)
                self._completed += len(batch)
            return
        t_done = time.perf_counter()
        n = len(batch)
        lats = []
        for r, res in zip(batch, results):
            lat_ms = (t_done - r.t_submit) * 1e3
            res["latency_ms"] = lat_ms
            res["batch_size"] = n
            lats.append(lat_ms)
        # One lock acquisition covers the whole batch's bookkeeping; futures
        # resolve after it so a drain poll never sees completed > resolved-
        # or-being-resolved.
        with self._lock:
            for lat_ms in lats:
                self._latency.observe(lat_ms)
            self._completed += n
        for r, res, lat_ms in zip(batch, results, lats):
            # Span end precedes set_result: once the future resolves, the
            # trace must already contain the request's full b/e pair.
            if r.trace_id:
                TRACER.async_end("request", r.trace_id, latency_ms=lat_ms)
            r.future.set_result(res)

    def update_params(self, params) -> None:
        """Hot-swap the serving params (e.g. after an online training step
        or incremental fine-tune). The swap and the materialized-cache
        invalidation happen under ONE lock acquisition, so no batch observes
        new params with old rows: a batch that snapshotted before the swap
        keeps serving (old params, old-version rows) consistently, and its
        late inserts are dropped by the version check. With a live graph
        attached, the new params also become the CURRENT graph version's
        retained snapshot — requests pinned to older versions keep their
        original params."""
        with self._lock:
            self.params = params
            if self.kg is not None:
                self._version_params[self._graph_version] = params
            if self.cfg.pin_params_on_admit:
                # New admissions stamp the new version; requests already
                # queued keep their admitted version and are served from the
                # retained snapshot below (hot swap without draining).
                self._params_version += 1
                self._params_by_version[self._params_version] = params
                while len(self._params_by_version) > self._params_retention:
                    del self._params_by_version[min(self._params_by_version)]
            if self.mat_cache is not None:
                self.mat_cache.bump_version("param_update")

    def _states_for(self, params, uniq: List[QueryInstance],
                    padded: List[QueryInstance], n_real: int, mat_ver: int,
                    gv: int = -1, use_cache: bool = True):
        """Encoded states for the padded unique composition, serving rows
        out of the materialized cache where possible. The assembled array is
        bitwise what ``executor.encode(params, padded)`` would return —
        pooled ops are row-wise, so subset encodes reproduce full-batch rows
        exactly, cached rows were such subset rows at the same version, and
        pad rows repeat the last unique row just as ``pad_to_bucket``'s
        repeated query would — so scoring and offline-oracle replay are
        untouched by the cache.

        ``gv`` (the batch's graph version; -1 = no live graph) keys both the
        plan cache and the materialized rows: rows encoded against different
        graph snapshots can never alias, even though all pins share one
        cache ``mat_ver`` stamp (the stamp owns PARAM freshness, the key
        owns graph state)."""
        if self.mat_cache is None or not use_cache:
            # ``use_cache=False``: the batch runs on a RETAINED (pre-swap)
            # params snapshot, while the cache stamp tracks the CURRENT
            # params — neither its rows nor inserts from this batch would be
            # valid, so the old-generation tail encodes around the cache.
            return self.executor.encode(params, padded, compiled=True,
                                        graph_version=gv)
        keys = [q.key() if gv < 0 else q.key() + (gv,) for q in uniq]
        cached = self.mat_cache.lookup(keys, version=mat_ver)
        miss = [j for j in range(len(uniq)) if j not in cached]
        fresh = None
        if miss:
            sub, sub_n = [uniq[j] for j in miss], len(miss)
            if self.cfg.bucket:
                sub, sub_n = pad_to_bucket(sub)
            fresh = np.asarray(
                self.executor.encode(params, sub, compiled=True,
                                     graph_version=gv))[: len(miss)]
            self.mat_cache.insert([keys[j] for j in miss], fresh,
                                  version=mat_ver)
        dim = (fresh.shape[1] if fresh is not None
               else next(iter(cached.values())).shape[0])
        states = np.empty((len(padded), dim), dtype=np.float32)
        for j, row in cached.items():
            states[j] = row
        for i, j in enumerate(miss):
            states[j] = fresh[i]
        states[n_real:] = states[n_real - 1]
        return states

    def _serve(self, batch: List[_Request], flush: str) -> List[Dict]:
        # Exact-duplicate coalescing: in-flight requests whose query keys
        # match share ONE computed row — encode + all-entity scoring run once
        # and the result fans out to every waiting future (requests with
        # different top_k still share the row; only the cheap final selection
        # differs). Partially overlapping requests are handled one layer
        # down: the executor's plan compiler CSE-merges shared subtrees of
        # DISTINCT queries in the same micro-batch.
        row_of: List[int] = []
        uniq: List[QueryInstance] = []
        index: Dict[Tuple, int] = {}
        for r in batch:
            key = r.query.key()
            j = index.get(key)
            if j is None:
                j = index[key] = len(uniq)
                uniq.append(r.query)
            row_of.append(j)
        if self.cfg.bucket:
            padded, n_real = pad_to_bucket(uniq)
        else:
            padded, n_real = list(uniq), len(uniq)
        # Snapshot (params, cache version, graph version) together under the
        # lock: ``update_params`` swaps and bumps under the same lock, so a
        # batch can never pair new params with rows materialized under old
        # ones (or vice versa) — the staleness contract
        # tests/test_plan_cache.py pins. A pinned batch (all requests share
        # one pin after grouping) serves from the pinned version's RETAINED
        # params instead of the live handle; ``_shed_stale`` already
        # guaranteed the pin is in bound and retained.
        pin = batch[0].pin_version
        use_mat = True
        with self._lock:
            if pin is not None:
                params = self._version_params.get(pin)
                if params is None:
                    # A write on another thread evicted the pin between the
                    # shed check and this snapshot — shed, don't fail.
                    raise StaleVersionError(pin, self._graph_version,
                                            self.cfg.max_staleness_versions)
                gv = pin
            else:
                params = self.params
                gv = self._graph_version
            if self.cfg.pin_params_on_admit:
                # The swap contract: serve on the params the batch was
                # ADMITTED under (all requests share one version after
                # grouping). An aged-out snapshot falls forward to current —
                # retention bounds memory, and the window (4 swaps) dwarfs
                # any realistic queue residency.
                pv = batch[0].params_version
                if pv != self._params_version:
                    params = self._params_by_version.get(pv, params)
                    use_mat = False
            mat_ver = (self.mat_cache.version
                       if self.mat_cache is not None else -1)
            lag = self._graph_version - gv if self.kg is not None else 0
        if self.sem_cache is not None:
            # Staging folds into the batcher thread: the plan's store read +
            # device put and the apply scatter happen here, once per
            # micro-batch, before the encode that gathers the rows. Single
            # batcher thread ⇒ plan order == apply order for free. No
            # mat-cache bump: staging changes WHERE rows live, not their
            # values, so materialized rows stay valid.
            anchors = np.concatenate([q.anchors for q in padded])
            with TRACER.span("sem_prefetch", rows=len(anchors)):
                stage = self.sem_cache.plan(anchors)
            if stage is not None:
                params = self.sem_cache.apply_to(params, stage)
                self.params = params
        with TRACER.span("encode", n=len(padded), graph_version=gv):
            states = self._states_for(params, uniq, padded, n_real, mat_ver,
                                      gv, use_cache=use_mat)
        with TRACER.span("score", n=len(padded)):
            if self.sem_cache is not None:
                scores = self.model.score_all_chunked(params, states,
                                                      self.sem_rows_fn)
            else:
                scores = np.asarray(self._scorer(params, states))
        # Select per DISTINCT (row, k) group, not one k_max selection sliced
        # per request: argpartition at k_max can arrange boundary-tied ids
        # differently than argpartition at k, and the contract is exact
        # per-request equality with serve_batch(top_k=k). Mixed-k batches
        # are rare, so this is one topk_desc call in the common case.
        ks = scores.shape[1]
        with TRACER.span("select", n=len(batch)):
            sel_of: Dict[Tuple[int, int], np.ndarray] = {}
            for i, r in enumerate(batch):
                sel_of.setdefault((row_of[i], min(r.top_k, ks)), None)
            by_k: Dict[int, List[int]] = {}   # k -> unique computed rows
            for row, k in sel_of:
                by_k.setdefault(k, []).append(row)
            for k, rows in by_k.items():
                # Unique rows appear in ascending order, so the common
                # single-k group covers the contiguous prefix — slice (a
                # view) instead of fancy-indexing (a copy).
                sub = (scores[:len(rows)] if len(rows) == len(uniq)
                       else scores[rows])
                idx = topk_desc(sub, k)
                for j, row in enumerate(rows):
                    sel_of[(row, k)] = idx[j]
        results: List[Optional[Dict]] = [None] * len(batch)
        log_rows: List[Optional[Dict]] = [None] * n_real
        default_k = min(self.cfg.top_k, ks)
        # One elementwise round of the whole matrix replaces a per-request
        # round of each selected slice — identical values (round is
        # elementwise), one vectorized call instead of batch-size tiny ones.
        rounded = scores.round(3)
        for i, r in enumerate(batch):
            row = row_of[i]
            k = min(r.top_k, ks)
            sel = sel_of[(row, k)]
            results[i] = {
                "pattern": r.query.pattern,
                "anchors": r.query.anchors.tolist(),
                "relations": r.query.relations.tolist(),
                "top_entities": sel.tolist(),
                "scores": rounded[row, sel].tolist(),
            }
            # Log rows prefer the engine's default k: offline-oracle replay
            # (check_against_offline) serves rec.queries at ONE fixed k, so
            # a coalesced row whose first submitter asked a custom k must
            # not shadow a co-batched duplicate at the default.
            if log_rows[row] is None or (
                    k == default_k
                    and len(log_rows[row]["top_entities"]) != default_k):
                log_rows[row] = results[i]
        with self._lock:
            self._batches += 1
            self._batch_rows += len(padded)
            self._padded_rows += len(padded) - n_real
            self._coalesced += len(batch) - len(uniq)
            self._occupancy.set(n_real / len(padded) if padded else 0.0)
            fc = self._flushes.get(flush)
            if fc is None:
                fc = self._flushes[flush] = self._metrics.counter(
                    "flushes", kind=flush)
            fc.inc()
            if self.kg is not None:
                # Per-version-lag served accounting (lag 0 = current graph
                # version): the §LiveStore observability hook for "how stale
                # is the traffic we actually serve".
                vc = self._version_served.get(lag)
                if vc is None:
                    vc = self._version_served[lag] = self._metrics.counter(
                        "version_lag_served", lag=str(lag))
                vc += len(batch)
            if self.cfg.record_batches:
                # The log holds the UNIQUE composition as executed (one
                # result per computed row), so offline-oracle replay compares
                # row-for-row against serve_batch on the same composition.
                self.batch_log.append(BatchRecord(
                    queries=padded, n_real=n_real, flush=flush,
                    results=log_rows))
        return results

    # -------------------------------------------------------------- metrics
    def retraces(self) -> int:
        """Cold signature work since the last ``reset_counters``: executor
        cache misses (schedule/encode/encode_jit — a new signature misses
        all three, so this over-counts distinct XLA programs on purpose) +
        scorer traces. The serving steady-state claim is that a replayed
        workload keeps this at ZERO: no scheduling, closure-building or
        compile work of any kind."""
        cs = self.executor.cache_stats()
        return (sum(int(v["misses"]) for v in cs.values())
                + self._scorer.traces - self._scorer_traces0)

    def reset_counters(self, clear_log: bool = True) -> None:
        """Zero retrace/latency/flush counters (after warmup) — compiled
        programs and cache contents are kept. Scoped to THIS engine (and its
        executor/caches): submitted/completed survive so ``close``'s drain
        accounting stays truthful. ``obs.get_registry().reset()`` is the
        process-wide variant (zeroes everything at once)."""
        self.executor.reset_cache_counters()
        if self.mat_cache is not None:
            self.mat_cache.reset_counters()
        self._scorer_traces0 = self._scorer.traces
        self._sharing0 = dict(self.executor.sharing_stats())
        with self._lock:
            self._latency.reset()
            self._metrics.reset(only=[
                self._batches, self._batch_rows, self._padded_rows,
                self._coalesced, self._failures, self._stale_sheds])
            for k in list(self._flushes):
                if k in ("size", "age", "drain"):
                    self._flushes[k].reset()
                else:
                    del self._flushes[k]
            for c in self._version_served.values():
                c.reset()
            if clear_log:
                self.batch_log = []

    def stats(self) -> Dict:
        with self._lock:
            lat = np.asarray(self._latency.window_values(), dtype=np.float64)
            out = {
                "submitted": int(self._submitted),
                "completed": int(self._completed),
                "failures": int(self._failures),
                "batches": int(self._batches),
                "flushes": {k: int(c) for k, c in self._flushes.items()},
                "mean_batch_size": (int(self._batch_rows) / int(self._batches)
                                    if self._batches else 0.0),
                "padded_row_frac": (
                    int(self._padded_rows) / int(self._batch_rows)
                    if self._batch_rows else 0.0),
                # duplicate in-flight requests served off a co-batched twin's
                # computation (same QueryInstance.key())
                "coalesced": int(self._coalesced),
            }
            if self.cfg.pin_params_on_admit:
                out["params_version"] = self._params_version
            if self.kg is not None:
                out["graph_version"] = self._graph_version
                out["retained_versions"] = sorted(self._version_params)
                out["stale_sheds"] = int(self._stale_sheds)
                out["version_lag_served"] = {
                    lag: int(c) for lag, c in self._version_served.items()}
        if len(lat):
            from repro.serving.loadgen import latency_summary

            out["latency_ms"] = {**latency_summary(lat),
                                 "max": float(lat.max()),
                                 "window_n": int(len(lat)),
                                 "window": int(self._latency.window)}
        out["retraces"] = self.retraces()
        out["caches"] = self.executor.cache_stats()
        # Same window as the engine's own counters: delta since the last
        # reset_counters(), not the executor's lifetime totals.
        sh = self.executor.sharing_stats()
        before = sh["nodes_before"] - self._sharing0["nodes_before"]
        after = sh["nodes_after"] - self._sharing0["nodes_after"]
        out["sharing"] = {
            "nodes_before": before,
            "nodes_after": after,
            "pooled_rows_saved": before - after,
            "saved_frac": (before - after) / max(before, 1),
        }
        out["scorer_traces"] = self._scorer.traces - self._scorer_traces0
        out["plan_cache"] = sh["plan_cache"]
        if self.sem_cache is not None:
            out["sem_cache"] = self.sem_cache.stats()
        if self.mat_cache is not None:
            # Duplicate-heavy traffic shows up here as the hit rate: rows
            # served without re-encoding since the last reset_counters.
            out["mat_cache"] = self.mat_cache.stats()
        return out
