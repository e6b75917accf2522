"""Producer/consumer data pipeline (§4.3 "Heterogeneous Pipelining").

While the accelerator executes the current pooled batch, host workers sample
the next queries (CSR traversal + rejection sampling are pure numpy and
release the GIL in the hot loops). This is the TPU analogue of the paper's
CPU↔GPU pipeline: the host side overlaps with async-dispatched device steps.

Two stages (DESIGN.md §Pipeline):

* ``BatchPrefetcher`` — sampling workers producing raw query batches.
* ``PreparedBatchPrefetcher`` — a background *scheduler thread* that consumes
  raw batches and runs everything that used to sit on the training critical
  path: negative sampling arrays, batch canonicalization, and Algorithm-1
  scheduling (``PooledExecutor.prepare``). Its output queue holds fully
  device-ready work items, so the main thread only dispatches jit calls while
  XLA executes the previous step — scheduling for batch k+1 overlaps device
  execution of batch k.

Straggler mitigation: multiple producers feed one queue; a slow producer
(e.g. pathological rejection sampling streak) cannot stall training because
consumption order is whoever-finishes-first, and a watchdog re-issues work
items that exceed a deadline.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from repro.obs.registry import get_registry
from repro.obs.trace import TRACER
from repro.sampling.online import OnlineSampler, SampledQuery


class BatchPrefetcher:
    def __init__(
        self,
        sampler: OnlineSampler,
        batch_size: int,
        depth: int = 2,
        workers: int = 2,
        deadline_s: float = 30.0,
    ):
        self.sampler = sampler
        self.batch_size = batch_size
        self.deadline_s = deadline_s
        self._q: "queue.Queue[List[SampledQuery]]" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._inflight = 0
        self._lock = threading.Lock()
        self._last_progress = time.monotonic()
        self.restarts = 0
        self._threads = [
            threading.Thread(target=self._produce, args=(i,), daemon=True)
            for i in range(workers)
        ]
        for t in self._threads:
            t.start()
        self._watchdog = threading.Thread(target=self._watch, daemon=True)
        self._watchdog.start()

    def _produce(self, worker_id: int) -> None:
        # Each worker gets an independent RNG stream so batches differ.
        import numpy as np

        TRACER.set_lane(f"sampling worker {worker_id}")
        local = OnlineSampler(
            self.sampler.kg,
            patterns=self.sampler.patterns,
            seed=hash((id(self), worker_id)) % (2**31),
            max_rejects=self.sampler.max_rejects,
            max_answers=self.sampler.max_answers,
        )
        while not self._stop.is_set():
            try:
                with TRACER.span("sample", n=self.batch_size):
                    batch = local.sample_batch(self.batch_size)
            except RuntimeError:
                continue  # rejection streak: drop and retry (straggler-safe)
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.25)
                    with self._lock:
                        self._last_progress = time.monotonic()
                    break
                except queue.Full:
                    continue

    def _watch(self) -> None:
        """Restart a producer if the queue has been starved past deadline."""
        while not self._stop.is_set():
            time.sleep(self.deadline_s / 4)
            with self._lock:
                starved = (
                    self._q.empty()
                    and time.monotonic() - self._last_progress > self.deadline_s
                )
            if starved:
                self.restarts += 1
                t = threading.Thread(
                    target=self._produce, args=(len(self._threads) + self.restarts,),
                    daemon=True,
                )
                t.start()
                self._threads.append(t)
                with self._lock:
                    self._last_progress = time.monotonic()

    def next(self, timeout: float = 120.0) -> List[SampledQuery]:
        return self._q.get(timeout=timeout)

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


def batch_entity_ids(queries, pos: np.ndarray, neg: np.ndarray) -> np.ndarray:
    """Every entity id one training step gathers semantic rows for: query
    anchors (EMBED pools) plus the positive/negative score candidates. This
    is the set the semantic hot-set cache must have staged before dispatch."""
    return np.concatenate(
        [np.asarray(q.anchors).ravel() for q in queries]
        + [np.asarray(pos).ravel(), np.asarray(neg).ravel()])


def prepare_work_item(sampler, executor, batch, n_negatives: int,
                      dev_static=None, sem_cache=None,
                      ctx=None, mat_cache=None, *, seq: Optional[int] = None,
                      phases: Optional[dict] = None,
                      counters: Optional[dict] = None) -> "PreparedWorkItem":
    """Run the full host side of one training step: negative-sampling arrays,
    plan compilation (canonicalize → CSE → Algorithm-1 lowering, i.e.
    ``executor.prepare`` returning a ``CompiledPlan``), and device transfer
    — the scheduler thread ships fully compiled plans, so the main thread
    only dispatches.

    ``dev_static`` (optional, a ``CompileCache``) caches device-resident
    static slot arrays by STRUCTURE key — under CSE that is the deduped
    topology, so they never change between batches sharing a post-CSE shape
    and transfer once instead of once per step. The structure key is
    essential: the coarser program signature only encodes bucketed shapes,
    and two different structures (e.g. 5 vs 6 queries padding to the same
    buckets) may share a signature while having different slot/answer
    arrays.

    ``sem_cache`` (optional, a ``semantic.store.SemanticCache``) is the
    prefetch half of the out-of-core semantic path: the batch's entity-id
    set is extracted HERE, on the scheduler thread, and the missing rows are
    read from the on-disk store, dequantized and device-put while the
    previous batch executes — the returned ``sem_stage`` is applied by the
    main thread right before this batch dispatches, so steady-state training
    never does a synchronous mid-step store read.

    ``ctx`` (an ``ExecutionContext``) makes every device put here
    sharding-aware: batch-like arrays go straight into the batch shardings
    the fused step was compiled against (``ctx.put_batch``), so the transfer
    happens once, on this thread, and dispatch does zero resharding. When
    omitted (or single-device) the puts are plain ``jnp.asarray`` —
    bit-for-bit the historical path.

    ``mat_cache`` (a ``core.matcache.MaterializedSubqueryCache``) is probed
    HERE, on the scheduler thread, like the semantic prefetch: the work item
    records how many of the batch's queries already have materialized rows
    at the current version (``mat_hits``/``mat_version``). Training itself
    never CONSUMES those rows — a cached constant inside the fused train
    step would detach its subtree's gradient — but the probe exercises the
    cross-thread lock discipline and surfaces reuse-potential counters,
    and inference consumers sharing the cache (eval after training, a
    co-located serving engine) get the rows the trainer's version bumps
    keep honest.

    Every phase here is one span on the calling thread's lane (``negatives``,
    ``sem_prefetch``, ``mat_probe``, ``schedule``, ``transfer``), each with
    arg ``step`` = ``seq`` when given: the id joining one batch's spans
    across the scheduler and dispatch lanes. ``transfer`` also carries
    ``bytes``, the device bytes it created (``dev_static`` hits excluded).
    Phase seconds go to ``phases`` (the item's ``phases``; a new dict when
    None) and to ``counters[name]`` where given."""
    import jax
    import jax.numpy as jnp  # deferred: keep module import light

    put = jnp.asarray
    if ctx is not None and ctx.is_sharded:
        put = ctx.put_batch

    # Per-phase wall times are ALWAYS collected (a perf_counter pair each —
    # nanoseconds against a multi-ms step) so step-time breakdowns work even
    # with the tracer off; the spans only fire when tracing is on.
    phases = {} if phases is None else phases
    counters = counters or {}
    ids = {} if seq is None else {"step": seq}

    def timed(name, **args):
        return TRACER.timed(name, phases, counters.get(name), **args, **ids)

    with timed("negatives"):
        queries, pos, neg = sampler.to_training_arrays(batch, n_negatives)
    sem_stage = None
    if sem_cache is not None:
        with timed("sem_prefetch", n=len(queries)):
            sem_stage = sem_cache.plan(batch_entity_ids(queries, pos, neg),
                                       background=True)
    mat_hits, mat_version = 0, -1
    if mat_cache is not None:
        with TRACER.span("mat_probe", n=len(queries), **ids):
            mat_version = mat_cache.version
            mat_hits = mat_cache.probe([q.key() for q in queries],
                                       version=mat_version)
    with timed("schedule", n=len(queries)):
        prepared = executor.prepare(queries)
    with timed("transfer", n_steps=len(prepared.bind_arrays)) as ph:
        static = (dev_static.get(prepared.structure_key)
                  if dev_static is not None else None)
        created = []
        if static is None:
            static = (
                [{k: put(v) for k, v in s.items()}
                 for s in prepared.slot_arrays],
                put(prepared.answer_slots),
            )
            created.append(static)
            if dev_static is not None:
                dev_static.put(prepared.structure_key, static)
        slot_dev, ans = static
        binds = [{k: put(v) for k, v in b.items()}
                 for b in prepared.bind_arrays]
        steps = [{**s, **b} for s, b in zip(slot_dev, binds)]
        pos_dev = put(pos[prepared.order])
        neg_dev = put(neg[prepared.order])
        if ph.span is not None:
            ph.span.args["bytes"] = sum(
                a.nbytes for a in jax.tree.leaves(
                    (created, binds, pos_dev, neg_dev)))
    return PreparedWorkItem(
        prepared=prepared,
        steps=steps,
        ans=ans,
        pos=pos_dev,
        neg=neg_dev,
        patterns=prepared.patterns,
        n_queries=len(queries),
        sem_stage=sem_stage,
        mat_hits=mat_hits,
        mat_version=mat_version,
        phases=phases,
        seq=seq,
    )


@dataclasses.dataclass
class PreparedWorkItem:
    """One fully host-scheduled training step, ready for device dispatch.

    ``pos``/``neg`` are already permuted into the prepared batch's canonical
    (pattern-sorted) order, and ``steps``/``ans``/``pos``/``neg`` are already
    device arrays (transferred from the scheduler thread), so the consumer
    never touches numpy on the critical path — it just dispatches the jitted
    program."""

    prepared: object            # repro.core.plan.CompiledPlan
    steps: List[dict]           # device-resident slot/bind arrays per step
    ans: object                 # device answer_slots
    pos: object                 # [B] positives, canonical order (device)
    neg: object                 # [B, K] negatives, canonical order (device)
    patterns: List[str]         # canonical order, for adaptive sampling
    n_queries: int
    sem_stage: object = None    # semantic.store.SemStage: rows prefetched on
    #                             the scheduler thread; main thread applies
    #                             it (one donated scatter) before dispatch
    mat_hits: int = 0           # queries with a materialized row resident at
    mat_version: int = -1       # this cache version when the item was staged
    phases: dict = dataclasses.field(default_factory=dict)
    #                             scheduler-thread phase wall times (seconds):
    #                             sample_s/negatives_s/sem_prefetch_s/
    #                             schedule_s/transfer_s — feeds step-time
    #                             breakdowns
    seq: Optional[int] = None   # the scheduler pass that built it: arg
    #                             ``step`` of its spans on both lanes


class PreparedBatchPrefetcher:
    """Background-thread prefetch queue feeding the Algorithm-1 scheduler.

    A single scheduler thread pulls raw batches (from an internal
    ``BatchPrefetcher``, or from ``batch_fn`` when the caller controls the
    workload — e.g. benchmarks replaying a fixed batch list), builds the
    training arrays, and runs ``executor.prepare`` so the schedule cache and
    all bind arrays are ready before the trainer ever sees the item.

    One scheduler thread by design — and deliberately few threads overall:
    ``executor.prepare`` mutates the executor's signature-keyed caches (a
    single consumer makes that race-free without locking the hot path), and
    under the GIL only one Python thread makes progress at a time anyway, so
    extra host threads just add handoff latency. When ``batch_fn`` is given
    (deterministic batch source), it runs inside the scheduler thread;
    otherwise an internal ``BatchPrefetcher`` supplies sampled batches.
    """

    def __init__(
        self,
        sampler: OnlineSampler,
        executor,
        batch_size: int,
        n_negatives: int,
        depth: int = 2,
        workers: int = 2,
        batch_fn: Optional[Callable[[], List[SampledQuery]]] = None,
        sem_cache=None,
        ctx=None,
        mat_cache=None,
    ):
        self.sampler = sampler
        self.executor = executor
        self.n_negatives = n_negatives
        self.sem_cache = sem_cache
        self.ctx = ctx
        self.mat_cache = mat_cache
        self._q: "queue.Queue[PreparedWorkItem]" = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._batches: Optional[BatchPrefetcher] = None
        if batch_fn is None:
            self._batches = BatchPrefetcher(sampler, batch_size, depth=depth,
                                            workers=workers)
            self._next_batch = self._batches.next
        else:
            self._next_batch = batch_fn
        # Device-resident static slot arrays, keyed by structure key. LRU so
        # an unbounded signature stream (e.g. a pattern curriculum) cannot
        # grow device memory without bound.
        from repro.core.compile_cache import CompileCache

        self._dev_static = CompileCache(128, name="dev_static")
        # Scheduler-side telemetry: queue depth (how far ahead of the
        # consumer this thread runs) + cumulative phase seconds.
        self._metrics = get_registry().group("pipeline")
        self._depth_gauge = self._metrics.gauge("prepared_q_depth")
        self._phase_s = {
            name: self._metrics.counter("phase_seconds", phase=name)
            for name in ("sample", "negatives", "sem_prefetch", "schedule",
                         "transfer")}
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        TRACER.set_lane("pipeline scheduler")
        # "sample" on this lane is the sampling itself when batch_fn runs
        # inline; with sampling workers it is only the wait on their queue
        # ("sample_wait": their own lanes carry the "sample" spans).
        sample_span = "sample" if self._batches is None else "sample_wait"
        for seq in itertools.count():
            if self._stop.is_set():
                return
            try:
                phases = {}
                with TRACER.timed(sample_span, phases, self._phase_s["sample"],
                                  key="sample_s", step=seq):
                    batch = self._next_batch()
                item = prepare_work_item(self.sampler, self.executor, batch,
                                         self.n_negatives, self._dev_static,
                                         sem_cache=self.sem_cache,
                                         ctx=self.ctx,
                                         mat_cache=self.mat_cache, seq=seq,
                                         phases=phases,
                                         counters=self._phase_s)
            except BaseException as e:  # surface on the consumer side
                if self._error is None:
                    self._error = e
                self._stop.set()
                return
            # Blocked here = the consumer (dispatch or device) sets the pace.
            with TRACER.span("prepared_put", step=seq):
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.25)
                        self._depth_gauge.set(self._q.qsize())
                        if TRACER.enabled:
                            TRACER.counter("prepared_q_depth",
                                           depth=self._q.qsize())
                        break
                    except queue.Full:
                        continue

    def next(self, timeout: float = 120.0) -> PreparedWorkItem:
        while True:
            if self._error is not None:
                raise RuntimeError("prepared-batch prefetcher failed") from self._error
            try:
                return self._q.get(timeout=0.25)
            except queue.Empty:
                timeout -= 0.25
                if timeout <= 0:
                    raise

    def close(self) -> None:
        self._stop.set()
        if self._batches is not None:
            self._batches.close()
        # Keep draining while joining: the scheduler thread may be blocked in
        # a queue.put, and taking items is what wakes it immediately.
        deadline = time.monotonic() + 5.0
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.02)
