"""End-to-end NGDB training loop: online sampling → operator-level scheduling
→ fused execution → vectorized loss → Adam, with adaptive sampling, prefetch
pipelining and fault-tolerant checkpointing.

Two execution modes (DESIGN.md §Pipeline):

* **sync** (``pipeline=False``, the ablation baseline): each step runs
  sampling → Algorithm-1 scheduling → device step → blocking loss readback
  strictly in sequence, so the host idles during device execution and the
  device idles during host scheduling.
* **pipelined** (``pipeline=True``): background threads run the host side —
  sampling workers (or a deterministic batch pump) feeding one scheduler
  thread that samples negatives, canonicalizes and runs Algorithm-1
  scheduling for batch *k+1* while batch *k* executes on device. The main
  thread dispatches jitted step programs (XLA executes with the GIL
  released, so host stages continue underneath) and retires finished steps
  from a bounded in-flight window (``max_inflight``, i.e. double-buffered
  for the default of 2).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compile_cache import CompileCache
from repro.core.executor import PooledExecutor, QueryLevelExecutor
from repro.core.plan import CompiledPlan
from repro.data.pipeline import batch_entity_ids
from repro.core.patterns import TEMPLATES
from repro.obs.registry import get_registry
from repro.obs.sink import MetricsSink
from repro.obs.trace import TRACER
from repro.sampling.adaptive import AdaptiveDistribution, pattern_losses_from_batch
from repro.sampling.online import OnlineSampler, SampledQuery
from repro.training.checkpoint import CheckpointManager
from repro.training.loss import negative_sampling_loss
from repro.training.optim import AdamConfig, adam_init, adam_update


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 512           # queries (Table 5)
    n_negatives: int = 64
    b_max: int = 512
    adam: AdamConfig = dataclasses.field(default_factory=AdamConfig)
    patterns: Tuple[str, ...] = tuple(TEMPLATES)
    adaptive: bool = False
    executor: str = "pooled"        # pooled | query_level
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 200
    seed: int = 0
    prefetch: int = 2               # producer/consumer queue depth (0 = sync)
    pipeline: bool = False          # overlap host scheduling w/ device steps
    max_inflight: int = 2           # pipelined: bounded dispatch window
    compile_cache_size: int = 128   # LRU capacity for jitted step programs
    gil_switch_interval: float = 2e-3  # pipelined: bound GIL handoff latency
    cse: bool = True                # cross-query subexpression sharing
    #                                 (False = --no-cse ablation baseline)
    materialized_rows: int = 0      # >0: attach a MaterializedSubqueryCache
    #                                 of that many rows to the pooled
    #                                 executor's eval/encode path (training
    #                                 gradients never consume cached rows)
    metrics_path: Optional[str] = None  # JSONL step-time breakdown sink
    #                                 (per-step phase durations + bubble
    #                                 fraction; None = disabled, zero cost)


def incremental_finetune(model, params, triples, *, steps: int = 4,
                         lr: float = 1e-3, n_negatives: int = 8,
                         seed: int = 0, b_max: int = 64, executor=None):
    """Incremental embedding maintenance for a live KG write (DESIGN.md
    §LiveStore): a few Adam steps of 1p link-prediction loss on exactly the
    written triples, touching the written neighborhood instead of
    retraining from scratch. Returns ``(new_params, losses)``.

    Deterministic by construction — a pure function of (params, triples,
    hyperparams, seed): negatives come from a seeded generator, the batch
    is canonicalized by the same plan compiler as training, and the jitted
    step does NOT donate its inputs — the caller's params are typically the
    serving engine's LIVE weights, concurrently read by the batcher thread,
    so they must survive this call unchanged. The background maintenance
    thread and a synchronous oracle rerun therefore produce bitwise-
    identical params, which ``tests/test_live.py::
    test_background_finetune_matches_sync_rerun`` holds."""
    triples = np.asarray(triples, dtype=np.int64).reshape(-1, 3)
    if len(triples) == 0:
        return params, []
    from repro.core.patterns import QueryInstance

    executor = executor or PooledExecutor(model, b_max=b_max)
    queries = [QueryInstance("1p", np.array([h]), np.array([r]))
               for h, r, _ in triples]
    pos = np.ascontiguousarray(triples[:, 2])
    rng = np.random.default_rng(seed)
    n_ent = model.n_entities
    neg = rng.integers(0, n_ent, size=(len(pos), n_negatives))
    clash = neg == pos[:, None]
    while clash.any():
        neg[clash] = rng.integers(0, n_ent, size=int(clash.sum()))
        clash = neg == pos[:, None]
    prepared = executor.prepare(queries)
    pos = pos[prepared.order]
    neg = neg[prepared.order]
    step_arrays, ans = prepared.device_args()
    encode = executor.encode_fn(prepared)
    adam_cfg = AdamConfig(lr=lr)
    frozen_names = set(model.frozen_param_names())

    def step_fn(params, opt_state, steps_in, ans_slots, pos_in, neg_in):
        trainable = {k: v for k, v in params.items()
                     if k not in frozen_names}
        frozen = {k: v for k, v in params.items() if k in frozen_names}

        def loss_fn(t):
            p = {**t, **frozen}
            q = encode(p, steps_in, ans_slots)
            return negative_sampling_loss(model, p, q, pos_in, neg_in)

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(trainable)
        grads = {**grads,
                 **{k: jnp.zeros((1,), jnp.float32) for k in frozen}}
        params, opt_state = adam_update(grads, opt_state, params, adam_cfg)
        return params, opt_state, loss

    fn = jax.jit(step_fn)
    opt_state = adam_init(params, adam_cfg)
    losses: List[float] = []
    pos_j, neg_j = jnp.asarray(pos), jnp.asarray(neg)
    for _ in range(steps):
        params, opt_state, loss = fn(params, opt_state, step_arrays, ans,
                                     pos_j, neg_j)
        losses.append(float(loss))
    return params, losses


class NGDBTrainer:
    def __init__(self, model, kg, cfg: TrainConfig, semantic_table=None,
                 semantic_cache=None, ctx=None):
        from repro.distributed.context import ExecutionContext

        self.model = model
        self.kg = kg
        self.cfg = cfg
        # Placement policy (DESIGN.md §Sharding): params/Adam state live in
        # their NamedShardings, batches shard over the data axes, and the
        # fused step compiles with explicit in/out shardings. The default
        # single-device context makes every placement hook a no-op.
        self.ctx = ctx or ExecutionContext.single_device()
        # Materialized subquery rows are an inference-side cache: the fused
        # train step never reads them (a constant row would detach the
        # gradient), but executor.encode() on the eval path does, and they
        # must be invalidated on every param update / KG write (bumps below).
        self.mat_cache = None
        if cfg.materialized_rows > 0 and cfg.executor == "pooled":
            from repro.core.matcache import MaterializedSubqueryCache

            self.mat_cache = MaterializedSubqueryCache(cfg.materialized_rows)
            self.mat_cache.watch_kg(kg)
        if cfg.executor == "pooled":
            self.executor = PooledExecutor(model, b_max=cfg.b_max,
                                           cache_size=cfg.compile_cache_size,
                                           ctx=self.ctx, cse=cfg.cse,
                                           mat_cache=self.mat_cache)
        else:
            self.executor = QueryLevelExecutor(model, b_max=cfg.b_max,
                                               ctx=self.ctx)
        # Out-of-core semantic mode (semantic/store.py): the params carry a
        # bounded device hot set + indirection instead of the full H_sem;
        # every batch's rows are staged (plan/apply_to) before dispatch.
        self.sem_cache = semantic_cache
        key = jax.random.PRNGKey(cfg.seed)
        self.params = model.init_params(
            key, kg.n_entities, kg.n_relations, semantic_table=semantic_table,
            semantic_cache=semantic_cache, ctx=self.ctx,
        )
        self.opt_state = adam_init(self.params, cfg.adam, ctx=self.ctx)
        # Shardings the fused step is compiled against (None single-device).
        self._param_sh = self.ctx.param_shardings(self.params)
        self._opt_sh = self.ctx.param_shardings(self.opt_state)
        self.sampler = OnlineSampler(kg, patterns=cfg.patterns, seed=cfg.seed)
        self.adaptive = AdaptiveDistribution(cfg.patterns) if cfg.adaptive else None
        self.ckpt = (
            CheckpointManager(cfg.checkpoint_dir, every=cfg.checkpoint_every)
            if cfg.checkpoint_dir
            else None
        )
        self.step = 0
        self._train_fns = CompileCache(cfg.compile_cache_size, name="train_step")
        self.history: List[Dict] = []
        # Step-time telemetry (DESIGN.md §Observability): cumulative
        # main-thread phase seconds + the per-step JSONL sink. The sink is a
        # no-op object when metrics_path is None, so instrumented paths need
        # no gating.
        self._obs = get_registry().group("trainer")
        self._steps_done = self._obs.counter("steps")
        self._phase_s = {
            name: self._obs.counter("phase_seconds", phase=name)
            for name in ("pipeline_wait", "sem_apply", "compile", "dispatch",
                         "retire")}
        self._inflight_gauge = self._obs.gauge("inflight")
        # Bytes one device holds of the entity table: the whole table on one
        # device, 1/N of it under the fsdp profile.
        self._obs.gauge("entity_bytes_per_device").set(max(
            s.data.nbytes for s in self.params["entity"].addressable_shards))
        self._step_collectives: Dict[Tuple, object] = {}
        self.metrics_sink = MetricsSink(cfg.metrics_path)

    # ------------------------------------------------------------------ fns
    def _split_frozen(self, params):
        """(trainable, frozen) views of the params dict. Frozen buffers —
        H_sem in either layout, including the int32 cache indirection which
        could not be differentiated at all — are closed over by the loss, so
        XLA never materializes gradients for them (at d_l=1024 a sem_table
        cotangent would double the largest buffer in the step)."""
        frozen_names = set(self.model.frozen_param_names())
        trainable = {k: v for k, v in params.items() if k not in frozen_names}
        frozen = {k: v for k, v in params.items() if k in frozen_names}
        return trainable, frozen

    def _train_fn(self, prepared: CompiledPlan, example=None):
        """Jitted fused step for ``prepared``'s signature. ``example`` is the
        (steps, ans, pos, neg) the step will be called with — under a mesh
        context their SHAPES pick the batch in_shardings, so the program is
        compiled against exactly the layout the pipeline stages arrays into
        (signature-keyed cache: same signature ⇒ same bucketed shapes ⇒ same
        shardings, so the example never fragments the cache).

        The loss consumes the plan's per-query answer map (``ans_slots``):
        with CSE, queries sharing their full tree alias the same workspace
        row, the encode-final gather fans that row out per query, and
        reverse-mode AD sums the per-query cotangents into the shared node —
        gradients through shared subexpressions need no special handling."""
        sig = prepared.signature
        fn = self._train_fns.get(sig)
        if fn is not None:
            return fn
        model, cfg = self.model, self.cfg
        encode = self.executor.encode_fn(prepared)

        def step_fn(params, opt_state, steps, ans_slots, pos, neg):
            trainable, frozen = self._split_frozen(params)

            def loss_fn(t):
                p = {**t, **frozen}
                q = encode(p, steps, ans_slots)
                return negative_sampling_loss(model, p, q, pos, neg)

            (loss, per_q), grads = jax.value_and_grad(loss_fn, has_aux=True)(trainable)
            # Token gradients for frozen leaves keep the pytree aligned with
            # params/opt_state; adam_update skips them by name.
            grads = {**grads, **{k: jnp.zeros((1,), jnp.float32) for k in frozen}}
            params, opt_state = adam_update(grads, opt_state, params, cfg.adam)
            return params, opt_state, loss, per_q

        jit_kwargs = {}
        if self.ctx.is_sharded and example is not None:
            steps, ans, pos, neg = example
            rep = self.ctx.replicated()
            jit_kwargs = dict(
                # params + Adam state per tree_param_shardings; batch arrays
                # over the data axes; loss and per-query aux replicated (both
                # are read back on the host every retire).
                in_shardings=(self._param_sh, self._opt_sh,
                              self.ctx.batch_shardings(steps),
                              self.ctx.batch_sharding(np.shape(ans)),
                              self.ctx.batch_sharding(np.shape(pos)),
                              self.ctx.batch_sharding(np.shape(neg))),
                out_shardings=(self._param_sh, self._opt_sh, rep, rep),
            )
        fn = jax.jit(step_fn, donate_argnums=self.ctx.donate_argnums(0, 1),
                     **jit_kwargs)
        if jit_kwargs:
            # Compile ahead of the first dispatch to read the partitioned
            # module's collectives; the dispatch reuses this executable.
            from repro.launch.roofline import parse_collectives

            compiled = fn.lower(self.params, self.opt_state, *example).compile()
            self._step_collectives[sig] = parse_collectives(
                compiled.as_text(), self.ctx.n_devices)
        self._train_fns.put(sig, fn)
        return fn

    def _collective_args(self, sig) -> Dict:
        """Span args of a dispatch of ``sig``: its step's collectives (wire
        bytes per device, payload bytes); none single-device."""
        st = self._step_collectives.get(sig)
        if st is None:
            return {}
        return {"collective_wire_bytes": st.wire_bytes,
                "collective_payload_bytes": st.payload_bytes}

    @property
    def step_collectives(self) -> Dict[Tuple, object]:
        """Collectives of each compiled train-step signature under a mesh
        (``launch.roofline.CollectiveStats``), read once from the compiled
        module; empty single-device."""
        return self._step_collectives

    def compile_cache_stats(self) -> Dict[str, Dict[str, float]]:
        """Counters for every signature-keyed cache in the engine."""
        out = {"train_step": self._train_fns.stats()}
        out.update(self.executor.cache_stats())
        if self.sem_cache is not None:
            out["sem_cache"] = self.sem_cache.stats()
        return out

    # ----------------------------------------------------------------- steps
    def train_step(self, batch: Optional[List[SampledQuery]] = None) -> Dict[str, float]:
        if batch is None:
            dist = self.adaptive.distribution() if self.adaptive else None
            with TRACER.span("sample", n=self.cfg.batch_size):
                batch = self.sampler.sample_batch(self.cfg.batch_size, dist)
        queries, pos, neg = self.sampler.to_training_arrays(batch, self.cfg.n_negatives)
        phases: Dict[str, float] = {}
        if self.sem_cache is not None:
            # Sync mode stages on the critical path (the pipelined loop does
            # this on the scheduler thread instead — zero mid-step reads).
            with TRACER.timed("sem_prefetch", phases):
                stage = self.sem_cache.plan(batch_entity_ids(queries, pos, neg))
                if stage is not None:
                    self.params = self.sem_cache.apply_to(self.params, stage)
        t0 = time.perf_counter()
        if isinstance(self.executor, PooledExecutor):
            with TRACER.timed("schedule", phases, n=len(queries)):
                prepared = self.executor.prepare(queries)
            pos = pos[prepared.order]
            neg = neg[prepared.order]
            steps, ans = prepared.device_args()
            # A signature absent from the cache means THIS dispatch pays the
            # jit trace+compile — label the span accordingly.
            key = ("compile" if prepared.signature not in self._train_fns
                   else "dispatch")
            # pos/neg go in as host numpy: the jit places them per its
            # in_shardings (one transfer straight into the compiled layout);
            # a jnp.asarray here would commit to device 0 first and force a
            # second reshard transfer at dispatch under a mesh ctx.
            with TRACER.timed(key, phases, self._phase_s[key]) as ph:
                fn = self._train_fn(prepared, example=(steps, ans, pos, neg))
                self.params, self.opt_state, loss, per_q = fn(
                    self.params, self.opt_state, steps, ans, pos, neg
                )
                if ph.span is not None:
                    ph.span.args.update(
                        self._collective_args(prepared.signature))
            patterns = prepared.patterns
        else:  # query-level baseline: one fragmented pass per pattern group
            loss, per_q, patterns = self._query_level_step(queries, pos, neg)
        if self.mat_cache is not None:
            # params handle just advanced — rows encoded under the old
            # params must never be served (or inserted: version pinning in
            # insert() drops in-flight encodes started before this bump).
            self.mat_cache.bump_version("param_update")
        with TRACER.timed("retire", phases, self._phase_s["retire"]):
            loss = float(loss)
        self._steps_done.inc()
        if self.adaptive:
            self.adaptive.update(pattern_losses_from_batch(patterns, per_q))
        self.step += 1
        rec = {
            "step": self.step,
            "loss": loss,
            "queries_per_sec": len(queries) / max(time.perf_counter() - t0, 1e-9),
        }
        self.history.append(rec)
        if self.metrics_sink.enabled:
            # Separate record, not extra keys on rec: history is compared
            # across runs by tests/benchmarks and must not change shape.
            self.metrics_sink.write({"kind": "step", "mode": "sync", **rec,
                                     **phases})
        if self.ckpt:
            self.ckpt.maybe_save(
                self.step,
                {"params": self.params, "opt": self.opt_state},
                metadata={"loss": loss},
            )
        return rec

    def _qlevel_grad_fn(self, prepared):
        """Jitted per-pattern-group loss+grad — the baseline frameworks jit
        each isomorphic group too; only the BATCHING granularity differs."""
        sig = ("ql",) + prepared.signature
        fn = self._train_fns.get(sig)
        if fn is not None:
            return fn
        encode = self.executor.encode_fn(prepared)
        model = self.model

        def gfn(params, steps, ans, pos, neg):
            trainable, frozen = self._split_frozen(params)

            def loss_fn(t):
                p = {**t, **frozen}
                qs = encode(p, steps, ans)
                return negative_sampling_loss(model, p, qs, pos, neg)

            (loss, per_q), grads = jax.value_and_grad(loss_fn, has_aux=True)(trainable)
            grads = {**grads, **{k: jnp.zeros((1,), jnp.float32) for k in frozen}}
            return loss, per_q, grads

        fn = jax.jit(gfn)
        self._train_fns.put(sig, fn)
        return fn

    def _query_level_step(self, queries, pos, neg):
        """Baseline: independent fragmented train micro-steps per pattern."""
        if not hasattr(self, "_adam_jit"):
            cfg = self.cfg.adam
            self._adam_jit = jax.jit(
                lambda g, s, p: adam_update(g, s, p, cfg), donate_argnums=(1, 2))
        groups: Dict[str, List[int]] = {}
        for i, q in enumerate(queries):
            groups.setdefault(q.pattern, []).append(i)
        total, n = 0.0, 0
        per_q_all, patterns = [], []
        grads_acc = None
        for pat, idxs in groups.items():
            sub = [queries[i] for i in idxs]
            prepared = self.executor.prepare(sub)
            fn = self._qlevel_grad_fn(prepared)
            steps, ans = prepared.device_args()
            loss, per_q, grads = fn(self.params, steps, ans,
                                    jnp.asarray(pos[idxs][prepared.order]),
                                    jnp.asarray(neg[idxs][prepared.order]))
            w = len(idxs)
            grads_acc = (
                grads
                if grads_acc is None
                else jax.tree.map(lambda a, b: a + b * w, grads_acc, grads)
            )
            if grads_acc is grads:
                grads_acc = jax.tree.map(lambda g: g * w, grads_acc)
            total += float(loss) * w
            n += w
            per_q_all.extend(np.asarray(per_q).tolist())
            patterns.extend([pat] * w)
        grads_acc = jax.tree.map(lambda g: g / n, grads_acc)
        self.params, self.opt_state = self._adam_jit(
            grads_acc, self.opt_state, self.params)
        return total / n, np.array(per_q_all), patterns

    # ------------------------------------------------------------------ loop
    def train(self, n_steps: int, log_every: int = 50, prefetcher=None,
              batches=None) -> List[Dict]:
        """Run ``n_steps``. ``batches`` pins the workload — a fixed batch
        list (cycled) or a zero-arg callable yielding batches (e.g. a seeded
        sampler stream) — so tests and the benchmark can feed sync and
        pipelined modes the SAME batches; otherwise batches come from the
        online sampler."""
        if self.cfg.pipeline and isinstance(self.executor, PooledExecutor):
            return self._train_pipelined(n_steps, log_every, batches=batches)

        TRACER.set_lane("main dispatch")
        from repro.data.pipeline import BatchPrefetcher

        own = None
        if (prefetcher is None and batches is None and self.cfg.prefetch > 0
                and not self.adaptive):
            own = prefetcher = BatchPrefetcher(
                self.sampler, self.cfg.batch_size, depth=self.cfg.prefetch
            )
        try:
            for i in range(n_steps):
                if callable(batches):
                    batch = batches()
                elif batches is not None:
                    batch = batches[i % len(batches)]
                else:
                    batch = prefetcher.next() if prefetcher else None
                rec = self.train_step(batch)
                if log_every and (i + 1) % log_every == 0:
                    print(
                        f"step {rec['step']:6d} loss {rec['loss']:.4f} "
                        f"q/s {rec['queries_per_sec']:.0f}"
                    )
        finally:
            if own is not None:
                own.close()
        if self.ckpt:
            self.ckpt.maybe_save(
                self.step, {"params": self.params, "opt": self.opt_state}, force=True
            )
        return self.history

    # ------------------------------------------------------------- pipelined
    def _retire(self, pending, t_last: float, log_every: int) -> float:
        """Block on one in-flight step's loss, fold its metrics into history.

        ``pending`` carries a snapshot of the (params, opt_state) produced BY
        the retired step when that step lands on a checkpoint boundary, so
        the checkpoint is labeled with the step whose parameters it actually
        contains — ``self.params`` may already belong to a later dispatched
        step, and the retired step's own outputs are donated into the next
        dispatch (hence the explicit copy at dispatch time)."""
        loss, per_q, patterns, n_queries, snap, phases = pending
        tr = time.perf_counter()
        with TRACER.span("retire"):
            loss = float(loss)  # sync point: waits for that device step only
        now = time.perf_counter()
        phases["retire_s"] = now - tr
        self._phase_s["retire"].inc(phases["retire_s"])
        if self.adaptive:
            self.adaptive.update(pattern_losses_from_batch(patterns, per_q))
        self.step += 1
        self._steps_done.inc()
        rec = {
            "step": self.step,
            "loss": loss,
            "queries_per_sec": n_queries / max(now - t_last, 1e-9),
        }
        self.history.append(rec)
        if self.metrics_sink.enabled:
            # Bubble fraction: main-thread time spent WAITING for the
            # prefetcher (pf.next) over this step's wall time — the share of
            # the loop the pipeline failed to hide host work in. Retire
            # (device sync) is reported separately: a big retire_s means the
            # DEVICE is the bottleneck, which is the pipeline working.
            wall = max(now - t_last, 1e-9)
            self.metrics_sink.write({
                "kind": "step", "mode": "pipelined", **rec, **phases,
                "bubble_frac": min(phases.get("wait_s", 0.0) / wall, 1.0),
                "wall_s": wall,
            })
        if log_every and self.step % log_every == 0:
            print(f"step {rec['step']:6d} loss {rec['loss']:.4f} "
                  f"q/s {rec['queries_per_sec']:.0f}")
        if self.ckpt and snap is not None:
            params, opt_state = snap
            self.ckpt.maybe_save(
                self.step,
                {"params": params, "opt": opt_state},
                metadata={"loss": loss},
            )
        return now

    def _train_pipelined(self, n_steps: int, log_every: int,
                         batches=None) -> List[Dict]:
        """Dataflow mode (DESIGN.md §Pipeline).

        Host stages run on background threads (sampling workers — or a batch
        pump for a deterministic source — feeding one scheduler thread that
        builds fully device-ready work items). The main thread dispatches
        the jitted step program (XLA executes with the GIL released, so the
        host stages keep running underneath) and retires finished steps from
        a bounded in-flight window (``max_inflight``, default 2 = double
        buffered): a step's loss is only read back once it leaves the
        window, so metric readback never stalls dispatch."""
        from repro.data.pipeline import PreparedBatchPrefetcher

        batch_fn = None
        if callable(batches):
            batch_fn = batches
        elif batches is not None:
            it = itertools.cycle(batches)
            batch_fn = lambda: next(it)  # noqa: E731 — single pump thread
        elif self.adaptive:
            # Adaptive needs the latest distribution at sample time; sample in
            # the pump thread with a (≤ max_inflight steps) stale π.
            batch_fn = lambda: self.sampler.sample_batch(  # noqa: E731
                self.cfg.batch_size, self.adaptive.distribution())
        pf = PreparedBatchPrefetcher(
            self.sampler, self.executor, self.cfg.batch_size,
            self.cfg.n_negatives, depth=max(self.cfg.prefetch, 1),
            batch_fn=batch_fn, sem_cache=self.sem_cache, ctx=self.ctx,
            mat_cache=self.mat_cache,
        )
        # The main thread re-acquires the GIL every time a jit call returns
        # from (GIL-free) XLA execution; the default 5 ms switch interval
        # makes each re-acquisition wait on whichever host stage holds the
        # GIL. Tightening it while pipeline threads are live keeps dispatch
        # latency bounded; restored on exit.
        import sys as _sys

        old_switch = _sys.getswitchinterval()
        if self.cfg.gil_switch_interval:
            _sys.setswitchinterval(self.cfg.gil_switch_interval)
        inflight: deque = deque()
        t_last = time.perf_counter()
        TRACER.set_lane("main dispatch")
        try:
            for _ in range(n_steps):
                # This wait IS the pipeline bubble: the prefetcher had no
                # ready item, so the main thread idles instead of dispatching.
                with TRACER.timed("pipeline_wait", None,
                                  self._phase_s["pipeline_wait"]) as wait:
                    item = pf.next()
                item.phases["wait_s"] = wait.seconds
                if item.sem_stage is not None:
                    # The scheduler thread already did the store read +
                    # device put (overlapped with step k); this is just the
                    # donated scatter, enqueued after step k's program — the
                    # in-order device stream makes eviction of step k's rows
                    # safe even while k is still executing.
                    with TRACER.timed("sem_apply", item.phases,
                                      self._phase_s["sem_apply"],
                                      step=item.seq):
                        self.params = self.sem_cache.apply_to(self.params,
                                                              item.sem_stage)
                sig = item.prepared.signature
                key = "compile" if sig not in self._train_fns else "dispatch"
                with TRACER.timed(key, item.phases, self._phase_s[key],
                                  step=item.seq) as ph:
                    fn = self._train_fn(item.prepared,
                                        example=(item.steps, item.ans,
                                                 item.pos, item.neg))
                    self.params, self.opt_state, loss, per_q = fn(
                        self.params, self.opt_state, item.steps, item.ans,
                        item.pos, item.neg,
                    )
                    if ph.span is not None:
                        ph.span.args.update(self._collective_args(sig))
                if self.mat_cache is not None:
                    # Dispatch replaced the params handle; scheduler-thread
                    # probes pinned to the old version stop matching and any
                    # in-flight insert pinned to it is dropped.
                    self.mat_cache.bump_version("param_update")
                # Snapshot on checkpoint boundaries BEFORE the next dispatch
                # donates these buffers (jnp.copy enqueues ahead of donation).
                step_no = self.step + len(inflight) + 1
                snap = None
                if (self.ckpt and self.ckpt.every > 0
                        and step_no % self.ckpt.every == 0):
                    snap = jax.tree.map(jnp.copy,
                                        (self.params, self.opt_state))
                inflight.append((loss, per_q, item.patterns, item.n_queries,
                                 snap, item.phases))
                self._inflight_gauge.set(len(inflight))
                while len(inflight) >= max(self.cfg.max_inflight, 1):
                    t_last = self._retire(inflight.popleft(), t_last, log_every)
                    self._inflight_gauge.set(len(inflight))
            while inflight:
                t_last = self._retire(inflight.popleft(), t_last, log_every)
                self._inflight_gauge.set(len(inflight))
        finally:
            _sys.setswitchinterval(old_switch)
            pf.close()
            if self.sem_cache is not None:
                # Drained queue items may hold planned-but-unapplied stages;
                # drop residency metadata so future plans restage from disk.
                self.sem_cache.reconcile()
        if self.ckpt:
            self.ckpt.maybe_save(
                self.step, {"params": self.params, "opt": self.opt_state}, force=True
            )
        return self.history

    # ---------------------------------------------------------------- resume
    def resume(self) -> bool:
        if not self.ckpt:
            return False
        # Checkpoints store arrays UNSHARDED (host numpy); passing the
        # context's shardings reshards them onto whatever mesh THIS run has —
        # save on 8 devices, restore on 4 (mesh-shape-agnostic restore).
        shardings = None
        if self.ctx.is_sharded:
            shardings = {"params": self._param_sh, "opt": self._opt_sh}
        restored = self.ckpt.restore(
            template={"params": self.params, "opt": self.opt_state},
            shardings=shardings)
        if restored is None:
            return False
        self.step, tree, _ = restored
        self.params, self.opt_state = tree["params"], tree["opt"]
        if self.sem_cache is not None:
            # Restored cache buffers don't match whatever residency metadata
            # accumulated before resume; declare everything absent so the
            # next plan restages from the store into the restored buffers.
            self.sem_cache.reset()
        return True
