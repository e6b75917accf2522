"""Observability layer tests (DESIGN.md §Observability): the metrics
registry (counters/gauges/histograms, snapshot/delta, the ONE registry-wide
reset), the span tracer (lanes, nesting across threads, async request spans,
trace-event schema validation), the JSONL metrics sink, step-time breakdown
records, and the ``repro.obs.report`` summarizers."""
import json
import threading
import time

import jax
import pytest

from repro.core import PooledExecutor
from repro.models import ModelConfig, make_model
from repro.obs import (Counter, Gauge, Histogram, MetricsSink, TRACER,
                       get_registry, read_jsonl, validate_trace)
from repro.obs.registry import MetricsRegistry, metric_key
from repro.obs.report import cache_tables, summarize_metrics, summarize_trace
from repro.serving import (ServingConfig, ServingEngine, make_workload,
                           run_closed_loop)
from repro.training import AdamConfig, NGDBTrainer, TrainConfig


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test leaves the process-wide tracer disabled."""
    yield
    TRACER.disable()


def _engine(tiny_kg, dim=8, **kw):
    model = make_model("gqe", ModelConfig(dim=dim, gamma=6.0))
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    return ServingEngine(model, params,
                         executor=PooledExecutor(model, b_max=64), **kw)


def _trainer(tiny_kg, dim=8, **cfg_kw):
    cfg = TrainConfig(batch_size=8, n_negatives=4, b_max=64,
                      adam=AdamConfig(lr=1e-3), seed=0, **cfg_kw)
    return NGDBTrainer(make_model("gqe", ModelConfig(dim=dim, gamma=6.0)),
                       tiny_kg, cfg)


# ------------------------------------------------------------------ registry
def test_counter_is_int_like():
    c = Counter("x_hits")
    c += 2
    c.inc(3)
    assert c == 5 and c > 4 and c <= 5 and bool(c)
    assert int(c) == 5 and float(c) == 5.0 and c / 2 == 2.5
    assert c + 1 == 6 and 1 + c == 6 and 10 - c == 5 and c - 1 == 4
    assert [0] * Counter("n") == []  # __index__
    d = Counter("y")
    d.inc(5)
    assert c == d and not (c < d)  # counter-vs-counter comparisons
    c.reset()
    assert c == 0 and not bool(c)


def test_gauge_reset_is_noop():
    g = Gauge("depth")
    g.set(7)
    g.reset()  # state, not history: reset must not fabricate depth 0
    assert g == 7


def test_histogram_window_and_summary():
    h = Histogram("lat", window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    assert h.count == 5 and h.sum == 15.0
    assert h.window_values() == [2.0, 3.0, 4.0, 5.0]  # bounded window
    s = h.summary()
    assert s["count"] == 5 and s["window_n"] == 4 and s["window"] == 4
    assert s["p50"] == 3.5 and s["max"] == 5.0
    with pytest.raises(ValueError):
        Histogram("bad", window=0)


def test_metric_key_sorts_labels():
    g = MetricsRegistry().group("cache", cache="encode")
    c = g.counter("hits", b="2", a="1")
    assert metric_key(c) == "cache_hits{a=1,b=2,cache=encode}"


def test_snapshot_aggregates_same_key_instances():
    reg = MetricsRegistry()
    c1 = reg.group("serving").counter("batches")
    c2 = reg.group("serving").counter("batches")  # second engine
    c1.inc(3)
    c2.inc(4)
    snap = reg.snapshot()
    assert snap["serving_batches"] == 7
    c1.inc(10)
    d = reg.delta(snap)
    assert d["serving_batches"] == 10


def test_snapshot_histogram_keys():
    reg = MetricsRegistry()
    h = reg.group("serving").histogram("latency_ms", window=8)
    h.observe(10.0)
    h.observe(20.0)
    snap = reg.snapshot()
    assert snap["serving_latency_ms_count"] == 2
    assert snap["serving_latency_ms_sum"] == 30.0
    assert snap["serving_latency_ms_window_n"] == 2
    assert snap["serving_latency_ms_p50"] == 15.0


def test_registry_holds_metrics_weakly():
    reg = MetricsRegistry()
    g = reg.group("tmp")
    c = g.counter("hits")
    c.inc()
    assert "tmp_hits" in reg.snapshot()
    del g, c  # component dies -> its metrics leave the snapshot
    assert "tmp_hits" not in reg.snapshot()


def test_group_reset_scopes_and_only():
    reg = MetricsRegistry()
    g = reg.group("eng")
    a, b = g.counter("a"), g.counter("b")
    a.inc(5)
    b.inc(5)
    g.reset(only=[a])
    assert a == 0 and b == 5
    g.reset()
    assert b == 0


def test_registry_reset_runs_hooks():
    reg = MetricsRegistry()
    fired = []

    class Comp:
        def hook(self):
            fired.append(1)

    comp = Comp()
    reg.on_reset(comp.hook)
    reg.reset()
    assert fired == [1]
    del comp  # weakly held: dead component's hook disappears
    reg.reset()
    assert fired == [1]


# ------------------------------------------- satellite: one reset, no drift
def test_registry_reset_zeroes_every_published_counter(tiny_kg):
    """Regression for counter-reset drift: after warmup, ONE registry-level
    reset() must zero every published counter together — no component-
    specific path can leave a sibling's counters running."""
    tr = _trainer(tiny_kg, materialized_rows=64)
    tr.train(3, log_every=0)
    engine = _engine(tiny_kg, dim=12, cfg=ServingConfig(max_batch=8))
    try:
        for f in engine.submit_many(make_workload(tiny_kg, 8, seed=3)):
            f.result(timeout=60)
        # warm state: counters across four+ subsystems are nonzero
        assert tr.compile_cache_stats()["train_step"]["misses"] > 0
        assert engine.stats()["submitted"] == 8
        get_registry().reset()
        # every live counter/histogram in the process is zero — checked at
        # the registry (the source of truth every stats() dict reads)
        for m in get_registry().metrics():
            if m.kind == "counter":
                assert m.read() == 0, f"{metric_key(m)} survived reset()"
            elif m.kind == "histogram":
                assert m.count == 0, f"{metric_key(m)} survived reset()"
        # and the published views agree
        cs = tr.compile_cache_stats()
        assert all(cs[k]["hits"] == 0 and cs[k]["misses"] == 0 for k in cs)
        st = engine.stats()
        assert st["submitted"] == 0 and st["completed"] == 0
        assert st["batches"] == 0 and st["coalesced"] == 0
        assert all(v == 0 for v in st["flushes"].values())
        assert st["retraces"] == 0  # re-baselined by the on_reset hook
        sh = tr.executor.sharing_stats()
        assert sh["nodes_before"] == 0 and sh["plan_cache"]["hits"] == 0
        assert sh["materialized"]["hits"] == 0
    finally:
        engine.close()


# --------------------------------------------- satellite: latency_window
def test_engine_latency_window_and_window_n(tiny_kg):
    engine = _engine(tiny_kg, dim=10, cfg=ServingConfig(max_batch=4),
                     latency_window=4)
    try:
        for f in engine.submit_many(make_workload(tiny_kg, 6, seed=5)):
            f.result(timeout=60)
        lm = engine.stats()["latency_ms"]
        assert lm["window"] == 4
        assert lm["window_n"] == 4  # 6 observed, window keeps the last 4
        assert lm["n"] == 4  # percentiles computed over the window
    finally:
        engine.close()
    with pytest.raises(ValueError):
        _engine(tiny_kg, dim=10, latency_window=0)


# -------------------------------------------------------------------- tracer
def test_disabled_tracer_is_free_and_silent():
    TRACER.disable()
    s1 = TRACER.span("a", n=1)
    s2 = TRACER.span("b")
    assert s1 is s2  # shared null context: the one-attribute-read fast path
    with s1:
        pass
    with TRACER.timed("c", {}, None, n=2) as ph:
        pass
    assert ph.span is None  # no span object, no clock read beyond the phase
    TRACER.counter("q", depth=3)
    TRACER.async_begin("r", 1)
    TRACER.async_end("r", 1)
    assert TRACER._events == []


def test_spans_nest_within_and_across_threads():
    TRACER.enable(jax_annotations=False)
    TRACER.set_lane("main dispatch")
    with TRACER.span("outer"):
        with TRACER.span("inner"):
            time.sleep(0.002)

    def worker():
        TRACER.set_lane("pipeline scheduler")
        with TRACER.span("schedule"):
            with TRACER.span("transfer"):
                time.sleep(0.002)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    obj = TRACER.to_json()
    s = validate_trace(obj)
    # superset: lane names persist process-wide, so earlier tests' threads
    # may also appear
    assert {"main dispatch", "pipeline scheduler"} <= set(s["lanes"])
    ev = {e["name"]: e for e in obj["traceEvents"] if e["ph"] == "X"}
    # children close before parents and sit inside the parent's interval,
    # on the parent's lane
    for child, parent in (("inner", "outer"), ("transfer", "schedule")):
        c, p = ev[child], ev[parent]
        assert c["tid"] == p["tid"]
        assert p["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
    # the two threads got distinct lanes
    assert ev["outer"]["tid"] != ev["schedule"]["tid"]


def test_set_lane_survives_enable():
    """Long-lived threads (batcher, scheduler) name their lane once at
    thread start — possibly before enable(); the name must still appear."""
    TRACER.disable()
    done = threading.Event()
    go = threading.Event()

    def worker():
        TRACER.set_lane("early bird")  # registered while disabled
        done.set()
        go.wait(5)
        with TRACER.span("work"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    done.wait(5)
    TRACER.enable(jax_annotations=False)
    go.set()
    t.join()
    s = validate_trace(TRACER.to_json())
    assert "early bird" in s["lanes"]
    assert "work" in s["names"]


def test_max_events_truncation():
    TRACER.enable(jax_annotations=False, max_events=3)
    for i in range(5):
        with TRACER.span(f"e{i}"):
            pass
        TRACER.counter(f"c{i}", depth=i)
    obj = TRACER.to_json()
    # metadata (lane names) is exempt from the cap; data events are capped
    data = [e for e in obj["traceEvents"] if e["ph"] != "M"]
    assert len(data) == 3
    assert obj["otherData"]["truncated"] is True
    validate_trace(obj)
    TRACER.enable(jax_annotations=False, max_events=2_000_000)  # restore


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"events": []})
    with pytest.raises(ValueError, match="missing key"):
        validate_trace({"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                                         "pid": 1, "tid": 1}]})  # no dur
    with pytest.raises(ValueError, match="bad dur"):
        validate_trace({"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                                         "dur": -1.0, "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError, match="unsupported phase"):
        validate_trace({"traceEvents": [{"name": "a", "ph": "Z"}]})
    with pytest.raises(ValueError, match="without begin"):
        validate_trace({"traceEvents": [
            {"name": "r", "ph": "e", "ts": 0.0, "id": 1, "pid": 1, "tid": 1,
             "cat": "request"}]})
    with pytest.raises(ValueError, match="unbalanced"):
        validate_trace({"traceEvents": [
            {"name": "r", "ph": "b", "ts": 0.0, "id": 1, "pid": 1, "tid": 1,
             "cat": "request"}]})


# ------------------------------------- satellite: trace ids through serving
def test_request_spans_thread_submit_to_complete(tiny_kg):
    engine = _engine(tiny_kg, dim=14, cfg=ServingConfig(max_batch=4))
    try:
        TRACER.enable(jax_annotations=False)
        for f in engine.submit_many(make_workload(tiny_kg, 6, seed=9)):
            f.result(timeout=60)
        obj = TRACER.to_json()
        TRACER.disable()
        s = validate_trace(obj)  # includes b/e balance per (cat, id, name)
        begins = [e for e in obj["traceEvents"]
                  if e["ph"] == "b" and e["name"] == "request"]
        assert len(begins) == 6
        assert len({e["id"] for e in begins}) == 6  # one async span each
        assert {"batch", "encode", "score", "select"} <= set(s["names"])
        assert "serving batcher" in s["lanes"]
    finally:
        engine.close()


def test_coalesced_requests_keep_distinct_request_spans(tiny_kg):
    """Duplicate in-flight requests share ONE computed row (one batch/encode
    span) but each keeps its own request span, so per-request latency stays
    attributable in the trace."""
    engine = _engine(tiny_kg, dim=14,
                     cfg=ServingConfig(max_batch=8, max_wait_ms=100.0))
    try:
        q = make_workload(tiny_kg, 1, seed=9)[0]
        TRACER.enable(jax_annotations=False)
        for f in engine.submit_many([q] * 8):
            f.result(timeout=60)
        obj = TRACER.to_json()
        TRACER.disable()
        validate_trace(obj)
        ids = {e["id"] for e in obj["traceEvents"]
               if e["ph"] == "b" and e["name"] == "request"}
        assert len(ids) == 8  # distinct request spans for every duplicate
        batches = [e for e in obj["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "batch"]
        assert len(batches) < 8  # shared compute spans
        assert any(len(b["args"]["trace_ids"]) > 1 for b in batches)
        # every request id appears in exactly one batch's trace_ids
        covered = [i for b in batches for i in b["args"]["trace_ids"]]
        assert sorted(covered) == sorted(ids)
        assert engine.stats()["coalesced"] >= 1
    finally:
        engine.close()


# ------------------------------------------------------- sink + breakdowns
def test_metrics_sink_disabled_and_roundtrip(tmp_path):
    off = MetricsSink(None)
    assert not off.enabled
    off.write({"kind": "step"})  # no-op
    assert off.records == 0
    p = tmp_path / "m.jsonl"
    with MetricsSink(str(p)) as sink:
        assert sink.enabled
        sink.write({"kind": "step", "loss": 1.5})
        sink.write({"kind": "snapshot", "metrics": {"a": 1}})
    recs = read_jsonl(str(p))
    assert [r["kind"] for r in recs] == ["step", "snapshot"]
    assert recs[0]["loss"] == 1.5


def test_sync_trainer_writes_step_records(tiny_kg, tmp_path):
    p = tmp_path / "sync.jsonl"
    tr = _trainer(tiny_kg, metrics_path=str(p))
    tr.train(3, log_every=0)
    recs = read_jsonl(str(p))
    assert len(recs) == 3
    for r in recs:
        assert r["kind"] == "step" and r["mode"] == "sync"
        assert "loss" in r and "schedule_s" in r and "retire_s" in r
    # history records are untouched: the JSONL is a separate surface
    assert set(tr.history[0]) == {"step", "loss", "queries_per_sec"}


def test_pipelined_trainer_writes_bubble_fraction(tiny_kg, tmp_path):
    from repro.sampling import OnlineSampler

    p = tmp_path / "pipe.jsonl"
    batches = [OnlineSampler(tiny_kg, seed=17).sample_batch(8)]
    tr = _trainer(tiny_kg, pipeline=True, metrics_path=str(p))
    tr.train(4, log_every=0, batches=batches)
    recs = read_jsonl(str(p))
    assert len(recs) == 4
    for r in recs:
        assert r["mode"] == "pipelined"
        assert 0.0 <= r["bubble_frac"] <= 1.0
        assert r["wall_s"] > 0
        assert "wait_s" in r and "schedule_s" in r and "transfer_s" in r


@pytest.fixture(scope="module")
def scheduler_trace(tiny_kg):
    """A pipelined ``train`` of 6 steps on a pinned batch (``batch_fn``
    given: the batch is drawn inline on the scheduler lane), traced; the
    trace object and the X events of each lane by lane name."""
    from repro.sampling import OnlineSampler

    batches = [OnlineSampler(tiny_kg, seed=23).sample_batch(8)]
    tr = _trainer(tiny_kg, pipeline=True)
    TRACER.enable(jax_annotations=False)
    try:
        tr.train(6, log_every=0, batches=batches)
        obj = TRACER.to_json()
    finally:
        TRACER.disable()
    lanes = {e["tid"]: e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M"}
    by_lane = {}
    for e in obj["traceEvents"]:
        if e["ph"] == "X":
            by_lane.setdefault(lanes.get(e["tid"], ""), []).append(e)
    return obj, by_lane


def test_scheduler_spans_join_each_dispatched_step(scheduler_trace):
    """Every dispatched step id has one span of each producer phase on the
    scheduler lane and one dispatch/compile span on the main lane; transfer
    spans carry the device bytes they created."""
    obj, by_lane = scheduler_trace
    validate_trace(obj)
    main, sched = by_lane["main dispatch"], by_lane["pipeline scheduler"]
    dispatched = [e["args"]["step"] for e in main
                  if e["name"] in ("dispatch", "compile")]
    assert len(dispatched) == 6 and len(set(dispatched)) == 6
    for step in dispatched:
        names = sorted(e["name"] for e in sched
                       if e.get("args", {}).get("step") == step)
        assert names == ["negatives", "prepared_put", "sample", "schedule",
                         "transfer"], (step, names)
    transfers = [e for e in sched if e["name"] == "transfer"]
    # pos [8] + neg [8, 4] int32 at least, on every batch
    assert all(e["args"]["bytes"] >= 8 * 5 * 4 for e in transfers)


def test_scheduler_lane_is_covered_by_spans(scheduler_trace):
    """The scheduler lane's spans cover >= 90% of its wall time from its
    first span's start to its last span's end: no unnamed hole."""
    _, by_lane = scheduler_trace
    sched = sorted(by_lane["pipeline scheduler"], key=lambda e: e["ts"])
    lo = sched[0]["ts"]
    hi = max(e["ts"] + e["dur"] for e in sched)
    covered, end = 0.0, lo
    for e in sched:  # union of the intervals
        a, b = max(e["ts"], end), e["ts"] + e["dur"]
        if b > a:
            covered += b - a
            end = b
    assert covered >= 0.9 * (hi - lo), (covered, hi - lo)


def test_span_thread_time_is_within_wall_time(scheduler_trace):
    """``tdur`` (thread CPU time) of every span lies in [0, dur + 50 us],
    and the validator rejects a negative one."""
    obj, _ = scheduler_trace
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert spans and all("tdur" in e for e in spans)
    for e in spans:
        assert 0.0 <= e["tdur"] <= e["dur"] + 50.0, e
    with pytest.raises(ValueError, match="bad tdur"):
        validate_trace({"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                                         "dur": 1.0, "tdur": -1.0, "pid": 1,
                                         "tid": 1}]})


def test_pipelined_train_records_nothing_with_tracing_off(tiny_kg):
    from repro.sampling import OnlineSampler

    TRACER.enable(jax_annotations=False)
    TRACER.disable()  # enable() cleared the buffer
    batches = [OnlineSampler(tiny_kg, seed=23).sample_batch(8)]
    tr = _trainer(tiny_kg, pipeline=True)
    tr.train(3, log_every=0, batches=batches)
    assert TRACER._events == []
    assert TRACER.span("a") is TRACER.span("b")


def test_worker_fed_scheduler_waits_in_sample_wait(tiny_kg):
    """With sampling workers, the scheduler lane only waits on their queue:
    its span is ``sample_wait``; the workers' lanes carry ``sample``."""
    tr = _trainer(tiny_kg, pipeline=True)
    TRACER.enable(jax_annotations=False)
    tr.train(3, log_every=0)
    obj = TRACER.to_json()
    TRACER.disable()
    lanes = {e["tid"]: e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M"}
    names = {}
    for e in obj["traceEvents"]:
        if e["ph"] == "X":
            names.setdefault(lanes.get(e["tid"], ""), set()).add(e["name"])
    assert "sample_wait" in names["pipeline scheduler"]
    assert "sample" not in names["pipeline scheduler"]
    assert any("sample" in v for k, v in names.items()
               if k.startswith("sampling worker"))


def test_tracing_keeps_losses_and_names_every_train_lane(tiny_kg):
    """Tracing on leaves a pipelined run's losses bitwise unchanged; a
    trace of a replayed run and an online-sampled one together has the
    main, scheduler and sampling-worker lanes and every phase's span."""
    from repro.sampling import OnlineSampler

    batches = [OnlineSampler(tiny_kg, seed=29).sample_batch(8)]
    off = _trainer(tiny_kg, pipeline=True)
    off.train(3, log_every=0, batches=batches)
    TRACER.enable(jax_annotations=False)
    on = _trainer(tiny_kg, pipeline=True)
    on.train(3, log_every=0, batches=batches)
    _trainer(tiny_kg, pipeline=True).train(2, log_every=0)
    s = validate_trace(TRACER.to_json())
    TRACER.disable()
    assert [h["loss"] for h in on.history] == [h["loss"] for h in off.history]
    assert {"main dispatch", "pipeline scheduler",
            "sampling worker 0"} <= set(s["lanes"])
    assert {"sample", "negatives", "schedule", "transfer", "pipeline_wait",
            "compile", "dispatch", "retire"} <= set(s["names"])


def test_multi_client_serving_trace_has_a_lane_per_client(tiny_kg):
    engine = _engine(tiny_kg, cfg=ServingConfig(max_batch=8))
    try:
        TRACER.enable(jax_annotations=False)
        run_closed_loop(engine, make_workload(tiny_kg, 24, seed=7),
                        concurrency=8, threads=3)
        s = validate_trace(TRACER.to_json())
        TRACER.disable()
    finally:
        engine.close()
    assert {"serving batcher", "client 0", "client 1",
            "client 2"} <= set(s["lanes"])
    assert {"request", "batch", "encode", "score", "select"} <= set(s["names"])


def test_phase_counters_register_in_snapshot(tiny_kg, mixed_queries):
    tr = _trainer(tiny_kg)
    # pinned batch: step 1 is the cold compile, step 2 a warm dispatch
    tr.train(2, log_every=0, batches=[list(mixed_queries)[:8]])
    snap = get_registry().snapshot()
    assert snap["trainer_steps"] >= 2
    assert snap["trainer_phase_seconds{phase=dispatch}"] > 0
    assert snap["trainer_phase_seconds{phase=retire}"] > 0


# ------------------------------------------------------------------- report
def test_report_summarizers():
    TRACER.enable(jax_annotations=False)
    TRACER.set_lane("main dispatch")
    with TRACER.span("dispatch"):
        time.sleep(0.001)
    out = summarize_trace(TRACER.to_json())
    TRACER.disable()
    assert "main dispatch" in out and "dispatch" in out

    steps = [{"kind": "step", "mode": "pipelined", "wall_s": 0.1,
              "wait_s": 0.01, "dispatch_s": 0.08, "bubble_frac": 0.1}] * 3
    out = summarize_metrics(steps)
    assert "3 step records" in out and "pipeline bubble" in out
    assert summarize_metrics([]).startswith("metrics: no step records")

    out = cache_tables({"cache_hits{cache=encode}": 3,
                        "cache_misses{cache=encode}": 1,
                        "plan_cache_hits": 9, "plan_cache_misses": 1,
                        "unrelated_gauge": 5})
    assert "cache{cache=encode}" in out and "75.0%" in out
    assert "plan_cache" in out and "90.0%" in out


def test_report_cli_end_to_end(tiny_kg, tmp_path):
    """The exact flow the README quickstart documents: train with
    --metrics/--trace equivalents, then summarize both files."""
    from repro.obs.report import main as report_main

    p = tmp_path / "m.jsonl"
    tp = tmp_path / "t.json"
    TRACER.enable(jax_annotations=False)
    tr = _trainer(tiny_kg, metrics_path=str(p))
    tr.train(2, log_every=0)
    tr.metrics_sink.write({"kind": "snapshot",
                           "metrics": get_registry().snapshot()})
    tr.metrics_sink.close()
    TRACER.write(str(tp))
    TRACER.disable()
    validate_trace(json.load(open(tp)))
    report_main(["--trace", str(tp), "--metrics", str(p)])
