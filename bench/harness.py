"""Shared pieces of the benchmark: finding a cell's files by name, the graph,
the training feed, the compile clock and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json``: family, model widths, graph and trainer
  settings, ``source``, ``assumed``, ``reduced``;
* ``bench/traffic/<traffic>.json``: the parameters one general generator
  reads; its ``kind`` names the runner, ``bench/kinds/<kind>.py``;
* ``bench/metrics/<metric>.py``: one reducer per per-layer metric;
* ``bench/ref/<family>.py``: the plain reference of one model family.

This module imports nothing of the program at import time; ``add_src_path``
puts the program's ``src`` on ``sys.path`` for the runners.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def add_src_path() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no repro package under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_config(name: str) -> Dict:
    cfg = _read_json(os.path.join(BENCH, "configs", name + ".json"))
    cfg["name"] = name
    return cfg


def load_traffic(name: str) -> Dict:
    mix = _read_json(os.path.join(BENCH, "traffic", name + ".json"))
    mix["name"] = name
    return mix


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: Dict, workload: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(bench: Dict, workload: str, section: str) -> List[Dict]:
    """The metrics of ``section`` (end_to_end or per_layer) of this cell."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def seed_words(seed: int) -> List[int]:
    """A seed of any size as the unsigned 32-bit words numpy and JAX take."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose)."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng(seed_words(seed) + [0x9E3779B9] + tag)


def derive_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for program APIs that take an int, from (seed, stream)."""
    return int(rng_for(seed, stream).integers(0, 2 ** 31 - 1))


# ---------------------------------------------------------------------- graph
def draw_triples(rng, n_entities: int, n_relations: int, m: int,
                 hub_exponent: float) -> np.ndarray:
    """m (h, r, t) rows: Zipf-like heads and tails, relations skewed by
    rank**-0.5. Copied from the program's ``data/kg.py::_draw_triples`` so
    the graph cannot move with the program."""
    ent_w = np.arange(1, n_entities + 1, dtype=np.float64) ** (-hub_exponent)
    ent_p = ent_w / ent_w.sum()
    rel_w = np.arange(1, n_relations + 1, dtype=np.float64) ** (-0.5)
    rel_p = rel_w / rel_w.sum()
    h = rng.choice(n_entities, size=m, p=ent_p)
    t = rng.choice(n_entities, size=m, p=ent_p)
    r = rng.choice(n_relations, size=m, p=rel_p)
    return np.stack([h, r, t], axis=1)


def graph_triples(graph: Dict) -> np.ndarray:
    """The training triples of the configuration's graph: distinct triples
    drawn until train + valid + test exist, shuffled, the first ``n_train``
    kept (``data/kg.py::generate_table4_kg``, copied)."""
    rng = np.random.default_rng(graph["graph_seed"])
    e, r = graph["n_entities"], graph["n_relations"]
    n = graph["n_train"] + graph["n_valid"] + graph["n_test"]
    h = graph["hub_exponent"]
    tri = np.unique(draw_triples(rng, e, r, int(n * 1.3) + 16, h), axis=0)
    while len(tri) < n:
        more = draw_triples(rng, e, r, 2 * (n - len(tri)) + 1024, h)
        tri = np.unique(np.concatenate([tri, more]), axis=0)
    tri = tri[rng.permutation(len(tri))[:n]]
    return tri[:graph["n_train"]]


def build_graph(cfg: Dict):
    """The program's ``KnowledgeGraph`` over the configuration's triples."""
    from repro.data.kg import KnowledgeGraph

    g = cfg["graph"]
    return KnowledgeGraph(g["n_entities"], g["n_relations"], graph_triples(g),
                          name=g["dataset"] + "-train")


# ------------------------------------------------------------------ templates
# The 14 EFO patterns as node lists (op, inputs), in the node order the
# program's ``core/patterns.py`` uses: anchors fill EMBED nodes and
# relations fill PROJECT nodes in this order. E embed, P project,
# I intersect, U union, N negate; the last node is the answer.
TEMPLATES: Dict[str, tuple] = {
    "1p": (("E", ()), ("P", (0,))),
    "2p": (("E", ()), ("P", (0,)), ("P", (1,))),
    "3p": (("E", ()), ("P", (0,)), ("P", (1,)), ("P", (2,))),
    "2i": (("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)), ("I", (2, 3))),
    "3i": (("E", ()), ("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)),
           ("P", (2,)), ("I", (3, 4, 5))),
    "pi": (("E", ()), ("P", (0,)), ("P", (1,)), ("E", ()), ("P", (3,)),
           ("I", (2, 4))),
    "ip": (("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)), ("I", (2, 3)),
           ("P", (4,))),
    "2u": (("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)), ("U", (2, 3))),
    "up": (("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)), ("U", (2, 3)),
           ("P", (4,))),
    "2in": (("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)), ("N", (3,)),
            ("I", (2, 4))),
    "3in": (("E", ()), ("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)),
            ("P", (2,)), ("N", (5,)), ("I", (3, 4, 6))),
    "inp": (("E", ()), ("E", ()), ("P", (0,)), ("P", (1,)), ("N", (3,)),
            ("I", (2, 4)), ("P", (5,))),
    "pin": (("E", ()), ("P", (0,)), ("P", (1,)), ("E", ()), ("P", (3,)),
            ("N", (4,)), ("I", (2, 5))),
    "pni": (("E", ()), ("P", (0,)), ("P", (1,)), ("N", (2,)), ("E", ()),
            ("P", (4,)), ("I", (3, 5))),
}


# ------------------------------------------------------------ training feed
def batch_patterns(mix: Dict, batch_size: int) -> List[str]:
    """The patterns of one training batch: every pattern of the mix
    ``batch_size // n`` times, the first ``batch_size % n`` once more."""
    pats = mix["patterns"]
    n, extra = divmod(batch_size, len(pats))
    return [p for i, p in enumerate(pats) for _ in range(n + (i < extra))]


def online_sampler(kg, mix: Dict, seed: int):
    """The program's online sampler over the mix's templates."""
    from repro.sampling import OnlineSampler

    return OnlineSampler(kg, patterns=mix["patterns"], seed=seed,
                         degree_weighted=mix["degree_weighted_answers"])


def train_feed(sampler, mix: Dict, batch_size: int, seed: int):
    """A zero-argument callable yielding training batches: every batch holds
    the same pattern counts (``batch_patterns``) in a new order drawn from
    ``seed``, each query sampled by the program's ``sampler``. So every
    seed asks the same work of each operator, and the batches keep one
    program signature with CSE off."""
    pats = np.array(batch_patterns(mix, batch_size))
    order_rng = np.random.default_rng(seed_words(seed) + [0x5EED])

    def feed():
        return [sampler.sample(p) for p in order_rng.permutation(pats)]

    return feed


# -------------------------------------------------------------- compile clock
class CompileClock:
    """Backend compile seconds and persistent-cache hits from JAX's
    monitoring events (process-wide; read as deltas). Copied from the
    program's ``chip_smoke.py``."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.cache_hits}

    def since(self, snap: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


class Stopwatch:
    """Named set-up phases on the host clock."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.phases: Dict[str, float] = {}
        self._last = t0

    def mark(self, name: str) -> float:
        now = time.perf_counter()
        self.phases[name] = self.phases.get(name, 0.0) + now - self._last
        self._last = now
        return now


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Window:
    """The measured window on the host clock."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.t0: Optional[float] = None
        self.t_end: Optional[float] = None

    def open(self) -> float:
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        return self.t0


TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class TraceSession:
    """The profiler over the window (``--trace 1``): the program's span
    tracer on, bridged into the profiler, and one ``bench.window``
    annotation marking the window on the profiler's own clock."""

    WINDOW = "bench.window"

    def __init__(self):
        self._ann = None
        self.path: Optional[str] = None

    def start(self) -> None:
        import jax
        from repro.obs.trace import TRACER

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACER.enable(jax_annotations=True)
        jax.profiler.start_trace(TRACE_DIR)
        self._ann = jax.profiler.TraceAnnotation(self.WINDOW)
        self._ann.__enter__()

    def stop(self) -> None:
        import jax
        from repro.obs.trace import TRACER

        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        TRACER.disable()

    def events(self) -> List[dict]:
        from repro.obs.trace import TRACER

        return TRACER.events()

    def xplane(self) -> Optional[str]:
        for d, _, files in os.walk(TRACE_DIR):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(d, f)
        return None

    def cleanup(self, keep: Optional[str] = None) -> None:
        if keep and os.path.isdir(TRACE_DIR):
            shutil.copytree(TRACE_DIR, keep, dirs_exist_ok=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


@dataclasses.dataclass
class Run:
    """One run of one cell: its inputs, clocks and what it read."""

    args: object
    bench: Dict
    cell: Dict
    cfg: Dict
    mix: Dict
    devices: list
    t_start: float

    def __post_init__(self):
        self.watch = Stopwatch(self.t_start)
        self.clock = CompileClock()
        self.tracer = TraceSession() if self.args.trace else None
        self.memory_peak: Optional[int] = None
        self.window_info: Dict = {}

    def read_memory(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None

    def release(self) -> None:
        gc.collect()

    def result(self, *, correct: bool, attempted: int, failed: int,
               e2e: Dict[str, float], checks: Dict) -> Dict:
        info = self.window_info
        setup_s = info["t0"] - self.t_start
        phases = {k: round(v, 3) for k, v in self.watch.phases.items()}
        log(f"bench: set-up {setup_s:.3f}s: {phases}; backend "
            f"compile {info['setup_compile']['compile_s']:.3f}s in "
            f"{int(info['setup_compile']['compiles'])} compiles, "
            f"{int(info['setup_compile']['cache_hits'])} persistent-cache "
            f"hits")
        d0 = self.devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": self.memory_peak}
        out = {"correct": bool(correct), "attempted": int(attempted),
               "failed": int(failed)}
        if self.args.trace:
            from bench import layers

            metrics, breakdown, busy = layers.read(self)
            device.update(busy)
            out.update(metrics=metrics, device=device)
            if breakdown:
                out["breakdown"] = breakdown
        else:
            values = dict(e2e, setup_s=setup_s)
            units = {m["name"]: m["unit"] for m in self.bench["end_to_end"]}
            metrics = {}
            for m in cell_metrics(self.bench, self.cell["name"],
                                  "end_to_end"):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": units[m["name"]]}
            out.update(metrics=metrics, device=device)
        out["checks"] = checks
        return out


def print_result(result: Dict) -> None:
    """The numbers compared, each beside its limit, last on stderr; the
    result line last on stdout."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
