"""Training cells: the pipelined ``NGDBTrainer.train`` on online-sampled
batches from the seed.

Set-up builds one trainer with the benchmark's weights (``reference.
init_params``), warms its step program up on a stream that does not depend
on the seed, puts the weights back, and starts ONE ``train`` call on the
seed's feed. The first ``checked_steps`` steps of that call are the ones
the reference follows; the window opens when the last of them retires and
closes ``--seconds`` later, and steps count by their retire time. The call
is never drained or restarted inside the window.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import checks, harness, ops_count, reference


class WindowClosed(Exception):
    pass


def _leaf_norms(tree) -> Dict[str, float]:
    import jax.numpy as jnp

    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
            for k, v in tree.items()}


def build_trainer(run, params):
    """The program's trainer for the configuration, on ``params``."""
    import jax
    from repro.models import ModelConfig, make_model
    from repro.training import AdamConfig, NGDBTrainer, TrainConfig

    cfg, t = run.cfg, run.cfg["trainer"]
    g = cfg["graph"]
    model = make_model(cfg["family"], ModelConfig(**cfg["model"]))
    want = jax.eval_shape(
        lambda k: type(model).init_params(model, k, g["n_entities"],
                                          g["n_relations"]),
        jax.random.PRNGKey(0))
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in params.items()}
    exp = {k: (tuple(v.shape), str(v.dtype)) for k, v in want.items()}
    if got != exp:
        raise ValueError(f"benchmark params {got} != program's {exp}")

    def supplied(key, n_entities, n_relations, **kw):
        model.n_entities = n_entities
        return params

    model.init_params = supplied          # the benchmark's weights, not a
    tcfg = TrainConfig(                   # second init of the program's
        batch_size=t["batch_size"], n_negatives=t["n_negatives"],
        b_max=t["b_max"], adam=AdamConfig(**t["adam"]), pipeline=True,
        max_inflight=t["max_inflight"], prefetch=t["prefetch"], cse=t["cse"],
        patterns=tuple(run.mix["patterns"]),
        seed=harness.derive_seed(run.args.seed, "negatives"))
    return NGDBTrainer(model, run.kg, tcfg)


def _hooks_fired(tr, retired, dispatched, inputs, snaps, n_check) -> None:
    """The window is defined through the trainer's private ``_retire``,
    ``_train_fn`` and ``sampler.to_training_arrays``: fail loudly when a
    change to the program stops any of them from firing once per step."""
    inflight = max(tr.cfg.max_inflight, 1)
    faults = []
    if len(retired) != len(tr.history) or len(retired) != tr.step:
        faults.append(f"{len(retired)} retires seen, trainer history "
                      f"{len(tr.history)}, step {tr.step}")
    if not 0 <= len(dispatched) - len(retired) <= inflight:
        faults.append(f"{len(dispatched)} dispatches seen for "
                      f"{len(retired)} retires (max_inflight {inflight})")
    if len(inputs) != n_check or sorted(snaps) != sorted({1, n_check}):
        faults.append(f"{len(inputs)} checked inputs and snapshots "
                      f"{sorted(snaps)} recorded for {n_check} checked steps")
    if faults:
        raise RuntimeError("the benchmark's hooks into the trainer did not "
                           "fire once per step: " + "; ".join(faults))


def run(run) -> Dict:
    import jax
    from repro.training.optim import adam_init

    args, cfg, mix = run.args, run.cfg, run.mix
    t = cfg["trainer"]
    watch, clock = run.watch, run.clock
    run.kg = harness.build_graph(cfg)
    watch.mark("graph")
    params = reference.init_params(cfg, args.seed)
    jax.block_until_ready(params)
    watch.mark("params")
    tr = build_trainer(run, params)
    watch.mark("trainer")

    def feed_for(seed):
        return harness.train_feed(harness.online_sampler(run.kg, mix, seed),
                                  mix, t["batch_size"], seed)

    # Warm-up on the seed-independent stream compiles (or loads) the step
    # programs; then the weights and Adam state go back to the start.
    tr.train(mix["warmup_batches"], log_every=0,
             batches=feed_for(mix["warmup_seed"]))
    jax.block_until_ready(tr.params)
    tr.params = reference.init_params(cfg, args.seed)
    tr.opt_state = adam_init(tr.params, tr.cfg.adam)
    tr.step, tr.history = 0, []
    jax.block_until_ready((tr.params, tr.opt_state))
    watch.mark("warmup")

    n_check = mix["checked_steps"]
    inputs: List = []                      # (queries, pos, neg) per step
    orig_arrays = tr.sampler.to_training_arrays

    def recorded(batch, k):
        out = orig_arrays(batch, k)
        if len(inputs) < n_check:
            inputs.append(out)
        return out

    tr.sampler.to_training_arrays = recorded

    snaps: Dict[int, object] = {}
    dispatched: List[float] = []
    orig_train_fn = tr._train_fn

    def train_fn(prepared, example=None):
        fn = orig_train_fn(prepared, example)

        def step(*a):
            out = fn(*a)
            dispatched.append(time.perf_counter())
            n = len(dispatched)
            if n == 1:                        # Adam's m after step 1
                snaps[1] = jax.tree.map(jax.numpy.copy, out[1]["m"])
            if n == n_check:                  # params after the checked steps
                snaps[n_check] = jax.tree.map(jax.numpy.copy, out[0])
            return out

        return step

    tr._train_fn = train_fn

    win = harness.Window(args.seconds)
    retired: List = []          # (retire time, queries, loss, patterns)
    orig_retire = tr._retire
    trace = run.tracer if args.trace else None
    cc0 = [None]

    def retire(pending, t_last, log_every):
        out = orig_retire(pending, t_last, log_every)
        now = time.perf_counter()
        retired.append((now, pending[3], tr.history[-1]["loss"], pending[2]))
        if win.t0 is None and len(retired) == n_check:
            cc0[0] = (tr._train_fns.stats(), clock.snapshot())
            if trace is not None:
                trace.start()
            win.open()
        elif win.t0 is not None and now >= win.t_end:
            raise WindowClosed()
        return out

    tr._retire = retire
    n_max = 10 ** 7
    try:
        tr.train(n_max, log_every=0, batches=feed_for(
            harness.derive_seed(args.seed, "train")))
    except WindowClosed:
        pass
    t_close = time.perf_counter()
    if trace is not None:
        trace.stop()
    if win.t0 is None:
        raise RuntimeError("the window never opened")
    _hooks_fired(tr, retired, dispatched, inputs, snaps, n_check)
    steps_cc = tr._train_fns.stats()
    compiles = clock.since(cc0[0][1])

    in_win = [r for r in retired[n_check:] if r[0] <= win.t_end]
    queries = sum(r[1] for r in in_win)
    bad = sum(r[1] for r in in_win if not np.isfinite(r[2]))
    n_disp = sum(1 for d in dispatched if d >= win.t0 and d <= win.t_end)
    run.window_info = {
        "steps": len(in_win), "queries": queries,
        "dispatched_steps": n_disp,
        "window_compiles": int(steps_cc["misses"] - cc0[0][0]["misses"]),
        "backend_compiles": int(compiles["compiles"]),
        "t0": win.t0, "t_end": win.t_end, "t_close": t_close,
        "setup_compile": cc0[0][1],
        "ops": sum(ops_count.train_step_ops(cfg["family"], cfg["model"], r[3],
                                            t["n_negatives"])
                   for r in in_win)}
    harness.log(f"bench: window {args.seconds:.1f}s: {len(in_win)} steps "
                f"retired, {queries} queries, {n_disp} dispatched, "
                f"{run.window_info['window_compiles']} step signatures and "
                f"{int(compiles['compiles'])} backend compiles inside")
    run.read_memory()

    # Readings of the program for the reference: losses of the checked
    # steps, the first gradient as Adam got it, the change after them.
    b1 = t["adam"]["b1"]
    prog = {
        "losses": [r[2] for r in retired[:n_check]],
        "grad1": {k: v / (1.0 - b1)
                  for k, v in _leaf_norms(snaps[1]).items()},
    }
    p0 = reference.init_params(cfg, args.seed)
    prog["change"] = _leaf_norms(jax.tree.map(lambda a, b: a - b,
                                              snaps[n_check], p0))
    del tr, snaps, p0
    run.release()
    res = checks.train_readings(run.cell, cfg, args.seed, inputs, prog)
    ok = checks.judge(run.cell, res)
    attempted = n_disp * t["batch_size"]
    return run.result(correct=ok, attempted=attempted, failed=bad,
                      e2e={"train_queries_per_s": queries / args.seconds},
                      checks=res)
