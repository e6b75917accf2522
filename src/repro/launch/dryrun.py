import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
# cell against the production mesh using ShapeDtypeStruct inputs — no real
# allocation anywhere. Records memory_analysis, cost_analysis and the parsed
# collective schedule for EXPERIMENTS.md §Dry-run / §Roofline.
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k [--multi-pod]
#   python -m repro.launch.dryrun --all [--multi-pod] --out results/
#   python -m repro.launch.dryrun --ngdb            # the paper's own model
#
# NOTE: the XLA_FLAGS assignment above MUST stay the first statement — jax
# locks the host device count on first init.

import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_arch
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    dp_axes,
    tree_param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import model_flops, parse_collectives, roofline_terms
from repro.lm.config import LMConfig
from repro.lm.model import abstract_params
from repro.lm.shapes import SHAPES, cell_supported, input_specs
from repro.lm.steps import make_decode_step, make_prefill_step, make_train_step
from repro.training.optim import adam_init

# The chip the production mesh is made of (roofline peaks are keyed by it).
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mem_analysis(compiled) -> Dict:
    try:
        m = compiled.memory_analysis()
        return {
            "argument_bytes": int(getattr(m, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(m, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(m, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(m, "alias_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(m, "argument_size_in_bytes", 0)
                + getattr(m, "temp_size_in_bytes", 0)
                + getattr(m, "output_size_in_bytes", 0)
                - getattr(m, "alias_size_in_bytes", 0)
            ),
        }
    except Exception as e:  # some backends don't implement it
        return {"error": repr(e)}


def _cost_analysis(compiled) -> Dict:
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return {k: float(v) for k, v in c.items() if np.isscalar(v)}
    except Exception as e:
        return {"error": repr(e)}


def _lower_cell(cfg: LMConfig, shape: str, mesh,
                profile: str = "2d") -> "jax.stages.Lowered":
    """Build jit + in_shardings for one cell and lower it."""
    cell = SHAPES[shape]
    dp = dp_axes(mesh, profile)
    params_abs = abstract_params(cfg)
    p_sh = tree_param_shardings(params_abs, mesh, cfg.moe_mode, profile)
    specs = input_specs(cfg, shape)
    with mesh:
        if cell.kind == "train":
            opt_abs = jax.eval_shape(adam_init, params_abs)
            o_sh = tree_param_shardings(opt_abs, mesh, cfg.moe_mode, profile)
            b_sh = batch_shardings(specs["batch"], mesh, profile)
            fn = make_train_step(cfg, mesh, dp)
            jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                             donate_argnums=(0, 1))
            return jitted.lower(params_abs, opt_abs, specs["batch"])
        if cell.kind == "prefill":
            b_sh = batch_shardings(specs["batch"], mesh, profile)
            fn = make_prefill_step(cfg, mesh, dp)
            jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
            return jitted.lower(params_abs, specs["batch"])
        c_sh = cache_shardings(specs["caches"], mesh)
        t_sh = batch_shardings({"tokens": specs["tokens"]}, mesh)["tokens"]
        fn = make_decode_step(cfg, mesh, dp)
        jitted = jax.jit(
            fn,
            in_shardings=(p_sh, c_sh, t_sh, NamedSharding(mesh, P())),
            donate_argnums=(1,),
        )
        return jitted.lower(params_abs, specs["caches"], specs["tokens"],
                            specs["cache_len"])


def _exact_cost(cfg: LMConfig, shape: str, mesh, n_dev: int,
                profile: str = "2d") -> Dict:
    """Exact per-device cost via k=2/k=3-block fully-unrolled compiles +
    linear extrapolation over the n_rep identical blocks. lax.scan bodies are
    counted once by XLA cost analysis, so the deployable (scanned) program
    cannot be costed directly; unrolled small models + extrapolation is exact
    because blocks are identical (validated: k=3 sits on the k=2/k=4 line to
    0.03%; k=1 is excluded — the partitioner makes different layout choices
    for single-layer models)."""
    from repro.lm.model import block_pattern

    pat = len(block_pattern(cfg))
    n_rep = cfg.n_layers // pat
    # SSM/hybrid blocks unroll every SSD chunk too; k=(1,2) keeps those
    # compiles bounded (multi-layer blocks are already past the k=1 anomaly).
    ks = (1, 2) if (cfg.ssm_state > 0 and pat >= 8) else (2, 3)
    samples = []
    for k in ks:
        over = {"n_layers": pat * k, "exact_cost_mode": True}
        if cfg.encoder_layers:
            over["encoder_layers"] = k
        cfg_k = dataclasses.replace(cfg, **over)
        compiled = _lower_cell(cfg_k, shape, mesh, profile).compile()
        cost = _cost_analysis(compiled)
        coll = parse_collectives(compiled.as_text(), n_dev)
        samples.append(
            (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
             coll.wire_bytes, coll.by_type, coll.counts)
        )
    (f1, b1, w1, t1, c1), (f2, b2, w2, t2, c2) = samples

    def ext(a, b):
        return a + (n_rep - ks[0]) * max(b - a, 0.0)

    by_type = {k: ext(t1.get(k, 0.0), t2.get(k, 0.0))
               for k in set(t1) | set(t2)}
    counts = {k: int(ext(c1.get(k, 0), c2.get(k, 0)))
              for k in set(c1) | set(c2)}
    return {
        "flops": ext(f1, f2),
        "bytes_accessed": ext(b1, b2),
        "wire_bytes": ext(w1, w2),
        "collective_by_type": by_type,
        "collective_counts": counts,
        "blocks_extrapolated": n_rep,
    }


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             cfg: Optional[LMConfig] = None, override=None,
             analyze: bool = True, profile: str = "2d") -> Dict:
    """Lower + compile one cell; returns the full record for EXPERIMENTS.md."""
    cfg = cfg or get_arch(arch)
    if override:
        cfg = dataclasses.replace(cfg, **override)
    cell = SHAPES[shape]
    rec: Dict = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16(pod,data,model)" if multi_pod else "16x16(data,model)",
        "kind": cell.kind,
    }
    skip = cell_supported(cfg, shape)
    if skip:
        rec["skipped"] = skip
        return rec

    rec["profile"] = profile
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowered = _lower_cell(cfg, shape, mesh, profile)
    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()  # REQUIRED: proves the cell compiles
    rec["compile_s"] = round(time.time() - t1, 1)

    rec["memory"] = _mem_analysis(compiled)
    cost = _cost_analysis(compiled)
    rec["cost_raw"] = {k: cost[k] for k in ("flops", "bytes accessed")
                       if k in cost} or cost
    coll = parse_collectives(compiled.as_text(), n_dev)
    rec["collectives_raw"] = coll.as_dict()

    if analyze:
        try:
            exact = _exact_cost(cfg, shape, mesh, n_dev, profile)
            rec["cost_exact"] = exact
            rec["roofline"] = roofline_terms(
                exact["flops"], exact["bytes_accessed"], exact["wire_bytes"],
                TARGET_DEVICE_KIND)
        except Exception:
            rec["cost_exact"] = {"error": traceback.format_exc(limit=10)}
            rec["roofline"] = roofline_terms(
                cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
                coll.wire_bytes, TARGET_DEVICE_KIND)
    else:
        rec["roofline"] = roofline_terms(
            cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
            coll.wire_bytes, TARGET_DEVICE_KIND)

    mf = model_flops(cfg, cell, cell.kind)
    rec["model_flops_global"] = mf
    rec["model_flops_per_device"] = mf / n_dev
    got = rec.get("cost_exact", {}).get("flops") or cost.get("flops", 0.0)
    if got:
        rec["useful_flops_ratio"] = (mf / n_dev) / got
    return rec


# ---------------------------------------------------------------- NGDB cell
def run_ngdb_cell(multi_pod: bool = False, dataset: str = "ogbl-wikikg2",
                  model_name: str = "betae", batch: int = 512,
                  n_neg: int = 64, dim: int = 400,
                  entity_pad: int = 4096, sparse_updates: bool = False) -> Dict:
    """Dry-run the paper's own training step at production scale: entity +
    semantic tables sharded over the mesh, one operator-level batch of mixed
    patterns, vectorized loss, Adam."""
    from repro.core.executor import PooledExecutor
    from repro.core.patterns import TEMPLATES, QueryInstance
    from repro.data.kg import TABLE4
    from repro.models.base import ModelConfig, make_model
    from repro.training.loss import negative_sampling_loss
    from repro.training.optim import AdamConfig, adam_update

    stats = TABLE4[dataset]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": f"ngdb-{model_name}-{dataset}", "shape": f"train_b{batch}",
           "mesh": "2x16x16" if multi_pod else "16x16", "kind": "train",
           "entity_pad": entity_pad, "sparse_updates": sparse_updates}
    t0 = time.time()

    model = make_model(model_name, ModelConfig(dim=dim, semantic_dim=1024,
                                               entity_pad=entity_pad))
    # One representative mixed batch (uniform over the 14 patterns).
    rng = np.random.default_rng(0)
    pats = list(TEMPLATES)
    queries = []
    for i in range(batch):
        t = TEMPLATES[pats[i % len(pats)]]
        queries.append(QueryInstance(
            pats[i % len(pats)],
            rng.integers(0, stats.n_entities, t.n_anchors),
            rng.integers(0, stats.n_relations, t.n_relations),
        ))
    ex = PooledExecutor(model, b_max=512)
    prepared = ex.prepare(queries)
    encode = ex.encode_fn(prepared)
    steps_np, ans = prepared.device_args()

    rows = model.padded_entities(stats.n_entities)
    sem_table = jax.ShapeDtypeStruct((rows, 1024), jnp.float32)
    params_abs = jax.eval_shape(
        lambda k, st: model.init_params(k, stats.n_entities, stats.n_relations,
                                        semantic_table=st),
        jax.random.PRNGKey(0), sem_table)
    opt_abs = jax.eval_shape(adam_init, params_abs)
    p_sh = tree_param_shardings(params_abs, mesh)
    o_sh = tree_param_shardings(opt_abs, mesh)
    adam = AdamConfig(lr=1e-4)

    steps_abs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (steps_np, ans))
    pos_abs = jax.ShapeDtypeStruct((batch,), jnp.int64)
    neg_abs = jax.ShapeDtypeStruct((batch, n_neg), jnp.int64)

    def train_step(params, opt_state, step_arrays, pos, neg):
        def loss_fn(p):
            q = encode(p, step_arrays[0], step_arrays[1])
            loss, _ = negative_sampling_loss(model, p, q, pos, neg)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = adam_update(grads, opt_state, params, adam)
        return params, opt_state, loss

    # §Perf iteration N3: row-sparse embedding updates. One batch touches
    # ~35k unique entity rows; the dense step streams the full 2.5M-row table
    # + both Adam moments every step (~70x waste). The sparse step gathers the
    # touched rows into a minibatch-local table (host dedups + remaps indices
    # — the same Precomputed Indexing machinery), differentiates w.r.t. the
    # LOCAL table only, and scatter-writes rows + moments back.
    u_rows = batch * 3 + batch * (1 + n_neg)  # anchors + pos + negs (padded)
    ids_abs = jax.ShapeDtypeStruct((u_rows,), jnp.int32)

    def train_step_sparse(params, opt_state, step_arrays, ids, pos_l, neg_l):
        ent_rows = params["entity"][ids]
        sem_rows = params["sem_table"][ids]
        m_rows = opt_state["m"]["entity"][ids]
        v_rows = opt_state["v"]["entity"][ids]

        def loss_fn(rows):
            p_local = dict(params, entity=rows, sem_table=sem_rows)
            q = encode(p_local, step_arrays[0], step_arrays[1])
            loss, _ = negative_sampling_loss(model, p_local, q, pos_l, neg_l)
            return loss

        loss, g_rows = jax.value_and_grad(loss_fn)(ent_rows)
        # row-local Adam (global bias correction; standard for sparse KGE)
        step = opt_state["step"] + 1
        b1t = 1.0 - adam.b1 ** step.astype(jnp.float32)
        b2t = 1.0 - adam.b2 ** step.astype(jnp.float32)
        m_rows = adam.b1 * m_rows + (1 - adam.b1) * g_rows
        v_rows = adam.b2 * v_rows + (1 - adam.b2) * jnp.square(g_rows)
        new_rows = ent_rows - adam.lr * (m_rows / b1t) / (
            jnp.sqrt(v_rows / b2t) + adam.eps)
        params = dict(params, entity=params["entity"].at[ids].set(new_rows))
        opt_state = dict(
            opt_state,
            m=dict(opt_state["m"], entity=opt_state["m"]["entity"].at[ids].set(m_rows)),
            v=dict(opt_state["v"], entity=opt_state["v"]["entity"].at[ids].set(v_rows)),
            step=step,
        )
        return params, opt_state, loss

    with mesh:
        repl = jax.tree.map(lambda _: NamedSharding(mesh, P()), steps_abs)
        if sparse_updates:
            jitted = jax.jit(
                train_step_sparse,
                in_shardings=(p_sh, o_sh, repl, NamedSharding(mesh, P()),
                              NamedSharding(mesh, P()), NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_abs, opt_abs, steps_abs, ids_abs,
                                   pos_abs, neg_abs)
        else:
            jitted = jax.jit(
                train_step,
                in_shardings=(p_sh, o_sh, repl,
                              NamedSharding(mesh, P()), NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_abs, opt_abs, steps_abs, pos_abs, neg_abs)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    rec["memory"] = _mem_analysis(compiled)
    cost = _cost_analysis(compiled)
    rec["cost"] = {k: cost[k] for k in ("flops", "bytes accessed") if k in cost} or cost
    coll = parse_collectives(compiled.as_text(), n_dev)
    rec["collectives"] = coll.as_dict()
    rec["roofline"] = roofline_terms(cost.get("flops", 0.0),
                                     cost.get("bytes accessed", 0.0),
                                     coll.wire_bytes, TARGET_DEVICE_KIND)
    rec["schedule_stats"] = prepared.sched.stats
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--ngdb", action="store_true")
    ap.add_argument("--no-analyze", action="store_true",
                    help="skip the k=2/k=3 exact-cost compiles (full compile only)")
    ap.add_argument("--out", default=None, help="directory for per-cell JSON")
    args = ap.parse_args()

    cells = []
    if args.ngdb:
        cells = [("ngdb", None)]
    elif args.all:
        cells = [(a, s) for a in sorted(ARCHS) for s in SHAPES]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        try:
            if arch == "ngdb":
                rec = run_ngdb_cell(multi_pod=args.multi_pod)
            else:
                rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                               analyze=not args.no_analyze)
        except Exception:
            rec = {"arch": arch, "shape": shape, "multi_pod": args.multi_pod,
                   "error": traceback.format_exc(limit=20)}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = f"{rec['arch']}_{rec.get('shape')}_{'mp' if args.multi_pod else 'sp'}"
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                f.write(line)


if __name__ == "__main__":
    main()
