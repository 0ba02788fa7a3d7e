import os  # noqa: F401 — kept first: flag setup precedes every jax use
# MUST run before anything initializes jax: jax locks the device count
# on first init. ensure_host_devices PRESERVES user/CI-provided
# XLA_FLAGS (an explicit external device-count directive wins; other
# flags are kept either way). Non-strict: a deliberately smaller
# external count falls through to the mesh-size checks below.
from ..distributed.spmd_runtime import ensure_host_devices

ensure_host_devices(512, strict=False)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline terms.

Per cell:
  - build the step function (train/prefill/decode/serve/retrieval) with
    the arch's full config,
  - build ShapeDtypeStruct stand-ins for params/opt-state/batch with
    NamedShardings on the target mesh (no allocation),
  - ``jax.jit(step).lower(...).compile()`` — success proves the sharding
    config is coherent (no mismatched specs, no OOM-at-compile, all
    collectives supported),
  - record ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
    (FLOPs/bytes) and the collective-byte census parsed from the
    partitioned HLO (with while-loop trip-count multiplication).

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.inputs import input_specs, step_kind
from ..configs.registry import cells, get_arch
from ..distributed.sharding import gnn_specs, lm_rules, recsys_specs
from ..models import transformer as tfm
from ..train import train_loop as tl
from ..train.optimizer import adamw, zero1_specs
from .hlo_census import collective_census
from .mesh import HW, make_production_mesh

I32 = jnp.int32


def _ns(mesh, spec):
    return NamedSharding(mesh, spec if spec is not None else P())


def _tree_ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: _ns(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _fit_spec(mesh, spec, shape):
    """Trim a PartitionSpec to the leaf rank and drop axes that do not
    divide the corresponding dim (e.g. batch=1 retrieval can't shard)."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    parts = list(spec)[: len(shape)]
    parts += [None] * (len(shape) - len(parts))
    fitted = []
    for dim, part in zip(shape, parts):
        if part is None:
            fitted.append(None)
            continue
        axes = part if isinstance(part, tuple) else (part,)
        extent = 1
        for a in axes:
            extent *= mesh_shape.get(a, 1)
        fitted.append(part if dim % extent == 0 and dim >= extent else None)
    return P(*fitted)


def _batch_sharding(mesh, batch_sds, family, cfg, rules=None):
    """NamedShardings for the batch dict."""
    if family == "lm":
        rules = rules if rules is not None else lm_rules(mesh)
        dp = rules.dp
        out = {}
        for k, v in batch_sds.items():
            spec = P(dp) if v.ndim == 1 else P(dp, *([None] * (v.ndim - 1)))
            out[k] = _ns(mesh, _fit_spec(mesh, spec, v.shape))
        return out
    table = gnn_specs(mesh) if family == "gnn" else recsys_specs(mesh)
    return {
        k: _ns(mesh, _fit_spec(mesh, table.get(k, P()), v.shape))
        for k, v in batch_sds.items()
    }


def _pad_gnn_batch(batch_sds, mesh):
    """Pad edge/node axes to multiples of the device count (masked padding
    is free; uneven shardings are what we avoid)."""
    ndev = mesh.devices.size
    out = {}
    for k, v in batch_sds.items():
        if k in ("edge_src", "edge_dst", "edge_mask", "node_mask",
                 "graph_ids", "labels", "label_mask", "node_feat") and v.ndim == 1:
            out[k] = jax.ShapeDtypeStruct((_pad_to(v.shape[0], ndev),), v.dtype)
        elif k in ("node_feat", "positions") and v.ndim == 2:
            out[k] = jax.ShapeDtypeStruct(
                (_pad_to(v.shape[0], ndev), v.shape[1]), v.dtype
            )
        else:
            out[k] = v
    return out


# --------------------------------------------------------------------------
# per-cell setup: returns (fn, args, in_shardings, meta)
# --------------------------------------------------------------------------
def setup_cell(arch_id: str, shape_id: str, mesh: Mesh, *, opt: bool = False):
    """``opt=True`` applies the §Perf beyond-baseline configuration:
    LM: flash attention from 2k ctx + MoE capacity-axis sharding +
    Megatron-style sequence parallelism; GNN: node arrays sharded over
    every mesh axis (not just data)."""
    arch = get_arch(arch_id)
    if arch.family == "graph-analytics":
        return _setup_lcc(arch.config(), mesh,
                          {"arch": arch_id, "shape": shape_id, "kind": "lcc"})
    cfg, shape, batch_sds = input_specs(arch_id, shape_id)
    kind = step_kind(arch, shape)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    meta = {"arch": arch_id, "shape": shape_id, "kind": kind, "opt": opt}

    if arch.family == "lm":
        rules = lm_rules(mesh)
        if opt:
            # §Perf iteration 2: flash attention + MoE capacity sharding.
            # Sequence parallelism was tried in iteration 1 and REFUTED
            # (GSPMD re-gathers activations around attention, 3x collective
            # regression — see EXPERIMENTS.md §Perf), so it stays off.
            cfg = dataclasses.replace(
                cfg, flash_cutoff=2048, flash_block=1024,
                moe_impl="local_ep",
            )
            rules = dataclasses.replace(rules, mesh=mesh)
            # §Perf iteration 5: right-size the parallelism — a 1.6B dense
            # model at TP=16 drowns in activation all-reduces (the Fig-9
            # "over-partitioning" effect the paper observes for graphs).
            # Fold the model axis into data parallelism when the model is
            # small enough that pure DP fits (params+opt < HBM/4).
            if (not cfg.is_moe and cfg.param_count() * 14 <
                    HW.HBM_BYTES * 0.25 * mesh.devices.size
                    and kind == "lm_train"
                    and cfg.param_count() < 3e9):
                all_ax = tuple(mesh.axis_names)
                rules = dataclasses.replace(
                    rules, data=all_ax, model=(), mesh=mesh)
        pspecs = tfm.param_specs(cfg, rules)
        params_sds = jax.eval_shape(
            partial(tfm.init_params, cfg), jax.random.key(0)
        )
        params_ns = _tree_ns(mesh, pspecs)
        meta["params"] = int(cfg.param_count())
        meta["active_params"] = int(cfg.active_param_count())

        if kind == "lm_train":
            optim = adamw(lr=3e-4)
            opt_sds = jax.eval_shape(optim.init, params_sds)
            mspecs = zero1_specs(pspecs, params_sds, rules.data, mesh_shape)
            opt_ns = type(opt_sds)(
                mu=_tree_ns(mesh, mspecs.mu),
                nu=_tree_ns(mesh, mspecs.nu),
                count=_ns(mesh, P()),
            )
            # §Perf iteration 4: smaller microbatches bound the per-layer
            # activation working set (temp memory halves; same math).
            # §Perf iteration 7: bf16 gradient accumulation halves both
            # the accumulator memory and the grad all-reduce bytes.
            n_micro = 8 if opt else 4
            accum = jnp.bfloat16 if opt else jnp.float32
            step = tl.make_lm_train_step(cfg, optim, rules,
                                         n_microbatches=n_micro,
                                         accum_dtype=accum)
            meta["n_microbatches"] = n_micro
            meta["tokens_per_step"] = shape.global_batch * shape.seq_len
            batch_ns = _batch_sharding(mesh, batch_sds, "lm", cfg, rules)
            return (step, (params_sds, opt_sds, batch_sds),
                    (params_ns, opt_ns, batch_ns), meta)

        if kind == "lm_prefill":
            step = tl.make_lm_prefill_step(cfg, rules, max_len=shape.seq_len)
            batch_ns = _batch_sharding(mesh, batch_sds, "lm", cfg)
            return (step, (params_sds, batch_sds["tokens"]),
                    (params_ns, batch_ns["tokens"]), meta)

        # decode
        b = shape.global_batch
        t = shape.seq_len
        cache_sds = jax.eval_shape(
            partial(tfm.init_kv_cache, cfg, b, t)
        )
        dp = rules.dp
        tp = rules.tp
        data_extent = int(np.prod([mesh_shape[a] for a in rules.data])) if rules.data else 1
        if b >= data_extent:
            kv_spec = {"k": P(None, dp, tp, None, None),
                       "v": P(None, dp, tp, None, None),
                       "pos": P(None, dp, None)}
            tok_spec = P(dp)
        else:  # long-context single stream: shard the sequence everywhere
            seq_ax = tuple(rules.data) + tuple(rules.model)
            kv_spec = {"k": P(None, None, seq_ax, None, None),
                       "v": P(None, None, seq_ax, None, None),
                       "pos": P(None, None, seq_ax)}
            tok_spec = P()
        cache_ns = {
            key: {kk: _ns(mesh, kv_spec[kk]) for kk in ("k", "v", "pos")}
            for key in cache_sds
        }
        step = tl.make_lm_decode_step(cfg, rules)
        pos_sds = jax.ShapeDtypeStruct((), I32)
        return (
            step,
            (params_sds, batch_sds["token"], pos_sds, cache_sds),
            (params_ns, _ns(mesh, tok_spec), _ns(mesh, P()), cache_ns),
            meta,
        )

    if arch.family == "gnn":
        import importlib

        mod = importlib.import_module(
            {
                "mace": "repro.models.gnn.mace",
                "pna": "repro.models.gnn.pna",
                "gin-tu": "repro.models.gnn.gin",
                "gat-cora": "repro.models.gnn.gat",
            }[arch_id]
        )
        batch_sds = _pad_gnn_batch(batch_sds, mesh)
        if opt:
            # §Perf iteration 6c: node-sharded aggregation — segment
            # reductions constrain their [N, ...] outputs to the full mesh
            # so the combine becomes reduce-scatter, not a replicated
            # accumulator + all-reduce (the measured GNN bottleneck).
            from ..models.gnn.common import set_node_spec

            set_node_spec(tuple(mesh.axis_names))
        if opt and arch_id == "gat-cora" and shape_id in ("ogb_products",
                                                          "minibatch_lg"):
            # §Perf iteration 6 — the PAPER's technique on the GNN gather:
            # statically split edges into a hot stream (src in the top-C
            # highest-degree nodes, features replicated = the degree-score
            # cache) and a cold stream (cross-shard gather). Hot share
            # measured on the power-law stand-in: C = 2.7%% of n absorbs
            # 35%% of edge-src gathers (see EXPERIMENTS.md).
            ndev = mesh.devices.size
            e_tot = batch_sds["edge_src"].shape[0]
            hub_c = 65536
            e_hot = _pad_to(int(e_tot * 0.35), ndev)
            e_cold = _pad_to(e_tot - e_hot, ndev)
            i32 = batch_sds["edge_src"].dtype
            for key in ("edge_src", "edge_dst", "edge_mask"):
                del batch_sds[key]
            batch_sds["edge_src_cold"] = jax.ShapeDtypeStruct((e_cold,), i32)
            batch_sds["edge_src_hub_pos"] = jax.ShapeDtypeStruct((e_hot,), i32)
            batch_sds["hub_ids"] = jax.ShapeDtypeStruct((hub_c,), i32)
            batch_sds["edge_dst_cold"] = jax.ShapeDtypeStruct((e_cold,), i32)
            batch_sds["edge_dst_hot"] = jax.ShapeDtypeStruct((e_hot,), i32)
            batch_sds["edge_mask_cold"] = jax.ShapeDtypeStruct(
                (e_cold,), jnp.bool_)
            batch_sds["edge_mask_hot"] = jax.ShapeDtypeStruct(
                (e_hot,), jnp.bool_)
            meta["hub_split"] = {"C": hub_c, "hot_share": 0.35}
        params_sds = jax.eval_shape(
            partial(mod.init_params, cfg), jax.random.key(0)
        )
        params_ns = jax.tree.map(lambda _: _ns(mesh, P()), params_sds)
        optz = adamw(lr=1e-3, weight_decay=0.0)
        opt_sds = jax.eval_shape(optz.init, params_sds)
        opt_ns = jax.tree.map(lambda _: _ns(mesh, P()), opt_sds)
        step = tl.make_gnn_train_step(mod.apply, cfg, optz)
        batch_ns = _batch_sharding(mesh, batch_sds, "gnn", cfg)
        if opt:
            # §Perf iteration 2 (GNN): feature-dimension sharding of the
            # node table — gathers by edge index then move NO rows across
            # devices (each device gathers its own feature columns); only
            # the small post-projection [N, H, D] partials cross the mesh.
            # (iteration 1 — node rows over all axes — was refuted: the
            # cross-shard row gather got slightly WORSE, 0.404 -> 0.423 s.)
            data_ax = tuple(a for a in mesh.axis_names if a != "model")
            if "node_feat" in batch_sds and batch_sds["node_feat"].ndim == 2:
                v = batch_sds["node_feat"]
                batch_ns["node_feat"] = _ns(
                    mesh, _fit_spec(mesh, P(data_ax, "model"), v.shape)
                )
        n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_sds))
        meta["params"] = n_par
        return (step, (params_sds, opt_sds, batch_sds),
                (params_ns, opt_ns, batch_ns), meta)

    if arch.family == "recsys":
        from ..models.recsys import din as din_mod

        # pad the candidate axis to a device-count multiple (masked padding)
        ndev = mesh.devices.size
        for key in ("cand_items", "cand_cats"):
            if key in batch_sds:
                v = batch_sds[key]
                batch_sds[key] = jax.ShapeDtypeStruct(
                    (_pad_to(v.shape[0], ndev),), v.dtype
                )
        params_sds = jax.eval_shape(
            partial(din_mod.init_params, cfg), jax.random.key(0)
        )
        tp = tuple(a for a in mesh.axis_names if a == "model")
        pspecs = jax.tree.map(lambda _: P(), params_sds)
        pspecs["item_table"] = P(tp, None)
        pspecs["cat_table"] = P(tp, None)
        params_ns = _tree_ns(mesh, pspecs)
        batch_ns = _batch_sharding(mesh, batch_sds, "recsys", cfg)
        n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_sds))
        meta["params"] = n_par
        if kind == "recsys_train":
            optim = adamw(lr=1e-3, weight_decay=0.0)
            opt_sds = jax.eval_shape(optim.init, params_sds)
            mspecs = type(opt_sds)(mu=pspecs, nu=pspecs, count=P())
            opt_ns = type(opt_sds)(
                mu=_tree_ns(mesh, mspecs.mu),
                nu=_tree_ns(mesh, mspecs.nu),
                count=_ns(mesh, P()),
            )
            step = tl.make_recsys_train_step(din_mod.apply, cfg, optim)
            return (step, (params_sds, opt_sds, batch_sds),
                    (params_ns, opt_ns, batch_ns), meta)
        if kind == "recsys_serve":
            step = tl.make_recsys_serve_step(din_mod.apply, cfg)
            return (step, (params_sds, batch_sds), (params_ns, batch_ns), meta)
        step = tl.make_retrieval_step(din_mod.retrieval_score, cfg, top_k=100)
        return (step, (params_sds, batch_sds), (params_ns, batch_ns), meta)

    if arch.family == "graph-analytics":
        return _setup_lcc(cfg, mesh, meta)
    raise ValueError(arch.family)


def _setup_lcc(cfg, mesh: Mesh, meta):
    """The paper's own engine on a flattened mesh (extra, non-assigned),
    with every row in one degree class of width ``row_width``."""
    from ..core.async_engine import lcc_program

    p = int(mesh.devices.size)
    flat = Mesh(mesh.devices.reshape(p), ("dev",))
    n = cfg.n_vertices
    n_loc = -(-n // p)
    w = cfg.row_width
    e_max = _pad_to(n_loc * cfg.avg_degree, cfg.n_rounds)
    e_chunk = e_max // cfg.n_rounds
    s_max = max(e_chunk // max(p - 1, 1), 8)
    fn = lcc_program(((w, w, 0, e_chunk),), flat, sentinel=n,
                     method="bsearch")
    c = cfg.cache_rows
    sds = (
        jax.ShapeDtypeStruct((p, n_loc + 1, w), I32),
        jax.ShapeDtypeStruct((p, n_loc), I32),
        jax.ShapeDtypeStruct((p, cfg.n_rounds, e_chunk), I32),
        jax.ShapeDtypeStruct((p, cfg.n_rounds, e_chunk), I32),
        jax.ShapeDtypeStruct((p, cfg.n_rounds, e_chunk), I32),
        jax.ShapeDtypeStruct((p, cfg.n_rounds, p, s_max), I32),
        jax.ShapeDtypeStruct((c, w), I32),
    )
    shards = tuple(
        NamedSharding(flat, P("dev"))
        for _ in range(6)
    ) + (NamedSharding(flat, P()),)
    meta["note"] = "paper LCC engine; flat 1D mesh over all chips"
    return fn, sds, shards, meta


# --------------------------------------------------------------------------
# run one cell
# --------------------------------------------------------------------------
def run_cell(arch_id: str, shape_id: str, mesh_kind: str,
             *, opt: bool = False, keep_hlo: bool = False) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    t0 = time.time()
    out = {"arch": arch_id, "shape": shape_id, "mesh": mesh_kind,
           "mesh_shape": list(mesh.devices.shape), "ok": False}
    try:
        fn, args, shardings, meta = setup_cell(arch_id, shape_id, mesh,
                                               opt=opt)
        out.update(meta)
        # donate what a real deployment donates: params/opt state for train
        # steps, the KV cache for decode (memory_analysis double-counts
        # in/out buffers otherwise).
        kind = meta.get("kind", "")
        if kind.endswith("_train") or kind == "gnn_train":
            donate = (0, 1)
        elif kind == "lm_decode":
            donate = (3,)
        else:
            donate = ()
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_comp = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        census = collective_census(hlo)
        out.update(
            ok=True,
            lower_s=round(t_lower - t0, 2),
            compile_s=round(t_comp - t_lower, 2),
            memory={
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
            },
            cost={
                "flops": float(cost.get("flops", 0.0)),
                "transcendentals": float(cost.get("transcendentals", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            },
            collectives=census,
            hlo_bytes=len(hlo),
        )
        if keep_hlo:
            out["hlo_text"] = hlo[:2_000_000]
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-4000:]
    out["total_s"] = round(time.time() - t0, 2)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-lcc", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--opt", action="store_true",
                    help="apply the §Perf beyond-baseline configuration")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print cell ids (for per-cell subprocess sweeps)")
    args = ap.parse_args(argv)

    if args.list:
        for aid, sid in cells():
            print(f"{aid} {sid}")
        if args.include_lcc:
            print("paper-lcc default")
        return 0

    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = []
    if args.all:
        for aid, sid in cells():
            todo += [(aid, sid, m) for m in meshes]
        if args.include_lcc:
            todo += [("paper-lcc", "default", m) for m in meshes]
    else:
        assert args.arch and args.shape
        todo = [(args.arch, args.shape, m) for m in meshes]

    for aid, sid, m in todo:
        tag = f"{aid}__{sid}__{m}".replace("/", "_").replace(".", "_")
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path) and not args.force:
            try:
                with open(path) as f:
                    if json.load(f).get("ok"):
                        print(f"[skip] {tag}")
                        continue
            except Exception:  # noqa: BLE001 — malformed -> rerun
                pass
        print(f"[run ] {tag}", flush=True)
        res = run_cell(aid, sid, m, opt=args.opt)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        status = "OK" if res["ok"] else "FAIL " + res.get("error", "")[:200]
        print(f"[done] {tag}: {status} ({res['total_s']}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
