"""Compiled asynchronous distributed LCC engine (paper Alg. 3 + §III-A).

``shard_map`` over a device axis ``"dev"`` of size p. Each device owns a
1D partition; per round one ``all_to_all`` ships exactly the adjacency
rows the static pull schedule (``rma.build_sharded_problem``) resolved as
remote+uncached. The ``lax.fori_loop`` carries next-round rows so round
``r``'s intersection overlaps round ``r+1``'s fetch — the paper's double
buffering; on TPU the XLA latency-hiding scheduler turns that structural
overlap into DMA/compute overlap.

Compute per edge: gather row_u (local) and row_v (local | cache | fetch
buffer — one combined gather), count |row_u ∩ row_v| with the regime-split
intersection, and segment-accumulate into S(u). LCC follows Eq. (2).
The stages carry the named scopes ``lcc.fetch``, ``lcc.gather``,
``lcc.count``, ``lcc.accumulate`` and ``lcc.finalize`` in the compiled
program's op metadata, which a profiler trace shows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace as obs_trace
from .intersect import count_bsearch_jnp, count_pairwise_jnp, tpu_regime_rule
from .rma import ShardedLCCProblem

__all__ = [
    "device_args",
    "lcc_mesh",
    "lcc_pipelined",
    "make_lcc_fn",
    "run_distributed_lcc",
]


def _shard_body(
    rows_ext,  # [n_loc+1, W]
    degrees,  # [n_loc]
    edge_u,  # [E_max]
    edge_vc,  # [E_max]
    edge_mask,  # [E_max]
    serve_idx,  # [NR, p, S_max]
    cache_rows,  # [C, W]
    *,
    axis: str,
    n_rounds: int,
    e_chunk: int,
    sentinel: int,
    method: str,
):
    # shard_map keeps the sharded leading axis at local size 1 — squeeze it.
    rows_ext = rows_ext[0]
    degrees = degrees[0]
    edge_u = edge_u[0]
    edge_vc = edge_vc[0]
    edge_mask = edge_mask[0]
    serve_idx = serve_idx[0]
    n_loc_p1, w = rows_ext.shape
    n_loc = n_loc_p1 - 1
    p = jax.lax.psum(1, axis)
    s_max = serve_idx.shape[-1]

    def fetch(r):
        # rows this device serves in round r -> one a2a -> rows it needs
        with jax.named_scope("lcc.fetch"):
            to_send = rows_ext[serve_idx[r]]  # [p, S_max, W]
            got = jax.lax.all_to_all(
                to_send, axis, split_axis=0, concat_axis=0, tiled=False
            )
            return got.reshape(p * s_max, w)

    def count(rows_a, rows_b, deg_a, deg_b):
        if method == "bsearch":
            return count_bsearch_jnp(rows_a, rows_b, sentinel)
        if method == "pairwise":
            return count_pairwise_jnp(rows_a, rows_b, sentinel)
        # hybrid: regime select per edge (Eq. 3 analogue)
        use_pw = tpu_regime_rule(deg_a, deg_b, rows_b.shape[-1])
        return jnp.where(
            use_pw,
            count_pairwise_jnp(rows_a, rows_b, sentinel),
            count_bsearch_jnp(rows_a, rows_b, sentinel),
        )

    deg_ext = jnp.concatenate([degrees, jnp.zeros((1,), degrees.dtype)])

    def body(r, carry):
        fetched_cur, acc = carry
        # double buffering: issue next round's fetch before this round's
        # compute so the collective overlaps the intersection work.
        fetched_nxt = fetch(jnp.minimum(r + 1, n_rounds - 1))
        with jax.named_scope("lcc.gather"):
            combined = jnp.concatenate([rows_ext, cache_rows, fetched_cur], 0)
            eu = jax.lax.dynamic_slice(edge_u, (r * e_chunk,), (e_chunk,))
            evc = jax.lax.dynamic_slice(edge_vc, (r * e_chunk,), (e_chunk,))
            msk = jax.lax.dynamic_slice(edge_mask, (r * e_chunk,), (e_chunk,))
            rows_a = rows_ext[eu]
            rows_b = combined[evc]
            deg_a = deg_ext[eu]
        with jax.named_scope("lcc.count"):
            deg_b = (rows_b < sentinel).sum(-1)
            cnt = count(rows_a, rows_b, deg_a, deg_b)
        with jax.named_scope("lcc.accumulate"):
            acc = acc.at[eu].add(jnp.where(msk, cnt, 0))
        return fetched_nxt, acc

    acc0 = jnp.zeros((n_loc + 1,), jnp.int32)
    fetched0 = fetch(0)
    _, acc = jax.lax.fori_loop(0, n_rounds, body, (fetched0, acc0))
    with jax.named_scope("lcc.finalize"):
        s = acc[:n_loc]
        t = s // 2  # undirected: each neighbor-edge seen twice in S(i)
        deg = degrees.astype(jnp.float32)
        denom = deg * (deg - 1.0)
        lcc = jnp.where(denom > 0, 2.0 * t.astype(jnp.float32) / denom, 0.0)
        return t[None], lcc[None]


def make_lcc_fn(
    prob: ShardedLCCProblem,
    mesh: Mesh,
    *,
    axis: str = "dev",
    method: str = "bsearch",
):
    """jit-compiled distributed LCC over ``mesh`` (1-D, axis name ``axis``)."""
    e_chunk = prob.e_max // prob.n_rounds
    body = functools.partial(
        _shard_body,
        axis=axis,
        n_rounds=prob.n_rounds,
        e_chunk=e_chunk,
        sentinel=prob.sentinel,
        method=method,
    )
    sharded = P(axis)
    repl = P()
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded, sharded, sharded, repl),
        out_specs=(sharded, sharded),
        check_vma=False,
    )
    return jax.jit(fn)


def lcc_mesh(p: int) -> Mesh:
    """The engine's 1-D ``("dev",)`` mesh over the first ``p`` devices."""
    devs = jax.devices()
    if len(devs) < p:
        raise RuntimeError(
            f"need {p} devices, have {len(devs)} {devs[0].platform} devices"
        )
    return Mesh(np.array(devs[:p]), ("dev",))


def device_args(prob: ShardedLCCProblem, mesh: Mesh, *, axis: str = "dev"):
    """The engine's inputs, each placed straight onto its shards (rank k's
    slice goes to device k; the cache rows are replicated), so no single
    device stages the whole problem. Returns once they are on the
    devices: the set-up span ``setup.place``, with the arrays' ``bytes``."""
    sharded = NamedSharding(mesh, P(axis))
    with obs_trace.setup_span("setup.place") as span:
        args = tuple(
            jax.device_put(x, sharded)
            for x in (prob.rows_ext, prob.degrees, prob.edge_u, prob.edge_vc,
                      prob.edge_mask, prob.serve_idx)
        ) + (jax.device_put(prob.cache_rows, NamedSharding(mesh, P())),)
        jax.block_until_ready(args)
        span.set(bytes=sum(x.nbytes for x in args))
    return args


def lcc_pipelined(
    prob: ShardedLCCProblem,
    mesh: Optional[Mesh] = None,
    *,
    method: str = "bsearch",
):
    """Run the engine; returns (t_per_vertex [p, n_loc], lcc [p, n_loc])."""
    if mesh is None:
        mesh = lcc_mesh(prob.p)
    fn = make_lcc_fn(prob, mesh, method=method)
    t, lcc = fn(*device_args(prob, mesh))
    return np.asarray(t), np.asarray(lcc)


def run_distributed_lcc(
    csr,
    p: int,
    *,
    n_rounds: int = 4,
    cache_rows: int = 0,
    method: str = "bsearch",
    mesh: Optional[Mesh] = None,
):
    """End-to-end: partition + schedule + compiled engine -> (t, lcc) global."""
    from .cache import build_static_degree_cache
    from .rma import build_sharded_problem

    cache = (
        build_static_degree_cache(csr.degrees, cache_rows)
        if cache_rows > 0
        else None
    )
    prob = build_sharded_problem(csr, p, n_rounds=n_rounds, cache=cache)
    t, lcc = lcc_pipelined(prob, mesh, method=method)
    # unstack device-padded rows back to global vertex order
    t_g = np.zeros(csr.n, np.int64)
    lcc_g = np.zeros(csr.n, np.float64)
    from .partition import partition_1d

    part = partition_1d(csr.n, p)
    for k in range(p):
        lo, hi = part.lo(k), part.hi(k)
        t_g[lo:hi] = t[k, : hi - lo]
        lcc_g[lo:hi] = lcc[k, : hi - lo]
    return t_g, lcc_g
