"""Compiled asynchronous distributed LCC engine (paper Alg. 3 + §III-A).

``shard_map`` over a device axis ``"dev"`` of size p. Each device owns a
1D partition; per round one ``all_to_all`` ships exactly the adjacency
rows the static pull schedule (``rma.build_sharded_problem``) resolved as
remote+uncached, each at its degree class's width, packed into one
buffer (``rma.class_layout``); where nothing is fetched (p=1) there is
no exchange. Round ``r+1``'s exchange is issued before round ``r``'s
compare, so the collective overlaps the intersection work — the paper's
double buffering; on TPU the XLA latency-hiding scheduler turns that
structural overlap into DMA/compute overlap. The last round is peeled
and issues no exchange.

Compute per undirected edge, once, on the owner of its lower (degree,
id) endpoint u: gather row_u (local) and row_v from the table of v's
degree class (its local and cache rows, built once, then, for a class
that is fetched, the round's fetched rows), count |row_u ∩ row_v| with
the regime-split intersection, and segment-accumulate it into both
S(u) and S(v). Where p > 1, v's share goes to a buffer of every
device's rows, and one ``psum_scatter`` closes the epoch by handing
each owner its own. LCC follows Eq. (2). Edge slots come ordered by
degree class: each class block gathers and compares its rows only as
wide as its class, not at the graph's maximum degree.
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
from .intersect import count_bsearch_jnp, count_equal_pairs_jnp, tpu_regime_rule
from .rma import ShardedLCCProblem, class_layout

__all__ = [
    "device_args",
    "lcc_mesh",
    "lcc_program",
    "lcc_pipelined",
    "make_lcc_fn",
    "run_distributed_lcc",
]


def _take_rows(table, idx):
    """``table[idx[:, 0]]`` (``idx`` is ``[N, 1]``): whole rows, which the
    TPU gathers natively (a gather of row prefixes narrower than the
    table becomes a loop of one-row copies)."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,)
    )
    return jax.lax.gather(
        table, idx, dnums, (1, table.shape[1]),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _prefixes(rows, widths):
    """``{w: rows[:, :w]}``: the row table cut to each class width."""
    return {w: jax.lax.slice_in_dim(rows, 0, w, axis=1) for w in widths}


def _shard_body(
    rows_ext,  # [n_loc+1, W]
    degrees,  # [n_loc]
    slot_u,  # [NR, T] local row of u per class-ordered slot
    slot_v,  # [NR, T] row of v in the table of its class
    slot_acc,  # [NR, T] v's accumulator index: owner * (n_loc+1) + row
    fetch_idx,  # [NR, p, sum of fetch caps] local rows to send, by class
    cache_rows,  # [C, W]
    *,
    axis: str,
    blocks: tuple,
    fetch: tuple,
    sentinel: int,
    method: str,
):
    # shard_map keeps the sharded leading axis at local size 1 — squeeze it.
    rows_ext = rows_ext[0]
    degrees = degrees[0]
    slot_u = slot_u[0]
    slot_v = slot_v[0]
    slot_acc = slot_acc[0]
    fetch_idx = fetch_idx[0]
    n_loc = rows_ext.shape[0] - 1
    n_slots = slot_u.shape[1]
    n_rounds, p = fetch_idx.shape[:2]
    base_fetch = n_loc + 1 + cache_rows.shape[0]

    def v_side(rows):
        # v rows are padded with sentinel + 1, u rows with the sentinel:
        # no padding slot ever equals another
        return jnp.where(rows < sentinel, rows, sentinel + 1)

    deg_ext = jnp.concatenate([degrees, jnp.zeros((1,), degrees.dtype)])

    def count(rows_a, rows_b, eu):
        if method == "bsearch":
            return count_bsearch_jnp(rows_a, rows_b, sentinel)
        pairs = count_equal_pairs_jnp(rows_a, rows_b)
        if method == "pairwise":
            return pairs
        # hybrid: regime select per edge (Eq. 3 analogue)
        deg_b = (rows_b < sentinel).sum(-1)
        use_pw = tpu_regime_rule(deg_ext[eu[:, 0]], deg_b, rows_b.shape[-1])
        return jnp.where(
            use_pw, pairs, count_bsearch_jnp(rows_a, rows_b, sentinel)
        )

    u_widths = {wu for wu, _, _, _ in blocks} | {wc for wc, _ in fetch}
    v_widths = sorted({wv for _, wv, _, _ in blocks})
    with jax.named_scope("lcc.gather"):
        u_tables = _prefixes(rows_ext, sorted(u_widths))
        # the local and cache rows of every v class, the same each round
        v_tables = _prefixes(
            v_side(jnp.concatenate([rows_ext, cache_rows], 0)), v_widths)

    def exchange(r):
        # the rows this device serves in round r, each at its class
        # width, packed into one buffer -> one a2a -> each class's rows
        # from every peer
        with jax.named_scope("lcc.fetch"):
            idx = jax.lax.dynamic_index_in_dim(fetch_idx, r, keepdims=False)
            packed, lo = [], 0
            for wc, sc in fetch:
                rows = _take_rows(u_tables[wc], jax.lax.slice_in_dim(
                    idx, lo, lo + sc, axis=1).reshape(p * sc, 1))
                packed.append(rows.reshape(p, sc * wc))
                lo += sc
            got = jax.lax.all_to_all(jnp.concatenate(packed, 1), axis,
                                     split_axis=0, concat_axis=0, tiled=False)
            out, lo = {}, 0
            for wc, sc in fetch:
                out[wc] = v_side(got[:, lo:lo + sc * wc].reshape(p * sc, wc))
                lo += sc * wc
            return out

    def hold(fetched, rows):
        # a round's fetched rows go after their class's local and cache rows
        with jax.named_scope("lcc.fetch"):
            return {wc: jax.lax.dynamic_update_slice_in_dim(
                fetched[wc], rows[wc], base_fetch, 0) for wc in fetched}

    # the accumulator holds every device's rows, (owner, local row), and
    # at p > 1 is summed onto their owners at the end
    u_base = jax.lax.axis_index(axis) * (n_loc + 1) if p > 1 else 0

    def compare(r, tables, acc):
        with jax.named_scope("lcc.gather"):
            su = jax.lax.dynamic_index_in_dim(slot_u, r, keepdims=False)
            su_col = su.reshape(n_slots, 1)
            sv_col = jax.lax.dynamic_index_in_dim(slot_v, r).reshape(n_slots, 1)
            sa = jax.lax.dynamic_index_in_dim(slot_acc, r, keepdims=False)
        counts = []
        # one static block per degree-class pair: each slot's rows are
        # gathered and compared only as wide as its class
        for wu, wv, lo, hi in blocks:
            with jax.named_scope("lcc.gather"):
                eu = jax.lax.slice_in_dim(su_col, lo, hi)
                rows_a = _take_rows(u_tables[wu], eu)
                rows_b = _take_rows(
                    tables[wv], jax.lax.slice_in_dim(sv_col, lo, hi))
            with jax.named_scope("lcc.count"):
                counts.append(count(rows_a, rows_b, eu))
        with jax.named_scope("lcc.accumulate"):
            # each edge is compared once: its count goes to u here and to
            # v at its owner's row; empty slots add 0 at a phantom row
            c = jnp.concatenate(counts)
            return acc.at[su + u_base].add(c).at[sa].add(c)

    acc = jnp.zeros((p * (n_loc + 1),), jnp.int32)
    if not fetch:  # nothing is fetched (p=1): no exchange
        acc = jax.lax.fori_loop(
            0, n_rounds, lambda r, acc: compare(r, v_tables, acc), acc)
    else:
        # double buffering: round r+1's exchange is issued before round
        # r's compare, so the collective overlaps it; the last round is
        # peeled and issues none, n_rounds exchanges in all
        def body(r, carry):
            fetched, acc = carry
            nxt = exchange(r + 1)
            acc = compare(r, {**v_tables, **fetched}, acc)
            return hold(fetched, nxt), acc

        # a fetched class's table holds the round's rows after its local
        # and cache rows
        first = exchange(0)
        fetched = {wc: jnp.concatenate([v_tables.pop(wc), first[wc]])
                   for wc in first}
        if n_rounds > 1:
            fetched, acc = jax.lax.fori_loop(
                0, n_rounds - 1, body, (fetched, acc))
        acc = compare(n_rounds - 1, {**v_tables, **fetched}, acc)
    if p > 1:
        with jax.named_scope("lcc.accumulate"):
            # each owner gets the sum of every device's adds to its rows
            acc = jax.lax.psum_scatter(acc, axis, tiled=True)
    with jax.named_scope("lcc.finalize"):
        s = acc[:n_loc]
        t = s // 2  # undirected: each neighbor-edge seen twice in S(i)
        deg = degrees.astype(jnp.float32)
        denom = deg * (deg - 1.0)
        lcc = jnp.where(denom > 0, 2.0 * t.astype(jnp.float32) / denom, 0.0)
        return t[None], lcc[None]


def lcc_program(
    blocks: tuple,
    mesh: Mesh,
    *,
    sentinel: int,
    fetch: tuple = (),
    axis: str = "dev",
    method: str = "bsearch",
):
    """jit-compiled distributed LCC over ``mesh`` (1-D, axis name
    ``axis``) for static class blocks ``((u width, v width, first
    column, end column), ...)`` and fetched classes ``((width, rows
    per peer), ...)`` (``rma.ClassLayout``; none: no exchange); it
    takes the arrays of ``device_args``."""
    body = functools.partial(
        _shard_body,
        axis=axis,
        blocks=tuple(blocks),
        fetch=tuple(fetch),
        sentinel=sentinel,
        method=method,
    )
    sharded = P(axis)
    repl = P()
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(sharded,) * 6 + (repl,),
        out_specs=(sharded, sharded),
        check_vma=False,
    )
    return jax.jit(fn)


def make_lcc_fn(
    prob: ShardedLCCProblem,
    mesh: Mesh,
    *,
    axis: str = "dev",
    method: str = "bsearch",
):
    """The problem's epoch program: ``lcc_program`` at its degree-class
    blocks and fetched classes (``rma.class_layout``)."""
    layout = class_layout(prob)
    return lcc_program(layout.blocks, mesh, sentinel=prob.sentinel,
                       fetch=layout.fetch, axis=axis, method=method)


def lcc_mesh(p: int) -> Mesh:
    """The engine's 1-D ``("dev",)`` mesh over the first ``p`` devices."""
    devs = jax.devices()
    if len(devs) < p:
        raise RuntimeError(
            f"need {p} devices, have {len(devs)} {devs[0].platform} devices"
        )
    return Mesh(np.array(devs[:p]), ("dev",))


def device_args(prob: ShardedLCCProblem, mesh: Mesh, *, axis: str = "dev"):
    """The engine's inputs: the rows, degrees, the class-ordered slots
    (u's row, v's row, v's accumulator index) and the per-class serve
    lists of ``rma.class_layout``, each placed
    straight onto its shards (rank k's slice goes to device k), and the
    replicated cache rows, so no single device stages the whole problem. Returns
    once they are on the devices: the set-up span ``setup.place``, with
    the arrays' ``bytes``."""
    sharded = NamedSharding(mesh, P(axis))
    with obs_trace.setup_span("setup.place") as span:
        layout = class_layout(prob)
        args = tuple(
            jax.device_put(x, sharded)
            for x in (prob.rows_ext, prob.degrees, layout.slot_u,
                      layout.slot_v, layout.slot_acc, layout.fetch_idx)
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
