import numpy as np
import pytest

from repro.core.cache import build_static_degree_cache
from repro.core.csr import from_edges
from repro.core.rma import (
    ScheduleWidthOverflow,
    assert_problems_equal,
    build_sharded_problem,
    class_layout,
    class_widths,
    schedule_counts,
    simulate_rma_lcc,
)
from repro.core.partition import partition_1d
from conftest import class_edge_graph, powerlaw_graph, random_graph, star_graph


def resolve_rows(prob, k):
    """Host-side re-execution of the combined-index scheme for device k."""
    import numpy as np

    p, nr, _, s_max = prob.serve_idx.shape[0], prob.n_rounds, None, prob.s_max
    n_loc1 = prob.n_loc + 1
    w = prob.width
    out = np.zeros((prob.e_max,), np.int64)
    counts = np.full(prob.e_max, -1, np.int64)
    e_chunk = prob.e_max // nr
    for r in range(nr):
        # fetched rows for device k in round r: what each peer q serves to k
        fetched = np.full((prob.p, s_max, w), prob.sentinel, np.int32)
        for q in range(prob.p):
            idx = prob.serve_idx[q, r, k]
            fetched[q] = prob.rows_ext[q][idx]
        combined = np.concatenate(
            [prob.rows_ext[k], prob.cache_rows, fetched.reshape(-1, w)], 0
        )
        for e in range(r * e_chunk, (r + 1) * e_chunk):
            if not prob.edge_mask[k, e]:
                continue
            row_u = prob.rows_ext[k][prob.edge_u[k, e]]
            row_v = combined[prob.edge_vc[k, e]]
            a = row_u[row_u < prob.sentinel]
            b = row_v[row_v < prob.sentinel]
            counts[e] = len(np.intersect1d(a, b))
    return counts


@pytest.mark.parametrize("p,cache_rows,n_rounds", [
    (1, 0, 1), (4, 0, 2), (4, 16, 3), (8, 8, 4),
])
def test_schedule_resolves_correct_rows(p, cache_rows, n_rounds):
    """The static pull schedule must deliver exactly adj(v) for every edge."""
    csr = powerlaw_graph(96, 6, seed=4)
    cache = (
        build_static_degree_cache(csr.degrees, cache_rows)
        if cache_rows
        else None
    )
    prob = build_sharded_problem(csr, p, n_rounds=n_rounds, cache=cache)
    part = partition_1d(csr.n, p)
    from repro.core.triangles import triangles_per_vertex

    want_t = triangles_per_vertex(csr)
    for k in range(p):
        counts = resolve_rows(prob, k)
        s = np.zeros(prob.n_loc + 1, np.int64)
        np.add.at(s, prob.edge_u[k], np.where(prob.edge_mask[k], np.maximum(counts, 0), 0))
        lo, hi = part.lo(k), part.hi(k)
        got_t = s[: hi - lo] // 2
        assert np.array_equal(got_t, want_t[lo:hi]), f"device {k}"


def test_cache_reduces_comm_volume():
    csr = powerlaw_graph(128, 8, seed=1)
    p = 4
    prob0 = build_sharded_problem(csr, p, n_rounds=2)
    cache = build_static_degree_cache(csr.degrees, 24)
    prob1 = build_sharded_problem(csr, p, n_rounds=2, cache=cache)
    b0 = prob0.comm_bytes_per_round().sum()
    b1 = prob1.comm_bytes_per_round().sum()
    assert b1 < b0, "degree-cache must cut communication volume"


def test_simulate_rma_stats():
    csr = powerlaw_graph(200, 8, seed=2)
    p = 4
    st_nc = simulate_rma_lcc(csr, p)
    st_c = simulate_rma_lcc(
        csr, p, offsets_cache_bytes=800, adj_cache_bytes=4096
    )
    assert st_nc.remote_gets.sum() > 0
    # cache hits reduce modeled communication time
    assert st_c.comm_time.sum() < st_nc.comm_time.sum()
    # hit rate in a power-law graph with decent cache must be positive
    assert sum(s.hits for s in st_c.adj_stats) > 0
    # compulsory misses can't exceed total misses
    for s in st_c.adj_stats:
        assert s.compulsory_misses <= s.misses


def test_degree_score_beats_lru_on_powerlaw():
    """Fig. 8: degree-centrality victim selection beats LRU+positional."""
    csr = powerlaw_graph(400, 10, seed=3)
    p = 2
    kw = dict(adj_cache_bytes=2048, table_slots_adj=64)
    lru = simulate_rma_lcc(csr, p, use_degree_score=False, **kw)
    deg = simulate_rma_lcc(csr, p, use_degree_score=True, **kw)
    hits_lru = sum(s.hits for s in lru.adj_stats)
    hits_deg = sum(s.hits for s in deg.adj_stats)
    assert hits_deg >= hits_lru


def test_expected_remote_reads_formula():
    """Paper §III-B: E[reads of v] ~ deg^-(v) * (p-1)/p under random owners."""
    csr = powerlaw_graph(300, 8, seed=5)
    p = 4
    st = simulate_rma_lcc(csr, p)
    total_remote = st.remote_gets.sum()
    expect = csr.degrees.sum() * (p - 1) / p
    assert abs(total_remote - expect) / expect < 0.25


# ---------------------------------------------------------------------------
# incremental pull-schedule maintenance (apply_delta)
# ---------------------------------------------------------------------------
def _edge_set(csr):
    src, dst = csr.edge_list()
    keep = src < dst
    return set(map(tuple, np.stack([src[keep], dst[keep]], 1).tolist()))


def _random_effective_delta(rng, edges, n, n_ins, n_del):
    """(ins, dele) honoring the streaming contract: inserts absent,
    deletes present, canonical u < v."""
    ins = []
    while len(ins) < n_ins:
        a, b = (int(x) for x in rng.integers(0, n, 2))
        if a == b:
            continue
        e = (min(a, b), max(a, b))
        if e not in edges and e not in ins:
            ins.append(e)
    pool = sorted(edges)
    pick = rng.choice(len(pool), size=min(n_del, len(pool)), replace=False)
    dele = [pool[i] for i in pick]
    return np.array(ins, np.int64), np.array(dele, np.int64)


@pytest.mark.parametrize("seed,p,cache_rows,dedup", [
    (0, 1, 0, True), (1, 4, 0, True), (2, 4, 12, True),
    (3, 8, 8, True), (4, 3, 6, False),
])
def test_apply_delta_matches_scratch_build(seed, p, cache_rows, dedup):
    """Property: after ANY sequence of effective insert/delete batches,
    the patched problem is field-for-field bit-exact vs a from-scratch
    build of the mutated graph — serve lists, edge worklists, padded
    rows — and resolving the patched schedule yields the exact new
    per-vertex triangle counts."""
    rng = np.random.default_rng(seed)
    n = 90 + 12 * seed
    csr = powerlaw_graph(n, 5, seed=seed)
    cache = (
        build_static_degree_cache(csr.degrees, cache_rows)
        if cache_rows
        else None
    )
    width = csr.max_degree + 8  # headroom for inserts
    prob = build_sharded_problem(
        csr, p, n_rounds=3, cache=cache, width=width, dedup_rounds=dedup
    )
    edges = _edge_set(csr)
    for _ in range(3):
        ins, dele = _random_effective_delta(rng, edges, n, 10, 6)
        edges.difference_update(map(tuple, dele.tolist()))
        edges.update(map(tuple, ins.tolist()))
        prob.apply_delta(ins, dele)
        csr2 = from_edges(np.array(sorted(edges), np.int64), n)
        fresh = build_sharded_problem(
            csr2, p, n_rounds=3, cache=cache, width=width,
            dedup_rounds=dedup,
        )
        assert_problems_equal(prob, fresh)
    # the maintained schedule still resolves to exact triangle counts
    from repro.core.triangles import triangles_per_vertex

    csr2 = from_edges(np.array(sorted(edges), np.int64), n)
    want_t = triangles_per_vertex(csr2)
    part = partition_1d(n, p)
    for k in range(p):
        counts = resolve_rows(prob, k)
        s = np.zeros(prob.n_loc + 1, np.int64)
        np.add.at(s, prob.edge_u[k],
                  np.where(prob.edge_mask[k], np.maximum(counts, 0), 0))
        lo, hi = part.lo(k), part.hi(k)
        assert np.array_equal(s[: hi - lo] // 2, want_t[lo:hi])


def test_apply_delta_width_overflow_raises_before_mutating():
    csr = powerlaw_graph(60, 6, seed=9)
    prob = build_sharded_problem(csr, 4, n_rounds=2)  # width == max degree
    hub = int(np.argmax(csr.degrees))
    absent = next(
        (hub, v) if hub < v else (v, hub)
        for v in range(csr.n)
        if v != hub and v not in set(csr.row(hub).tolist())
    )
    snap = {f: getattr(prob, f).copy()
            for f in ("rows_ext", "degrees", "edge_u", "edge_vc",
                      "serve_idx")}
    with pytest.raises(ScheduleWidthOverflow):
        prob.apply_delta(np.array([absent], np.int64),
                         np.zeros((0, 2), np.int64))
    for f, v in snap.items():  # overflow must leave the problem untouched
        assert np.array_equal(getattr(prob, f), v), f


def test_apply_delta_empty_batch_is_noop():
    csr = powerlaw_graph(40, 4, seed=3)
    prob = build_sharded_problem(csr, 2, n_rounds=2)
    before = prob.edge_vc.copy()
    prob.apply_delta(np.zeros((0, 2), np.int64), np.zeros((0, 2), np.int64))
    assert np.array_equal(prob.edge_vc, before)


def test_apply_delta_invalid_batch_leaves_problem_untouched():
    """A contract-violating batch (double-applied delta) must raise and
    leave every field bit-identical — a failed patch is retryable."""
    csr = powerlaw_graph(50, 5, seed=11)
    prob = build_sharded_problem(csr, 4, n_rounds=2,
                                 width=csr.max_degree + 4)
    edges = _edge_set(csr)
    rng = np.random.default_rng(12)
    ins, dele = _random_effective_delta(rng, edges, csr.n, 6, 4)
    prob.apply_delta(ins, dele)
    snap = {f: getattr(prob, f).copy()
            for f in ("rows_ext", "degrees", "edge_u", "edge_vc",
                      "edge_mask", "serve_idx")}
    works_snap = [(u.copy(), v.copy()) for u, v in prob.works]
    with pytest.raises(ValueError):
        prob.apply_delta(ins, dele)  # inserts now present: breach
    with pytest.raises(ValueError):
        prob.apply_delta(np.zeros((0, 2), np.int64), dele)  # already gone
    for f, v in snap.items():
        assert np.array_equal(getattr(prob, f), v), f
    for (u0, v0), (u1, v1) in zip(works_snap, prob.works):
        assert np.array_equal(u0, u1) and np.array_equal(v0, v1)
    # and the problem is still maintainable afterwards
    edges.difference_update(map(tuple, dele.tolist()))
    edges.update(map(tuple, ins.tolist()))
    ins2, dele2 = _random_effective_delta(rng, edges, csr.n, 5, 3)
    edges.difference_update(map(tuple, dele2.tolist()))
    edges.update(map(tuple, ins2.tolist()))
    prob.apply_delta(ins2, dele2)
    fresh = build_sharded_problem(
        from_edges(np.array(sorted(edges), np.int64), csr.n), 4,
        n_rounds=2, width=prob.width,
    )
    assert_problems_equal(prob, fresh)


@pytest.mark.parametrize("seed,p", [(5, 1), (6, 4), (7, 8)])
def test_apply_delta_residency_drift_matches_scratch_build(seed, p):
    """Property: interleaving effective update batches with STATIC
    RESIDENCY DRIFT — each batch re-scores the top-C from the current
    degrees and hands the drifted set to ``apply_delta`` — keeps the
    patched problem field-for-field bit-exact vs a from-scratch build
    with that same residency, without ever rebuilding (the PR-3
    follow-up: drift alone must not force a full schedule rebuild)."""
    rng = np.random.default_rng(seed)
    n = 80 + 10 * seed
    csr = powerlaw_graph(n, 5, seed=seed)
    cache_rows = 10
    cache = build_static_degree_cache(csr.degrees, cache_rows)
    width = csr.max_degree + 10
    prob = build_sharded_problem(
        csr, p, n_rounds=3, cache=cache, width=width
    )
    edges = _edge_set(csr)
    degrees = csr.degrees.copy()
    for _ in range(3):
        ins, dele = _random_effective_delta(rng, edges, n, 12, 8)
        edges.difference_update(map(tuple, dele.tolist()))
        edges.update(map(tuple, ins.tolist()))
        for a, b in ins:
            degrees[a] += 1
            degrees[b] += 1
        for a, b in dele:
            degrees[a] -= 1
            degrees[b] -= 1
        drifted = build_static_degree_cache(degrees, cache_rows)
        prob.apply_delta(ins, dele, new_cache_ids=drifted.vertex_ids)
        csr2 = from_edges(np.array(sorted(edges), np.int64), n)
        assert np.array_equal(degrees, csr2.degrees)  # bookkeeping sane
        fresh = build_sharded_problem(
            csr2, p, n_rounds=3, cache=drifted, width=width
        )
        assert_problems_equal(prob, fresh)
    # a pure residency refresh (no edges) also patches in place
    flipped = build_static_degree_cache(-degrees.astype(np.float64) - 1,
                                        cache_rows)
    z = np.zeros((0, 2), np.int64)
    prob.apply_delta(z, z, new_cache_ids=flipped.vertex_ids)
    csr2 = from_edges(np.array(sorted(edges), np.int64), n)
    fresh = build_sharded_problem(
        csr2, p, n_rounds=3, cache=flipped, width=width
    )
    assert_problems_equal(prob, fresh)


def test_maintain_schedule_refreshes_residency_without_rebuild():
    """Runtime wiring: a drifted residency set flows through
    ``maintain_schedule(new_cache_ids=...)`` as an incremental patch
    (returns True, bumps the refresh counter, no rebuild)."""
    from repro.core.runtime import ShardedRuntime
    from repro.streaming import DynamicCSR

    csr = powerlaw_graph(70, 5, seed=21)
    store = DynamicCSR.from_csr(csr)
    rt = ShardedRuntime(store, 4)
    cache = build_static_degree_cache(csr.degrees, 8)
    rt.attach_problem(build_sharded_problem(
        csr, 4, cache=cache, width=csr.max_degree + 6
    ))
    z = np.zeros((0, 2), np.int64)
    # drift only: rotate the residency set
    new_ids = np.sort(
        np.concatenate([cache.vertex_ids[2:],
                        np.setdiff1d(np.arange(csr.n),
                                     cache.vertex_ids)[:2]])
    )
    assert rt.maintain_schedule(z, z, new_cache_ids=new_ids) is True
    assert rt.schedule_rebuilds == 0
    assert rt.schedule_residency_refreshes == 1
    assert np.array_equal(rt.problem.cache_ids, new_ids)
    # unchanged set does not count as a refresh
    assert rt.maintain_schedule(z, z, new_cache_ids=new_ids) is True
    assert rt.schedule_residency_refreshes == 1


# --------------------------------------------------------------------------
# Degree-class layout of the epoch program.
# --------------------------------------------------------------------------
def _combined_ids(prob, k, r):
    """The vertex of each of device k's combined rows in round r (as
    ``edge_vc`` indexes them): local, cache, fetched."""
    part = prob.part
    fetched = [part.lo(q) + prob.serve_idx[q, r, k] for q in range(prob.p)]
    return np.concatenate([part.lo(k) + np.arange(prob.n_loc + 1),
                           prob.cache_ids, *fetched])


def _class_ids(prob, lay, k, r, wv):
    """The vertex of each row of device k's table of v class ``wv`` in
    round r: local, cache, then (a fetched class) what each peer serves
    k at that class (real slots index only real rows)."""
    part = prob.part
    ids = [part.lo(k) + np.arange(prob.n_loc + 1), prob.cache_ids]
    lo = 0
    for wc, sc in lay.fetch:
        if wc == wv:
            ids += [part.lo(q) + lay.fetch_idx[q, r, k, lo:lo + sc]
                    for q in range(prob.p)]
        lo += sc
    return np.concatenate(ids)


def _row_of(prob, vertex):
    """The padded row of a global vertex, from its owner."""
    q = int(prob.part.owner(vertex))
    return prob.rows_ext[q][vertex - prob.part.lo(q)]


def test_class_widths_ladder():
    assert class_widths(5977).tolist() == [128, 256, 512, 1024, 2048, 4096,
                                           5977]
    assert class_widths(4096).tolist() == [128, 256, 512, 1024, 2048, 4096]
    assert class_widths(129).tolist() == [128, 129]
    assert class_widths(128).tolist() == [128]
    assert class_widths(37).tolist() == [37]


GRAPHS = {
    "class_edges": class_edge_graph,
    "star": star_graph,
    "powerlaw": lambda: powerlaw_graph(96, 6, seed=4),
}


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("p,cache_rows,n_rounds", [
    (1, 0, 3), (4, 0, 2), (4, 16, 3),
])
def test_class_layout_places_every_real_slot_once(graph, p, cache_rows,
                                                  n_rounds):
    """Every real edge slot sits in exactly one block, as wide as both
    its rows or wider (the narrowest class that holds each), a fetched
    slot in the round that fetches its row; resolving the slots on the
    host at their blocks' widths gives the exact triangle counts; the
    evaluated compares are what ``schedule_counts`` reports."""
    from repro.core.triangles import triangles_per_vertex

    csr = GRAPHS[graph]()
    cache = (build_static_degree_cache(csr.degrees, cache_rows)
             if cache_rows else None)
    prob = build_sharded_problem(csr, p, n_rounds=n_rounds, cache=cache)
    lay = class_layout(prob)
    ladder = class_widths(prob.width)
    assert max(max(w) for w in lay.widths) <= prob.width
    assert all(wu in ladder and wv in ladder for wu, wv in lay.widths)
    assert len(set(lay.widths)) == len(lay.widths)
    counts = schedule_counts(prob, csr.degrees)
    assert counts["class_blocks"] == len(lay.caps)
    assert counts["padded_compares"] == lay.compares
    assert lay.compares <= prob.e_max * prob.width ** 2
    assert lay.slot_u.shape == (p, prob.n_rounds, sum(lay.caps))

    sent, e_chunk = prob.sentinel, prob.e_max // prob.n_rounds
    base_fetch = prob.n_loc + 1 + prob.cache_rows.shape[0]
    part = partition_1d(csr.n, p)
    want_t = triangles_per_vertex(csr)
    for k in range(p):
        real = np.flatnonzero(prob.edge_mask[k])
        want = [(u, int(_combined_ids(prob, k, r)[vc]),
                 r if vc >= base_fetch else -1)
                for u, vc, r in zip(prob.edge_u[k, real].tolist(),
                                    prob.edge_vc[k, real].tolist(),
                                    (real // e_chunk).tolist())]
        got = []
        s = np.zeros(prob.n_loc + 1, np.int64)
        for r in range(prob.n_rounds):
            for wu, wv, lo, hi in lay.blocks:
                ids = _class_ids(prob, lay, k, r, wv)
                for u, vc in zip(lay.slot_u[k, r, lo:hi],
                                 lay.slot_v[k, r, lo:hi]):
                    if u == prob.n_loc:  # an empty slot
                        assert vc == prob.n_loc
                        continue
                    got.append((int(u), int(ids[vc]),
                                r if vc >= base_fetch else -1))
                    row_u = prob.rows_ext[k][u]
                    row_v = _row_of(prob, int(ids[vc]))
                    du, dv = (row_u < sent).sum(), (row_v < sent).sum()
                    assert du <= wu and dv <= wv
                    # the narrowest class that holds the row
                    assert wu == ladder[np.searchsorted(ladder, du)]
                    assert wv == ladder[np.searchsorted(ladder, dv)]
                    a, b = row_u[:wu], row_v[:wv]
                    s[u] += np.intersect1d(a[a < sent], b[b < sent]).size
        # each real slot once, reading v's row; a fetched one in its own
        # round, any other in any round
        assert sorted(got) == sorted(want)
        lo, hi = part.lo(k), part.hi(k)
        assert np.array_equal(s[: hi - lo] // 2, want_t[lo:hi])


def test_class_layout_of_a_regular_graph_is_one_padded_block():
    """Degrees all in one class: one block as wide as the rows, the
    padded all-pairs compare of every slot."""
    n, k = 400, 90  # circulant graph, every degree 180
    ring = np.arange(n)
    edges = np.concatenate([np.stack([ring, (ring + d) % n], 1)
                            for d in range(1, k + 1)])
    prob = build_sharded_problem(from_edges(edges, n), 1, n_rounds=4)
    lay = class_layout(prob)
    assert prob.width == 2 * k
    assert lay.widths == ((2 * k, 2 * k),)
    assert lay.compares == prob.e_max * prob.width ** 2


def test_class_layout_follows_changes_made_in_place():
    """The layout kept on the problem is made anew once an array it came
    from changes in place."""
    csr = class_edge_graph(seed=1)
    prob = build_sharded_problem(csr, 1, n_rounds=2)
    full = class_layout(prob)
    assert class_layout(prob) is full
    prob.edge_mask[:, prob.e_max // 2:] = False
    half = class_layout(prob)
    assert half is not full
    real = (half.slot_u != prob.n_loc).sum()
    assert real == prob.edge_mask.sum() < (full.slot_u != prob.n_loc).sum()


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("p,cache_rows,n_rounds", [
    (1, 0, 3), (4, 0, 2), (4, 16, 3),
])
def test_class_layout_serves_each_fetched_row_once_at_its_class(
        graph, p, cache_rows, n_rounds):
    """The per-class serve lists hold every row of the serve lists once,
    in the list of the class of its degree, within that class's cap; a
    class is fetched only where some row of it is served."""
    csr = GRAPHS[graph]()
    cache = (build_static_degree_cache(csr.degrees, cache_rows)
             if cache_rows else None)
    prob = build_sharded_problem(csr, p, n_rounds=n_rounds, cache=cache)
    lay = class_layout(prob)
    ladder = class_widths(prob.width)
    caps = [c for _, c in lay.fetch]
    assert all(c > 0 for c in caps)
    assert lay.fetch_idx.shape == (p, prob.n_rounds, p, sum(caps))
    assert lay.exchange_ids == sum(w * c for w, c in lay.fetch)
    seen = set()
    for q in range(p):
        for r in range(prob.n_rounds):
            for k in range(p):
                want = prob.serve_idx[q, r, k]
                want = sorted(want[want < prob.n_loc].tolist())
                got, lo = [], 0
                for wc, sc in lay.fetch:
                    rows = lay.fetch_idx[q, r, k, lo:lo + sc]
                    rows = rows[rows < prob.n_loc]
                    deg = prob.degrees[q, rows]
                    assert np.all(ladder[np.searchsorted(ladder, deg)] == wc)
                    if rows.size:
                        seen.add(wc)
                    got += rows.tolist()
                    lo += sc
                assert sorted(got) == want
    assert seen == {wc for wc, _ in lay.fetch}
    if p == 1:
        assert lay.fetch == ()


def test_schedule_counts_exchange_matches_the_edge_list():
    """The exchange counts of ``schedule_counts`` equal those taken
    straight from the edge list: the busiest device's remote and cached
    reads, the rows it receives and the real ids it ships per round, and
    the wire ids every device ships each peer (each class's longest
    list at the class's width)."""
    csr = powerlaw_graph(96, 6, seed=4)
    p, nr = 4, 3
    cache = build_static_degree_cache(csr.degrees, 8)
    prob = build_sharded_problem(csr, p, n_rounds=nr, cache=cache)
    got = schedule_counts(prob, csr.degrees)

    part = partition_1d(csr.n, p)
    deg = csr.degrees.astype(np.int64)
    ladder = class_widths(int(deg.max()))
    cached = set(cache.vertex_ids.tolist())
    owned = [np.flatnonzero(part.owner(np.arange(csr.n)) == k)
             for k in range(p)]
    edges = [[(u, int(v)) for u in owned[k]
              for v in csr.adjacencies[csr.offsets[u]:csr.offsets[u + 1]]]
             for k in range(p)]
    chunk = -(-max(len(e) for e in edges) // nr)
    # want[j][r][q]: the rows device j fetches from q in round r
    want = [[[set() for _ in range(p)] for _ in range(nr)] for _ in range(p)]
    for j in range(p):
        for i, (u, v) in enumerate(edges[j]):
            q = int(part.owner(v))
            if q != j and v not in cached:
                want[j][i // chunk][q].add(v)
    k = max(range(p), key=lambda j: len(edges[j]))
    remote = [v for u, v in edges[k] if part.owner(v) != k]
    cap = {}
    for j in range(p):
        for r in range(nr):
            for q in range(p):
                for wc in ladder:
                    n_c = sum(ladder[np.searchsorted(ladder, deg[v])] == wc
                              for v in want[j][r][q])
                    cap[wc] = max(cap.get(wc, 0), n_c)
    assert got["remote_reads"] == len(remote)
    assert got["cache_reads"] == sum(v in cached for v in remote)
    assert got["fetched_rows"] == sum(len(s) for r in want[k] for s in r)
    assert got["payload_bytes"] == 4 * sum(
        deg[v] for j in range(p) for r in range(nr) for v in want[j][r][k])
    assert got["wire_bytes"] == 4 * nr * (p - 1) * sum(
        wc * c for wc, c in cap.items())
    assert got["wire_bytes"] > got["payload_bytes"] > 0
