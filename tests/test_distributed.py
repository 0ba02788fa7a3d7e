"""Distributed engine correctness.

In-process: p=1 (degenerate mesh). Multi-device: subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax pins device count
at first init, and the rest of the suite must see 1 device).
"""
import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import class_edge_graph, powerlaw_graph, random_graph, star_graph

TESTS = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(TESTS, "..", "src")
METHODS = ("pairwise", "bsearch", "hybrid")


def test_engine_p1_matches_reference():
    from repro.core.async_engine import run_distributed_lcc
    from repro.core.triangles import lcc_scores, triangles_per_vertex

    csr = powerlaw_graph(80, 6, seed=0)
    t, lcc = run_distributed_lcc(csr, 1, n_rounds=2)
    assert np.array_equal(t, triangles_per_vertex(csr))
    np.testing.assert_allclose(lcc, lcc_scores(csr), rtol=1e-5)


def test_engine_p1_hybrid_matches():
    from repro.core.async_engine import run_distributed_lcc
    from repro.core.triangles import triangles_per_vertex

    csr = random_graph(64, 8, seed=1)
    t, _ = run_distributed_lcc(csr, 1, n_rounds=1, method="hybrid")
    assert np.array_equal(t, triangles_per_vertex(csr))


@pytest.mark.parametrize("graph", [class_edge_graph, star_graph])
@pytest.mark.parametrize("method", METHODS)
def test_engine_p1_degree_classes_exact(method, graph):
    """Hubs at the edges of the degree classes, a maximum degree that is
    not a power of two, a star: exact for every method."""
    from repro.core.async_engine import run_distributed_lcc
    from repro.core.triangles import lcc_scores, triangles_per_vertex

    csr = graph()
    t, lcc = run_distributed_lcc(csr, 1, n_rounds=3, method=method)
    assert np.array_equal(t, triangles_per_vertex(csr))
    np.testing.assert_allclose(lcc, lcc_scores(csr), rtol=1e-5)


def exchanges(jaxpr) -> int:
    """``all_to_all`` calls a jaxpr makes, a scan's body counted once per
    iteration."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "all_to_all"
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                n += exchanges(sub) * eqn.params.get("length", 1)
    return n


COLLECTIVES = {"all_to_all", "reduce_scatter", "psum", "pmax", "pmin",
               "all_gather", "ppermute"}


def collectives(jaxpr) -> list:
    """The collective primitives a jaxpr issues, nested bodies included
    (each scan body once)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in COLLECTIVES:
            out.append(eqn.primitive.name)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                out += collectives(sub)
    return out


def hub_slots(prob, lay, hub):
    """Slots that hold the global vertex ``hub``: as u (on its owner),
    and as v (its accumulator index) on its owner and on other
    devices."""
    from repro.core.partition import partition_1d

    part = partition_1d(prob.n, prob.p)
    q = int(part.owner(hub))
    flat = q * (prob.n_loc + 1) + hub - part.lo(q)
    real = lay.slot_u != prob.n_loc
    as_v = (lay.slot_acc == flat) & real
    return {"as_u": int(((lay.slot_u[q] == hub - part.lo(q)) & real[q]).sum()),
            "as_v_local": int(as_v[q].sum()),
            "as_v_remote": int(as_v.sum() - as_v[q].sum())}


def orientation_flip(csr, p):
    """An edge {a, b} across two devices kept on a's side (the lower
    (degree, id) endpoint), and inserts that give a one more neighbour
    than b has, so the edge is kept on b's side afterwards."""
    from repro.core.partition import partition_1d

    part = partition_1d(csr.n, p)
    deg = csr.degrees
    for a in np.argsort(deg, kind="stable"):
        a = int(a)
        nbrs = set(csr.adjacencies[csr.offsets[a]:csr.offsets[a + 1]].tolist())
        for b in sorted(nbrs, key=lambda v: deg[v]):
            if part.owner(b) == part.owner(a) or deg[b] <= deg[a]:
                continue
            new = [v for v in range(csr.n) if v != a and v not in nbrs]
            grow = int(deg[b] - deg[a] + 1)
            return a, b, np.array([(a, v) for v in new[:grow]], np.int64)
    raise AssertionError("no edge across devices between unequal degrees")


def orientation(prob, lay, a, b):
    """``(owner's u row, v's accumulator index)`` slot pairs of {a, b}:
    ``"a"`` if the edge is kept on a's side, ``"b"`` on b's."""
    from repro.core.partition import partition_1d

    part = partition_1d(prob.n, prob.p)
    out = []
    for u, v, name in ((a, b, "a"), (b, a, "b")):
        k, q = int(part.owner(u)), int(part.owner(v))
        hit = ((lay.slot_u[k] == u - part.lo(k))
               & (lay.slot_acc[k] == q * (prob.n_loc + 1) + v - part.lo(q)))
        out += [name] * int(hit.sum())
    return out


def exchange_readers(jaxpr) -> set:
    """Primitives inside the round loop (the scan whose body exchanges)
    that read, directly or not, that round's exchange."""
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if not hasattr(sub, "eqns"):
                continue
            if eqn.primitive.name == "scan" and any(
                    e.primitive.name == "all_to_all" for e in sub.eqns):
                seen, names = set(), set()
                for e in sub.eqns:
                    if e.primitive.name == "all_to_all" or any(
                            x in seen for x in e.invars if hasattr(x, "count")):
                        seen.update(e.outvars)
                        names.add(e.primitive.name)
                return names
            found = exchange_readers(sub)
            if found is not None:
                return found
    return None


MULTIDEV_SCRIPT = r"""
from repro.distributed.spmd_runtime import ensure_host_devices
ensure_host_devices(8)  # preserves external XLA_FLAGS; must precede jax init
import json
import numpy as np
from repro.graphs.datasets import powerlaw_graph
from repro.core.async_engine import run_distributed_lcc
from repro.core.tric_baseline import tric_lcc_jnp
from repro.core.triangles import lcc_scores, triangles_per_vertex
from repro.core.partition import partition_1d

out = {}
csr = powerlaw_graph(160, 8, seed=0)
want_t = triangles_per_vertex(csr)
want_lcc = lcc_scores(csr)

for p in (2, 4, 8):
    for cache_rows in (0, 16):
        t, lcc = run_distributed_lcc(
            csr, p, n_rounds=3, cache_rows=cache_rows, method="bsearch"
        )
        out[f"p{p}_c{cache_rows}_t_ok"] = bool(np.array_equal(t, want_t))
        out[f"p{p}_c{cache_rows}_lcc_ok"] = bool(
            np.allclose(lcc, want_lcc, rtol=1e-5)
        )

# hybrid method on 4 devices
t, _ = run_distributed_lcc(csr, 4, n_rounds=2, cache_rows=8, method="hybrid")
out["hybrid_ok"] = bool(np.array_equal(t, want_t))

# every method on graphs with several degree classes, rows fetched and
# cached
import sys
sys.path.insert(0, TESTS)
from conftest import class_edge_graph, star_graph
for name, g in (("class_edges", class_edge_graph()), ("star", star_graph())):
    for method in ("pairwise", "bsearch", "hybrid"):
        t, lcc = run_distributed_lcc(g, 4, n_rounds=3, cache_rows=16,
                                     method=method)
        out[f"classes_{method}_{name}_ok"] = bool(
            np.array_equal(t, triangles_per_vertex(g))
            and np.allclose(lcc, lcc_scores(g), rtol=1e-5))

# the class-width exchange on a Kronecker graph: rows fetched in three
# classes, hub rows cached (cache_rows 8), every class fetched (0)
import jax
from repro.graphs.rmat import rmat_graph
from repro.core.async_engine import device_args, lcc_mesh, make_lcc_fn
from repro.core.cache import build_static_degree_cache
from repro.core.rma import build_sharded_problem, class_layout, schedule_counts
kg = rmat_graph(11, 16, seed=0)
for cache_rows in (0, 8):
    t, lcc = run_distributed_lcc(kg, 4, n_rounds=3, cache_rows=cache_rows,
                                 method="pairwise")
    cache = (build_static_degree_cache(kg.degrees, cache_rows)
             if cache_rows else None)
    prob = build_sharded_problem(kg, 4, n_rounds=3, cache=cache)
    lay = class_layout(prob)
    fn = make_lcc_fn(prob, lcc_mesh(4), method="pairwise")
    jaxpr = jax.make_jaxpr(fn)(*device_args(prob, lcc_mesh(4))).jaxpr
    out[f"kron_c{cache_rows}"] = {
        "exact": bool(np.array_equal(t, triangles_per_vertex(kg))
                      and np.allclose(lcc, lcc_scores(kg), rtol=1e-5)),
        "fetched": [w for w, _ in lay.fetch],
        "widths": sorted({wv for _, wv in lay.widths}),
        "cache_reads": schedule_counts(prob, kg.degrees)["cache_reads"],
        "n_rounds": prob.n_rounds,
        "exchanges": exchanges(jaxpr),
        "exchange_readers": sorted(exchange_readers(jaxpr) or ()),
        "collectives": collectives(jaxpr),
    }

# every row device 0 serves device 1 swapped for its next local row:
# the kept slots that read them must count something else
import dataclasses
from repro.core import async_engine
from repro.core.async_engine import lcc_pipelined
prob = build_sharded_problem(kg, 4, n_rounds=3,
                             cache=build_static_degree_cache(kg.degrees, 8))
lay = class_layout(prob)
idx = lay.fetch_idx.copy()
wrong = idx[0, :, 1] < prob.n_loc
idx[0, :, 1][wrong] = (idx[0, :, 1][wrong] + 1) % (prob.n_loc - 1)
async_engine.class_layout = lambda _: dataclasses.replace(lay, fetch_idx=idx)
t, _ = lcc_pipelined(prob, method="pairwise")
async_engine.class_layout = class_layout
part = partition_1d(kg.n, 4)
t = np.concatenate([t[k, :part.hi(k) - part.lo(k)] for k in range(4)])
out["wrong_rows"] = {"rows": int(wrong.sum()),
                     "tri_mismatch": int((t != triangles_per_vertex(kg)).sum())}

# a star whose hub (vertex 0) chip 0 owns: every hub edge is compared on
# the leaf's side, so the hub's whole count arrives through the
# reduce-scatter; with 1 cache row the hub row is read from the cache
g = star_graph()
for cache_rows in (0, 1):
    t, lcc = run_distributed_lcc(g, 4, n_rounds=3, cache_rows=cache_rows,
                                 method="pairwise")
    cache = (build_static_degree_cache(g.degrees, cache_rows)
             if cache_rows else None)
    prob = build_sharded_problem(g, 4, n_rounds=3, cache=cache)
    out[f"star_hub_c{cache_rows}"] = {
        "exact": bool(np.array_equal(t, triangles_per_vertex(g))
                      and np.allclose(lcc, lcc_scores(g), rtol=1e-5)),
        "hub_t": int(t[0]),
        "cache_reads": schedule_counts(prob, g.degrees)["cache_reads"],
        **hub_slots(prob, class_layout(prob), 0),
    }

# apply_delta, then a fresh epoch: the inserts give a more neighbours
# than b, so edge {a, b} moves to b's device
from repro.core.csr import from_edges
a, b, ins = orientation_flip(csr, 4)
prob = build_sharded_problem(csr, 4, n_rounds=3,
                             width=csr.max_degree + len(ins))
before = orientation(prob, class_layout(prob), a, b)
prob.apply_delta(ins, np.zeros((0, 2), np.int64))
after = orientation(prob, class_layout(prob), a, b)
src = np.repeat(np.arange(csr.n), csr.degrees)
g2 = from_edges(np.concatenate(
    [np.stack([src, csr.adjacencies], 1), ins]), csr.n)
t, lcc = lcc_pipelined(prob, method="pairwise")
part = partition_1d(csr.n, 4)
t = np.concatenate([t[k, :part.hi(k) - part.lo(k)] for k in range(4)])
lcc = np.concatenate([lcc[k, :part.hi(k) - part.lo(k)] for k in range(4)])
out["delta_flip"] = {
    "before": before, "after": after,
    "exact": bool(np.array_equal(t, triangles_per_vertex(g2))
                  and np.allclose(lcc, lcc_scores(g2), rtol=1e-5)),
}

# TriC BSP baseline must also be exact
t2, lcc2 = tric_lcc_jnp(csr, 4)
part = partition_1d(csr.n, 4)
t2g = np.concatenate([t2[k, : part.hi(k) - part.lo(k)] for k in range(4)])
out["tric_ok"] = bool(np.array_equal(t2g, want_t))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def multidev_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", f"TESTS = {TESTS!r}\n"
         + "".join(inspect.getsource(f) for f in (
             exchanges, exchange_readers, collectives, hub_slots,
             orientation_flip, orientation))
         + f"COLLECTIVES = {COLLECTIVES!r}\n" + MULTIDEV_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_multidevice_exact(multidev_results):
    for k, v in multidev_results.items():
        assert v, f"{k} failed"


@pytest.mark.parametrize("cache_rows", [0, 8])
def test_multidevice_class_width_exchange_exact(multidev_results, cache_rows):
    """p=4 on a Kronecker S11 graph, rows exchanged and held at their
    degree class: exact, with one exchange a round. With 8 cache rows
    the rows are fetched in three classes, the widest class is served
    from the cache alone; with none every class is fetched."""
    got = multidev_results[f"kron_c{cache_rows}"]
    assert got["exact"]
    assert got["exchanges"] == got["n_rounds"]
    # the counts for other devices' rows: one reduce-scatter an epoch
    assert got["collectives"].count("reduce_scatter") == 1
    # double buffering: in the round loop the exchange only fills the
    # next round's tables; no gather or compare of the round reads it
    readers = set(got["exchange_readers"])
    assert "dynamic_update_slice" in readers
    assert not readers & {"gather", "eq", "reduce_sum", "scatter-add"}
    if cache_rows:
        assert len(got["fetched"]) == 3 and got["cache_reads"] > 0
        assert set(got["widths"]) - set(got["fetched"])
    else:
        assert got["fetched"] == got["widths"] and len(got["widths"]) == 4


def test_engine_p1_makes_no_exchange():
    """At p=1 nothing is fetched and every count is the device's own,
    so the program issues no exchange and no other collective."""
    import jax
    from repro.core.async_engine import device_args, lcc_mesh, make_lcc_fn
    from repro.core.rma import build_sharded_problem, class_layout

    prob = build_sharded_problem(class_edge_graph(), 1, n_rounds=3)
    assert class_layout(prob).fetch == ()
    fn = make_lcc_fn(prob, lcc_mesh(1), method="pairwise")
    jaxpr = jax.make_jaxpr(fn)(*device_args(prob, lcc_mesh(1))).jaxpr
    assert exchanges(jaxpr) == 0
    assert collectives(jaxpr) == []


@pytest.mark.parametrize("cache_rows", [0, 1])
def test_multidevice_hub_count_arrives_through_the_reduce_scatter(
        multidev_results, cache_rows):
    """p=4 on a star whose hub chip 0 owns: the hub is never u (every
    leaf has the lower degree), so its whole count is added on the
    leaves' devices and arrives through the reduce-scatter; exact, with
    the hub row fetched (no cache rows) or read from the cache."""
    from repro.core.triangles import triangles_per_vertex

    got = multidev_results[f"star_hub_c{cache_rows}"]
    assert got["exact"]
    assert got["hub_t"] == triangles_per_vertex(star_graph())[0] > 0
    assert got["as_u"] == 0
    assert got["as_v_remote"] > got["as_v_local"] > 0
    assert (got["cache_reads"] > 0) == bool(cache_rows)


def test_multidevice_wrong_fetched_rows_count_wrong(multidev_results):
    """The kept slots read the rows they fetch: with every row device 0
    serves device 1 swapped for another, the p=4 epoch is not exact."""
    got = multidev_results["wrong_rows"]
    assert got["rows"] > 0 and got["tri_mismatch"] > 0


def test_multidevice_delta_flips_orientation_exact(multidev_results):
    """After ``apply_delta`` gives endpoint a more neighbours than b, the
    edge {a, b} is compared once on b's device instead of once on a's,
    and a fresh p=4 epoch is exact against the reference."""
    got = multidev_results["delta_flip"]
    assert got["before"] == ["a"] and got["after"] == ["b"]
    assert got["exact"]


def test_engine_p1_delta_flips_orientation_exact():
    """The same at p=1: the edge's slot moves from a's row to b's, and
    the epoch after ``apply_delta`` is exact."""
    from repro.core.async_engine import lcc_pipelined
    from repro.core.csr import from_edges
    from repro.core.rma import build_sharded_problem, class_layout
    from repro.core.triangles import lcc_scores, triangles_per_vertex

    csr = powerlaw_graph(120, 8, seed=3)
    a, b, ins = orientation_flip(csr, 2)  # any edge between unequal degrees
    prob = build_sharded_problem(csr, 1, n_rounds=2,
                                 width=csr.max_degree + len(ins))
    assert orientation(prob, class_layout(prob), a, b) == ["a"]
    prob.apply_delta(ins, np.zeros((0, 2), np.int64))
    assert orientation(prob, class_layout(prob), a, b) == ["b"]
    src = np.repeat(np.arange(csr.n), csr.degrees)
    g2 = from_edges(np.concatenate(
        [np.stack([src, csr.adjacencies], 1), ins]), csr.n)
    t, lcc = lcc_pipelined(prob, method="pairwise")
    assert np.array_equal(t[0, :csr.n], triangles_per_vertex(g2))
    np.testing.assert_allclose(lcc[0, :csr.n], lcc_scores(g2), rtol=1e-5)


@pytest.mark.parametrize("method", METHODS)
def test_multidevice_degree_classes_exact(multidev_results, method):
    """p=4 with cache rows: exact for every method on class-edge hubs and
    on a star."""
    for name in ("class_edges", "star"):
        assert multidev_results[f"classes_{method}_{name}_ok"], name
