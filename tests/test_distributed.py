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
         + inspect.getsource(exchanges) + inspect.getsource(exchange_readers)
         + MULTIDEV_SCRIPT],
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
    """At p=1 nothing is fetched, so the program issues no exchange."""
    import jax
    from repro.core.async_engine import device_args, lcc_mesh, make_lcc_fn
    from repro.core.rma import build_sharded_problem, class_layout

    prob = build_sharded_problem(class_edge_graph(), 1, n_rounds=3)
    assert class_layout(prob).fetch == ()
    fn = make_lcc_fn(prob, lcc_mesh(1), method="pairwise")
    jaxpr = jax.make_jaxpr(fn)(*device_args(prob, lcc_mesh(1))).jaxpr
    assert exchanges(jaxpr) == 0


@pytest.mark.parametrize("method", METHODS)
def test_multidevice_degree_classes_exact(multidev_results, method):
    """p=4 with cache rows: exact for every method on class-edge hubs and
    on a star."""
    for name in ("class_edges", "star"):
        assert multidev_results[f"classes_{method}_{name}_ok"], name
