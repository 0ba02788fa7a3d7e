"""Distributed engine correctness.

In-process: p=1 (degenerate mesh). Multi-device: subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax pins device count
at first init, and the rest of the suite must see 1 device).
"""
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
        [sys.executable, "-c", f"TESTS = {TESTS!r}\n" + MULTIDEV_SCRIPT],
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


@pytest.mark.parametrize("method", METHODS)
def test_multidevice_degree_classes_exact(multidev_results, method):
    """p=4 with cache rows: exact for every method on class-edge hubs and
    on a star."""
    for name in ("class_edges", "star"):
        assert multidev_results[f"classes_{method}_{name}_ok"], name
