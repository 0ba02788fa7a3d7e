"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see 1 real device;
multi-device behavior is tested via subprocesses (see test_distributed.py)
and the production meshes only via launch/dryrun.py."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def random_graph(n, avg_deg, seed=0):
    from repro.core.csr import from_edges

    r = np.random.default_rng(seed)
    m = n * avg_deg // 2
    e = r.integers(0, n, size=(m, 2))
    return from_edges(e, n, undirected=True)


def powerlaw_graph(n, avg_deg, seed=0):
    from repro.graphs.datasets import powerlaw_graph as plg

    return plg(n, avg_deg, seed=seed)


def class_edge_graph(seed=0):
    """Hubs of degree 128, 129, 256, 257 and 300 (the edges of the epoch
    program's degree classes, 2^k and 2^k + 1, and a maximum degree that
    is not a power of two) over random edges among the other vertices."""
    from repro.core.csr import from_edges

    r = np.random.default_rng(seed)
    n, hubs = 640, (128, 129, 256, 257, 300)
    rest = np.arange(len(hubs), n)
    edges = [r.choice(rest, size=(4 * n, 2))]
    for h, d in enumerate(hubs):
        nbrs = r.choice(rest, size=d, replace=False)
        edges.append(np.stack([np.full(d, h), nbrs], 1))
    return from_edges(np.concatenate(edges), n, undirected=True)


def star_graph(n=300):
    """One hub joined to every other vertex, whose ring closes a triangle
    with the hub at every leaf edge."""
    from repro.core.csr import from_edges

    leaves = np.arange(1, n)
    edges = np.concatenate([
        np.stack([np.zeros_like(leaves), leaves], 1),
        np.stack([leaves, np.roll(leaves, 1)], 1),
    ])
    return from_edges(edges, n, undirected=True)
