"""The benchmark's generators: the Graph500 edge list and the seeded
streams; and the compare count."""
import numpy as np

import _paths  # noqa: F401
from benchmarks.chip.counting import epoch_compares
from benchmarks.chip.graph import kronecker_edges, rng_for

BIG_SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits
GRAPH = {"scale": 10, "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
         "kronecker_seed": 1}


def _degrees(edges, n):
    e = edges[edges[:, 0] != edges[:, 1]]
    key = np.unique(np.minimum(e[:, 0], e[:, 1]) * n + np.maximum(e[:, 0], e[:, 1]))
    return np.bincount(np.concatenate([key // n, key % n]), minlength=n)


def test_kronecker_edges_seeded_relabelling():
    n = 1 << GRAPH["scale"]
    a, a2 = kronecker_edges(GRAPH, BIG_SEED), kronecker_edges(GRAPH, BIG_SEED)
    b = kronecker_edges(GRAPH, 7)
    assert a.shape == (16 << 10, 2) and a.dtype == np.int64
    assert np.array_equal(a, a2)
    assert not np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < n
    # every seed gets the same graph up to its labels: the same degrees
    assert np.array_equal(np.sort(_degrees(a, n)), np.sort(_degrees(b, n)))
    # Graph500 skew: the top vertex holds far more than the mean degree
    d = _degrees(a, n)
    assert d.max() > 10 * d.mean()


def test_rng_streams_are_independent_and_seeded():
    x = rng_for(BIG_SEED, "graph").random(4)
    assert np.array_equal(x, rng_for(BIG_SEED, "graph").random(4))
    assert not np.array_equal(x, rng_for(BIG_SEED, "other").random(4))
    assert not np.array_equal(x, rng_for(BIG_SEED + 1, "graph").random(4))
    assert not np.array_equal(x, rng_for(BIG_SEED + 2**32, "graph").random(4))


def test_kronecker_seed_draws_another_graph():
    n = 1 << GRAPH["scale"]
    a = kronecker_edges(GRAPH, BIG_SEED)
    b = kronecker_edges(dict(GRAPH, kronecker_seed=2), BIG_SEED)
    # other quadrant draws: another degree sequence, not a relabelling
    assert not np.array_equal(np.sort(_degrees(a, n)), np.sort(_degrees(b, n)))
    assert b.shape == a.shape and b.max() < n


def test_epoch_compares_counts_the_padded_compare():
    from repro.core.csr import from_edges
    from repro.core.rma import build_sharded_problem

    g = dict(GRAPH, scale=7)
    csr = from_edges(kronecker_edges(g, 3), 1 << 7)
    prob = build_sharded_problem(csr, 1, n_rounds=4)
    # the pairwise count compares an [e_chunk, W] block of rows with
    # another, all pairs, in each of n_rounds rounds
    e_chunk = prob.e_max // prob.n_rounds
    per_round = np.broadcast_shapes((e_chunk, prob.width, 1),
                                    (e_chunk, 1, prob.width))
    assert epoch_compares(prob.e_max, prob.width) == prob.n_rounds * int(
        np.prod(per_round))
    assert epoch_compares(426560, 3618) == 426560 * 3618 ** 2
