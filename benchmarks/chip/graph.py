"""Graph500 Kronecker edge lists for the benchmark, made from a seed.

A copy of the Graph500 reference generator (``kronecker_generator.m``
of the Graph500 specification): each of ``edge_factor * 2**scale``
edges picks one quadrant of the adjacency matrix per level with
probabilities A, B, C and D = 1 - A - B - C; the vertex labels are then
permuted and the edge list shuffled.

The quadrant draws use the configuration's fixed ``kronecker_seed``, so
every ``--seed`` gets the same graph up to a relabelling: the same
degree sequence, the same padded widths and the same work. The run's
seed drives the label permutation and the edge order, as the Graph500
generator's own two permutations do. The edge list is raw: self-loops
and repeated edges stay in, and whoever builds a graph from it drops
them.
"""
from __future__ import annotations

import numpy as np

__all__ = ["kronecker_edges", "rng_for"]


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of one run's seed (any
    non-negative integer, also past 64 bits)."""
    words = [int(seed) >> (32 * i) & 0xFFFFFFFF
             for i in range(max(1, -(-int(seed).bit_length() // 32)))]
    tag = [ord(ch) for ch in stream]
    return np.random.default_rng(np.random.SeedSequence(words + [0] + tag))


def kronecker_edges(graph: dict, seed: int) -> np.ndarray:
    """``[edge_factor * 2**scale, 2]`` int64 edge list of a configuration
    (its ``scale``, ``edge_factor``, ``A``, ``B``, ``C`` and
    ``kronecker_seed``), relabelled by ``seed``."""
    scale, ef = int(graph["scale"]), int(graph["edge_factor"])
    a, b, c = float(graph["A"]), float(graph["B"]), float(graph["C"])
    n, m = 1 << scale, ef << scale
    rng = np.random.default_rng(int(graph["kronecker_seed"]))
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), np.int64)
    for level in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ij[0] |= ii_bit.astype(np.int64) << level
        ij[1] |= jj_bit.astype(np.int64) << level
    perm_rng = rng_for(seed, "graph")
    labels = perm_rng.permutation(n)
    ij = labels[ij]
    ij = ij[:, perm_rng.permutation(m)]
    return np.ascontiguousarray(ij.T)
