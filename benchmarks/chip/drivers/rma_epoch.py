"""Window driver ``rma_epoch``: the ``epoch`` driver's exact full-graph
TC+LCC epochs, the graph partitioned over several chips with the
degree-score cache.

Set-up differs from ``drivers/epoch.py`` in two steps. The graph is
the configuration's dataset, labels included, drawn from
``kronecker_seed`` alone: over several chips the labels decide which
chip holds which vertex, hence each chip's work (a relabelling moved
the busiest chip's compares by 13%), as a Graphalytics dataset's fixed
labels decide it. So ``--seed`` changes nothing the program or the
reference sees, and every run does the same work on the same graph. And
the static schedule is built with ``core.cache.build_static_degree_cache``
of the engine's ``cache_rows`` highest-degree rows, so each chip holds
them replicated and fetches only the remote rows they leave out, each
round at their degree class. The window, the per-epoch outputs and
their comparison with the reference are the ``epoch`` driver's; the
reference's triangle blocks are counted on several threads
(``ThreadedReference``, the same sums). The session also records
``wire_bytes_per_epoch`` (``exchange.wire_bytes`` of the program's
shapes) and the chips' ``device_kind`` for the exchange's readers, and
prints the seconds the reference and the comparison take.
"""
from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.chip.drivers.epoch import EpochSession, compare, control_checks
from benchmarks.chip.exchange import wire_bytes
from benchmarks.chip.graph import kronecker_edges
from benchmarks.chip.reference import Reference

__all__ = ["prepare", "compare", "control_checks", "ThreadedReference"]

BLOCK = 1 << 15  # directed edges per popcount block, as the reference's
THREADS = min(16, os.cpu_count() or 1)


class ThreadedReference(Reference):
    """The plain reference, its triangle blocks counted on ``THREADS``
    threads (numpy leaves the interpreter lock in the row gathers, the
    AND and the popcount): the same sums in the same int64."""

    @property
    def triangles(self) -> np.ndarray:
        if self._tri is None:
            def block(lo):
                u = self.src[lo:lo + BLOCK]
                w = self.dst[lo:lo + BLOCK]
                both = self.bits[u] & self.bits[w]
                return np.bitwise_count(both).sum(1, dtype=np.int64)

            s = np.zeros(self.n, np.int64)
            starts = range(0, self.src.size, BLOCK)
            with ThreadPoolExecutor(THREADS) as pool:
                for lo, c in zip(starts, pool.map(block, starts)):
                    np.add.at(s, self.src[lo:lo + BLOCK], c)
            self._tri = s // 2
        return self._tri


class RmaEpochSession(EpochSession):
    def check(self):
        t0 = time.perf_counter()
        out = compare(ThreadedReference(self.edges, self.n), self._tri,
                      self._lcc, self.cell.config["limits"])
        print(f"reference and comparison: {time.perf_counter() - t0:.3f} s",
              file=sys.stderr, flush=True)
        return out


def prepare(cell, seed: int, seconds: float) -> RmaEpochSession:
    import jax

    from repro.core import rma
    from repro.core.async_engine import device_args, lcc_mesh, make_lcc_fn
    from repro.core.cache import build_static_degree_cache
    from repro.core.csr import from_edges
    from repro.core.partition import partition_1d

    g, eng = cell.config, cell.config["engine"]
    n, p = 1 << int(g["scale"]), int(eng["p"])
    marks = [time.perf_counter()]
    edges = kronecker_edges(g, int(g["kronecker_seed"]))
    csr = from_edges(edges, n)
    marks.append(time.perf_counter())
    cache = build_static_degree_cache(csr.degrees, int(eng["cache_rows"]))
    prob = rma.build_sharded_problem(csr, p, n_rounds=int(eng["n_rounds"]),
                                     cache=cache)
    # before the compile: a program without the class-width exchange
    # stops here, not minutes into compiling a program that cannot fit
    wire = wire_bytes(p, prob.n_rounds, rma.class_layout(prob).fetch)
    marks.append(time.perf_counter())
    mesh = lcc_mesh(p)
    fn = make_lcc_fn(prob, mesh, method=eng["method"])
    inputs = device_args(prob, mesh)
    jax.block_until_ready(inputs)
    marks.append(time.perf_counter())
    compiled = fn.lower(*inputs).compile()
    marks.append(time.perf_counter())
    parts = dict(zip(["graph", "schedule", "to_device", "compile"],
                     np.diff(marks).round(3).tolist()))
    print(f"set-up (s): {parts}", file=sys.stderr, flush=True)
    session = RmaEpochSession(cell, edges, n, prob, compiled, inputs,
                              partition_1d(n, p))
    session.stats.update(
        wire_bytes_per_epoch=wire,
        device_kind=jax.devices()[0].device_kind)
    return session
