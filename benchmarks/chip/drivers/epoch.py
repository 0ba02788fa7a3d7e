"""Window driver ``epoch``: exact full-graph TC+LCC epochs, back to back.

Set-up makes the seeded Graph500 edge list, builds the graph and the
static schedule through the program (``core.csr.from_edges``,
``core.rma.build_sharded_problem``), places the inputs on the mesh and
compiles the epoch program of ``core.async_engine.make_lcc_fn``
(compiled, not run: the window's first epoch is its first call).

The window calls that compiled program until ``--seconds`` have passed
and the epoch under way has ended, each call waited for with
``block_until_ready``; ``epoch_s`` is the window's length over the
number of epochs. Every epoch's per-vertex triangle counts and LCC are
kept and compared with the reference once the window has closed.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip.counting import epoch_compares
from benchmarks.chip.graph import kronecker_edges
from benchmarks.chip.harness import Check
from benchmarks.chip.reference import Reference

__all__ = ["prepare", "compare", "control_checks"]


def compare(ref: Reference, tri: list, lcc: list, limits: dict):
    """Checks of every epoch's output against the reference: vertices
    whose triangle count differs, and the widest relative LCC gap (the
    absolute gap where the reference LCC is 0)."""
    want_t = ref.triangles
    want_l = ref.lcc()
    scale = np.where(want_l > 0, want_l, 1.0)
    mism, gap, bad = 0, 0.0, 0
    for t, c in zip(tri, lcc):
        m = int(np.count_nonzero(np.asarray(t, np.int64) != want_t))
        g = float(np.max(np.abs(np.asarray(c, np.float64) - want_l) / scale))
        mism, gap = max(mism, m), max(gap, g)
        bad += m > limits["tri_mismatch"] or g > limits["lcc_max_rel_err"]
    return ([Check("tri_mismatch", mism, limits["tri_mismatch"]),
             Check("lcc_max_rel_err", gap, limits["lcc_max_rel_err"])],
            len(tri), bad)


def control_checks(session):
    """The checks with the reference put in the program's place, its LCC
    computed in bfloat16, the precision below the float32 the
    configuration states (the control, which has to fail)."""
    import ml_dtypes

    ref = Reference(session.edges, session.n)
    checks, _, _ = compare(ref, [ref.triangles],
                           [ref.lcc(ml_dtypes.bfloat16).astype(np.float32)],
                           session.cell.config["limits"])
    return checks


class EpochSession:
    def __init__(self, cell, edges, n, prob, compiled, inputs, part):
        self.cell = cell
        self.edges, self.n = edges, n
        self.compiled, self.inputs, self.part = compiled, inputs, part
        mem = compiled.memory_analysis()
        self.stats = {
            "program_bytes": mem.temp_size_in_bytes + mem.output_size_in_bytes,
            "compares_per_epoch": epoch_compares(prob.e_max, prob.width),
            "module_name": compiled.as_text().split("\n", 1)[0].split()[1]
            .rstrip(","),
        }
        self._outs = []
        self._tri, self._lcc = [], []

    def window(self, seconds: float) -> None:
        import jax

        ends = []
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.epoch"):
                out = self.compiled(*self.inputs)
                jax.block_until_ready(out)
            ends.append(time.perf_counter())
            self._outs.append(out)
            if ends[-1] - t0 >= seconds:
                break
        self.t0, self.t1 = t0, ends[-1]
        self.stats["epochs"] = len(ends)
        self.stats["epoch_walls_s"] = np.diff([t0] + ends).tolist()
        print(f"epochs: {len(ends)}, each (s): {self.stats['epoch_walls_s']}",
              file=sys.stderr, flush=True)

    def end_to_end(self) -> dict:
        return {"epoch_s": (self.t1 - self.t0) / self.stats["epochs"]}

    def release(self) -> None:
        for t, c in self._outs:
            t, c = np.asarray(t), np.asarray(c)
            self._tri.append(np.concatenate(
                [t[k, :self.part.hi(k) - self.part.lo(k)]
                 for k in range(t.shape[0])]))
            self._lcc.append(np.concatenate(
                [c[k, :self.part.hi(k) - self.part.lo(k)]
                 for k in range(c.shape[0])]))
        self._outs = self.compiled = self.inputs = None

    def check(self):
        ref = Reference(self.edges, self.n)
        return compare(ref, self._tri, self._lcc, self.cell.config["limits"])


def prepare(cell, seed: int, seconds: float) -> EpochSession:
    import jax

    from repro.core.async_engine import device_args, lcc_mesh, make_lcc_fn
    from repro.core.csr import from_edges
    from repro.core.partition import partition_1d
    from repro.core.rma import build_sharded_problem

    g, eng = cell.config, cell.config["engine"]
    n = 1 << int(g["scale"])
    marks = [time.perf_counter()]
    edges = kronecker_edges(g, seed)
    csr = from_edges(edges, n)
    marks.append(time.perf_counter())
    prob = build_sharded_problem(csr, int(eng["p"]),
                                 n_rounds=int(eng["n_rounds"]))
    marks.append(time.perf_counter())
    mesh = lcc_mesh(int(eng["p"]))
    fn = make_lcc_fn(prob, mesh, method=eng["method"])
    inputs = device_args(prob, mesh)
    jax.block_until_ready(inputs)
    marks.append(time.perf_counter())
    compiled = fn.lower(*inputs).compile()
    marks.append(time.perf_counter())
    parts = dict(zip(["graph", "schedule", "to_device", "compile"],
                     np.diff(marks).round(3).tolist()))
    print(f"set-up (s): {parts}", file=sys.stderr, flush=True)
    return EpochSession(cell, edges, n, prob, compiled, inputs,
                        partition_1d(n, int(eng["p"])))
