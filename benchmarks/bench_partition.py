"""Hub-aware partitioning vs the paper's 1D blocks (ROADMAP item 2).

Three CI-gated claims on a scale-free graph under a zipf query mix:

1. **bit_exact_all** — swapping ``Partition1D`` for ``partition_hub``
   changes WHERE rows live and HOW hub rows ship (per-rank fragments,
   reduced additively), never WHAT a query answers: every query result
   is identical across {1d, hub} x {loop, spmd} x p in {1, 4, 8}, and
   the per-rank freshness audit passes everywhere.
2. **imbalance_reduced** — balance-aware cuts + round-robin hub routing
   pull the per-rank read load (the ``load_imbalance`` gauge) below the
   1D baseline.
3. **skew_reduced** — fragmenting hub rows across all ranks flattens
   the serve matrix (the ``serve_matrix_skew`` gauge): a hot hub's
   serve traffic spreads over p ranks instead of hammering its owner.

The SPMD rows double as a model-fidelity check: the executor asserts
measured == modeled traffic per microbatch, so a hub-fragment
mischarge would abort the run rather than skew a number.

Runs in a subprocess with 8 forced host devices (jax pins the device
count at first init), like ``bench_spmd_scaling``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MEASURE_SCRIPT = r"""
from repro.distributed.spmd_runtime import ensure_host_devices
ensure_host_devices(8)  # preserves external XLA_FLAGS; must precede jax init
import json, sys, time
import numpy as np

quick = bool(int(sys.argv[1]))

from repro.core.partition import partition_hub
from repro.graphs.datasets import powerlaw_graph
from repro.serving import LiveQueryService
from repro.serving.workload import make_queries

n = 2048 if quick else 8192
csr = powerlaw_graph(n, 16 if quick else 24, seed=0)
queries = make_queries(
    csr.degrees, 384 if quick else 2048, kind="zipf", seed=1
)


def fingerprint(results):
    out = []
    for r in results:
        ids = getattr(r, "ids", None)
        out.append([float(r.value),
                    None if ids is None else [int(x) for x in ids]])
    return out


def run_one(p, mode, execution):
    part = partition_hub(csr.degrees, p) if mode == "hub" else None
    svc = LiveQueryService(csr, p=p, cross_rank=True, execution=execution,
                           partition=part, max_batch=64)
    t0 = time.perf_counter()
    results = svc.scheduler.run(queries)
    wall = time.perf_counter() - t0
    svc.verify()  # bit-exact vs recount + zero stale cached rows
    reg = svc.metrics_registry()
    return {
        "p": p, "partition": mode, "execution": execution,
        "wall_s": round(wall, 4),
        "load_imbalance": round(
            reg.get_gauge("load_imbalance", tier="host"), 4),
        "serve_matrix_skew": round(
            reg.get_gauge("serve_matrix_skew", tier="wire"), 4),
        "rows_served": int(svc.runtime.cross_rank_rows_served()),
    }, fingerprint(results)


rows, fps = [], []
for p in (1, 4, 8):
    for mode, execution in (("1d", "loop"), ("hub", "loop"),
                            ("hub", "spmd")):
        row, fp = run_one(p, mode, execution)
        rows.append(row)
        fps.append(fp)
print(json.dumps({
    "rows": rows,
    "bit_exact_all": all(fp == fps[0] for fp in fps[1:]),
}))
"""


def _mean(rows, mode, key):
    vals = [r[key] for r in rows if r["partition"] == mode and r["p"] > 1
            and r["execution"] == "loop"]
    return sum(vals) / max(len(vals), 1)


def run(quick: bool = True):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    # the child counts on forced host devices: keep it off any chip the
    # parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", MEASURE_SCRIPT, str(int(quick))],
        capture_output=True,
        text=True,
        env=env,
        timeout=3600,
    )
    if r.returncode != 0:
        return {"error": r.stderr[-2000:]}
    res = json.loads(r.stdout.strip().splitlines()[-1])
    rows = res["rows"]
    imb_1d = _mean(rows, "1d", "load_imbalance")
    imb_hub = _mean(rows, "hub", "load_imbalance")
    skew_1d = _mean(rows, "1d", "serve_matrix_skew")
    skew_hub = _mean(rows, "hub", "serve_matrix_skew")
    return {
        "platform": "cpu",
        "rows": rows,
        # CI-gated booleans (deterministic — counters, not wall clocks)
        "bit_exact_all": bool(res["bit_exact_all"]),
        "load_imbalance_1d": round(imb_1d, 4),
        "load_imbalance_hub": round(imb_hub, 4),
        "imbalance_reduced": bool(imb_hub < imb_1d),
        "serve_skew_1d": round(skew_1d, 4),
        "serve_skew_hub": round(skew_hub, 4),
        "skew_reduced": bool(skew_hub < skew_1d),
        "paper_ref": "ROADMAP item 2 — past the paper's §III-A 1D "
                     "blocks (hub splitting per Sanders & Uhl "
                     "arXiv:2302.11443)",
    }


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
