"""Bring-up smoke run of the graph engine on TPU.

    python chip_smoke.py              # one chip: all three entry points
    python chip_smoke.py --chips 4    # a v5e 2x2 host: the SPMD path only

Drives the three graph entry points through their ``main()``, in this
one process, on R-MAT graphs with the Graph500 parameters at edge
factor 16:

- ``lcc_run --method pairwise --verify``: the static TC+LCC epoch
  engine, per-vertex triangle counts exact against ``core/triangles.py``;
- ``stream_run --device-tier``: insert/delete batches on the default
  Pallas kernel path, with bit-exact checkpoints against a recount;
- ``query_serve --verify --device-tier``: Zipf point queries, each
  exact against a recount of the live graph.

With ``--chips 4`` it runs only the four-chip path and what it is
compared with: ``lcc_run`` at p=4 (exact against the single-node
reference) and ``query_serve --spmd --ranks 4 --verify`` (exact answers,
measured collective traffic equal to the modeled serve matrix).

Every phase prints the platform it ran on, which Pallas kernels ran and
whether compiled, its compile time and the rest of its wall time — one
bring-up run, not a benchmark. Any failed phase, wrong result, kernel
run in the interpreter, or platform other than a TPU exits non-zero,
and then no result line is printed. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Scales. Every row is padded to the maximum degree (ROADMAP B1), so one
# v5e chip holds the static epoch engine up to S15: in 32 rounds it
# compiles to 0.75 GiB of arguments and 2.84 GiB of temporaries. The
# static phase uses the all-pairs ``pairwise`` method: the default
# ``hybrid`` also evaluates a jnp binary search for every edge, which
# runs about 60x slower than the compare on the chip and takes tens of
# minutes per epoch at S15. The streaming and serving phases run at
# S14: their exactness checks recount every triangle on the host.
STATIC_SCALE, STATIC_ROUNDS, STATIC_METHOD = 15, 32, "pairwise"
LIVE_SCALE = 14
# --chips 4 checks the collectives, not the scale: the 1-D partition
# gives rank 0 over half the edges, so a larger graph mostly waits on it.
SPMD_STATIC_SCALE, SPMD_LIVE_SCALE = 14, 12
EDGE_FACTOR = 16

# (kernel, layout) pairs each phase must have dispatched compiled
_KERNELS = {
    "lcc_run": set(),  # the epoch engine intersects with jnp ops
    "stream_run": {("intersect_count", None), ("resident_intersect", "rows"),
                   ("resident_intersect", "slots")},
    "query_serve": {("intersect_count", None), ("resident_intersect", "rows")},
    "query_serve_spmd": {("spmd_pairs", None)},
}


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


class _CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.total += duration


def _run_phase(name, entry, argv, graph, dev, clock):
    """Run one entry point's ``main(argv)`` under a fresh tracer and
    print what it ran on; exceptions propagate (a failed phase)."""
    import jax

    from repro.obs import trace as obs_trace

    print(f"\n=== {name}: python -m {entry.__name__} {' '.join(argv)}",
          flush=True)
    tracer = obs_trace.enable_tracing()
    c0, t0 = clock.total, time.perf_counter()
    rc = entry.main(argv)
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0
    obs_trace.disable_tracing()
    if rc != 0:
        _fail(f"{name} returned {rc}")

    ran = {}
    for ev in tracer.events:
        if ev["name"] != "pallas_kernel":
            continue
        a = ev["args"]
        key = (a["kernel"], a.get("layout"))
        calls, interp = ran.get(key, (0, set()))
        ran[key] = (calls + 1, interp | {bool(a["interpret"])})
    interpreted = sorted(k for k, (_, i) in ran.items() if True in i)
    if interpreted:
        _fail(f"{name}: Pallas kernels ran in the interpreter: {interpreted}")
    missing = _KERNELS[name] - set(ran)
    if missing:
        _fail(f"{name}: expected kernels never ran: {sorted(missing, key=str)}")
    kernels = ", ".join(
        f"{k}{'/' + lay if lay else ''} x{calls}"
        for (k, lay), (calls, _) in sorted(ran.items(), key=str)
    ) or "none on this path (jnp ops)"
    peak = jax.devices()[0].memory_stats() or {}
    print(f"[{name}] entry point {entry.__name__}; graph R-MAT "
          f"S{graph['scale']} EF{EDGE_FACTOR} n={graph['n']} m={graph['m']} "
          "(directed)", flush=True)
    print(f"[{name}] device: {dev['count']} x {dev['platform']} "
          f"({dev['kind']}); Pallas kernels compiled (interpret=False): "
          f"{kernels}", flush=True)
    print(f"[{name}] one bring-up run, not a benchmark: wall "
          f"{wall:.2f} s, of which compile {compile_s:.2f} s, the rest "
          f"{wall - compile_s:.2f} s; device-0 peak memory so far "
          f"{peak.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB", flush=True)
    print(f"[{name}] exact: yes (every check of the entry point passed)",
          flush=True)


def _graph(scale: int) -> dict:
    from repro.graphs.rmat import rmat_graph

    csr = rmat_graph(scale, EDGE_FACTOR, seed=0)
    return {"scale": scale, "n": csr.n, "m": csr.m}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: all three entry points on one chip; 4: the "
                         "SPMD path on a four-chip host")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        _fail(f"the repro package is not at {SRC}; run chip_smoke.py "
              "from a checkout of the repository")
    if not __debug__:
        _fail("run without -O: the entry points check results with "
              "assert statements too")
    sys.path.insert(0, str(SRC))

    from repro.launch.chip import device_summary, enable_compile_cache

    cache = Path(enable_compile_cache())
    warm = cache.is_dir() and any(cache.iterdir())
    clock = _CompileClock()
    dev = device_summary()
    if dev["platform"] != "tpu":
        _fail(f"JAX found no TPU (platform {dev['platform']!r}); this "
              "smoke run needs the chip")
    if dev["count"] < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"JAX sees {dev['count']}")
    print(f"chip_smoke: {dev['count']} x {dev['platform']} "
          f"({dev['kind']}), chips used {args.chips}; compile cache "
          f"{cache} ({'warm' if warm else 'cold'})", flush=True)
    static_scale, live_scale = ((STATIC_SCALE, LIVE_SCALE) if args.chips == 1
                                else (SPMD_STATIC_SCALE, SPMD_LIVE_SCALE))
    print(f"chip_smoke: static phase at S{static_scale} ({STATIC_ROUNDS} "
          f"rounds, {STATIC_METHOD}), live phases at S{live_scale}; see "
          "the scale notes at the top of chip_smoke.py", flush=True)

    from repro.launch import lcc_run, query_serve, stream_run

    static = _graph(static_scale)
    live = _graph(live_scale)
    common = ["--edge-factor", str(EDGE_FACTOR)]
    lcc_argv = ["--scale", str(static_scale), *common, "--n-rounds",
                str(STATIC_ROUNDS), "--method", STATIC_METHOD, "--verify",
                "--p", str(args.chips)]
    live_argv = ["--scale", str(live_scale), *common]
    query_argv = [*live_argv, "--queries", "256", "--workload", "zipf",
                  "--verify"]
    _run_phase("lcc_run", lcc_run, lcc_argv, static, dev, clock)
    if args.chips == 1:
        _run_phase("stream_run", stream_run,
                   [*live_argv, "--batches", "4", "--checkpoint-every", "2",
                    "--device-tier"],
                   live, dev, clock)
        _run_phase("query_serve", query_serve,
                   [*query_argv, "--device-tier"], live, dev, clock)
    else:
        _run_phase("query_serve_spmd", query_serve,
                   [*query_argv, "--spmd", "--ranks", "4"], live, dev, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
