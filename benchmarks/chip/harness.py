"""The benchmark's harness: finds a cell's files by name, runs it, and
prints the result line.

Everything that belongs to one cell is data or a file of its own, found
by the names in ``BENCHMARK.json``:

- ``configs/<config>.json``: the deployment (graph, engine or service
  settings, guarantees, the limits of the correctness check);
- ``traffic/<mix>.json``: the mix's parameters, and the ``driver`` that
  runs it (``drivers/<driver>.py``, shared by every mix of its kind);
- ``metrics/<metric>.py``: one reader per per-layer metric, ``read(run)``
  returning a number, or None where it finds nothing to read.

A run: check the devices, make the inputs from the seed and set up the
program (``setup_s`` runs from process start to here), measure for
``--seconds``, read peak memory, free the program, then compare what
the window produced with the plain reference. With ``--trace 1`` the
window runs under the JAX profiler and the line carries the per-layer
metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]

__all__ = ["Cell", "Check", "RunRecord", "load_cell", "load_driver",
           "load_reader", "main", "run_cell"]


class BenchError(RuntimeError):
    """A run that cannot give a result: no line is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader may read: the driver's host readings, the
    profiler trace, the program's own spans and counters, all recorded
    while the window ran."""

    cell: Cell
    stats: dict
    trace: object = None  # devtrace.Trace
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def load_driver(traffic: dict, bench_dir: Path = BENCH_DIR):
    return _load_module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                        f"chipbench_driver_{traffic['driver']}")


def load_reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    mod = _load_module(bench_dir / "metrics" / f"{metric}.py",
                       "chipbench_metric_" + metric.replace(".", "_"))
    return mod.read


def tpu_devices(chips: int) -> dict:
    """The devices JAX runs on; a BenchError unless they are at least
    ``chips`` TPU chips listed in ``peaks.json``."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"devices: {dev['count']} x {dev['platform']} ({dev['kind']})",
          file=sys.stderr, flush=True)
    if dev["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if dev["kind"] not in peaks:
        raise BenchError(f"{dev['kind']!r} is not in peaks.json")
    return dev


def _memory_stats(n: int) -> List[dict]:
    import jax

    return [d.memory_stats() or {} for d in jax.devices()[:n]]


def _memory_peak(before: List[dict], after: List[dict], program_bytes: int) -> int:
    """Peak bytes on the fullest chip: the allocator's own peak, or,
    where larger, what the chip held as the window began plus the
    temporaries and outputs of the window's program as compiled. The
    TPU runtime's ``peak_bytes_in_use`` counts the arrays a program is
    given and returns, not the temporaries it runs in."""
    return int(max((max(a.get("peak_bytes_in_use", 0),
                        b.get("bytes_in_use", 0) + program_bytes)
                    for b, a in zip(before, after)), default=0))


class _CompileCounter:
    """Programs lowered while ``counting`` is on (a lowering is a new
    program shape in this process, cached on disk or not)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if self.counting and event == self.EVENT:
            self.count += 1


def _enable_compile_cache() -> None:
    import jax

    from repro.launch.chip import enable_compile_cache

    enable_compile_cache()
    # keep every program, however quick to compile, so that only a
    # cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices: Callable[[int], dict] = tpu_devices,
             root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``devices`` checks the chips (the tests give it a CPU stand-in),
    ``root`` and ``bench_dir`` say where ``BENCHMARK.json`` and the
    cell's files are."""
    import jax

    cell = load_cell(name, root, bench_dir)
    dev = devices(cell.chips)
    print(f"set-up: devices ready {time.perf_counter() - t_start:.3f} s "
          "after process start", file=sys.stderr, flush=True)
    _enable_compile_cache()
    compiles = _CompileCounter()
    driver = load_driver(cell.traffic, bench_dir)
    readers = {m["name"]: load_reader(m["name"], bench_dir)
               for m in cell.per_layer}

    session = driver.prepare(cell, seed, seconds)
    setup_s = time.perf_counter() - t_start
    tracer = None
    logdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-") if trace else None
    if trace:
        from repro.obs import trace as obs_trace

        tracer = obs_trace.enable_tracing()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # on by default; it slows the host many times over
        jax.profiler.start_trace(logdir.name, profiler_options=opts)
    mem_before = _memory_stats(cell.chips)
    compiles.counting = True
    with jax.profiler.TraceAnnotation("bench.window"):
        session.window(seconds)
    compiles.counting = False
    if trace:
        jax.profiler.stop_trace()
        obs_trace.disable_tracing()
    mem_after = _memory_stats(cell.chips)
    print(f"memory stats before the window: {mem_before}; after: "
          f"{mem_after}", file=sys.stderr, flush=True)
    device = dict(dev, memory_peak_bytes=_memory_peak(
        mem_before, mem_after, session.stats.get("program_bytes", 0)))
    session.release()

    breakdown = None
    if trace:
        tr = _read_trace(logdir)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        run = RunRecord(cell, session.stats, tr, tracer.events,
                        {"window_compiles": compiles.count})
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]](run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = dict(session.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    checks, attempted, failed = session.check()
    result = {"correct": all(c.ok for c in checks), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def _read_trace(logdir):
    from benchmarks.chip import devtrace

    try:
        return devtrace.Trace(devtrace.load(logdir.name))
    finally:
        logdir.cleanup()


def main(args, *, t_start: float) -> int:
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0
