"""Near-zero-overhead span tracer with a Chrome-trace-event exporter.

The paper's headline numbers are *attribution* claims — caching cuts
total running time by up to 73%, and communication vs. computation
decomposes per rank. Reproducing those breakdowns needs a time
dimension on top of the counter ledgers: which phase (``fetch_rows``,
``all_to_all``, ``intersect_kernel``, ...) spent the wall clock, on
which rank, inside which enclosing unit of work.

Design constraints, in order:

1. **Disabled is the default and must cost ~nothing.** ``span()`` with
   no tracer installed is one module-global load, a ``None`` check, and
   a shared no-op context manager — no allocation, no clock read. The
   serving benchmark measures this (< 3% of end-to-end wall is the
   gate; in practice it is orders of magnitude below that).
2. **Spans are nestable and per-rank.** Rank maps to the Chrome trace
   ``tid``, so Perfetto renders one swim-lane per rank; nesting follows
   ``with`` scoping, which makes the exported span tree well-nested by
   construction (the validator checks it anyway).
3. **The export is a standard Chrome trace** (``{"traceEvents": [...]}``
   with ``ph: "X"`` complete events, microsecond timestamps): open it
   at https://ui.perfetto.dev or ``chrome://tracing`` unmodified.
4. **Spans sit on the device clock.** While a tracer is installed, each
   span also opens a ``jax.profiler.TraceAnnotation`` of its name, so a
   profiler trace taken at the same time shows it on the host line,
   on the same clock as the device operations.
5. **Set-up is recorded whether or not a tracer is installed.**
   ``setup_span()`` keeps the newest span of each name (its seconds and
   its counts) in a small bounded record that lives as long as the
   process; ``install_compile_listener()`` adds the seconds JAX spends
   lowering and compiling (or loading from the persistent cache) and
   the cache's hits and misses. Set-up runs a few times per process,
   so this costs microseconds per run. ``setup_record()`` reads it.
   Set-up spans never enter a ``Tracer``'s events.

The span names, the set-up spans and the compile counters are listed in
docs/observability.md.

Fine mode (``enable_tracing(fine=True)``) additionally emits per-entry
``cache_admit``/``cache_evict`` instants from inside the cache — useful
for cache forensics, too hot to leave on for long runs.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "SETUP_LIMIT",
    "Tracer",
    "enable_tracing",
    "disable_tracing",
    "get_tracer",
    "span",
    "instant",
    "counter",
    "fine_enabled",
    "setup_span",
    "setup_record",
    "install_compile_listener",
]

class _NullSpan:
    """Shared no-op context manager returned when tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        """No-op twin of ``_Span.set`` (late argument attachment)."""


_NULL_SPAN = _NullSpan()


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` named ``name``."""
    from jax import profiler

    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class _Span:
    """One live span: records a ``ph: "X"`` complete event on exit, and
    shows on the profiler's host line while it is open."""

    __slots__ = ("_tracer", "name", "rank", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, rank: int, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.rank = rank
        self.cat = cat
        self.args = args

    def set(self, **args) -> None:
        """Attach arguments discovered mid-span (e.g. measured bytes)."""
        if self.args is None:
            self.args = {}
        self.args.update(args)

    def __enter__(self) -> "_Span":
        self._ann = _annotation(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._tracer._complete(self, self._t0, t1)
        return False


class Tracer:
    """Collects trace events in memory; exports Chrome trace JSON.

    ``rank`` maps to ``tid`` (+1, so unranked events get lane 0); the
    single process is ``pid`` 0. Timestamps are microseconds relative
    to tracer creation (``perf_counter`` based, so durations are exact
    even though the origin is arbitrary).
    """

    def __init__(self, *, fine: bool = False):
        self.fine = bool(fine)
        self.events: List[dict] = []
        self._t0 = time.perf_counter()

    # ---------------- recording ----------------
    def _ts(self, t: float) -> float:
        return (t - self._t0) * 1e6  # microseconds, Chrome's unit

    def span(self, name: str, *, rank: int = -1, cat: str = "",
             **args) -> _Span:
        return _Span(self, name, int(rank), cat, args or None)

    def _complete(self, s: _Span, t0: float, t1: float) -> None:
        ev = {
            "name": s.name,
            "ph": "X",
            "ts": self._ts(t0),
            "dur": (t1 - t0) * 1e6,
            "pid": 0,
            "tid": s.rank + 1,
        }
        if s.cat:
            ev["cat"] = s.cat
        if s.args:
            ev["args"] = {k: _jsonable(v) for k, v in s.args.items()}
        self.events.append(ev)

    def instant(self, name: str, *, rank: int = -1, cat: str = "",
                **args) -> None:
        ev = {
            "name": name,
            "ph": "i",
            "ts": self._ts(time.perf_counter()),
            "pid": 0,
            "tid": int(rank) + 1,
            "s": "t",  # thread-scoped instant
        }
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        self.events.append(ev)

    def counter(self, name: str, value: float, *, rank: int = -1) -> None:
        self.events.append({
            "name": name,
            "ph": "C",
            "ts": self._ts(time.perf_counter()),
            "pid": 0,
            "tid": int(rank) + 1,
            "args": {name: float(value)},
        })

    # ---------------- aggregation ----------------
    def phase_totals(self) -> Dict[str, Dict[str, float]]:
        """Per-phase rollup over the complete ("X") events:
        ``{name: {"calls", "total_s", "bytes"}}`` — the time dimension
        the metric registry folds in (``metrics.fold_trace``)."""
        out: Dict[str, Dict[str, float]] = {}
        for ev in self.events:
            if ev.get("ph") != "X":
                continue
            d = out.setdefault(
                ev["name"], {"calls": 0.0, "total_s": 0.0, "bytes": 0.0}
            )
            d["calls"] += 1
            d["total_s"] += ev.get("dur", 0.0) * 1e-6
            args = ev.get("args") or {}
            for k, v in args.items():
                if k.endswith("bytes") and isinstance(v, (int, float)):
                    d["bytes"] += v
        return out

    # ---------------- export ----------------
    def to_chrome(self) -> dict:
        """The Chrome trace object (Perfetto/chrome://tracing format)."""
        meta = [
            {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
             "args": {"name": "repro"}},
            {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
             "args": {"name": "global"}},
        ]
        for tid in sorted({ev["tid"] for ev in self.events}):
            if tid > 0:
                meta.append({
                    "name": "thread_name", "ph": "M", "pid": 0,
                    "tid": tid, "args": {"name": f"rank {tid - 1}"},
                })
        return {
            "traceEvents": meta + self.events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs.trace"},
        }

    def export(self, path: str) -> None:
        """Write the trace; open the file at https://ui.perfetto.dev."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)

    def __len__(self) -> int:
        return len(self.events)


def _jsonable(v):
    """Span args must survive json.dump: coerce numpy scalars etc."""
    if isinstance(v, (str, bool)) or v is None:
        return v
    if isinstance(v, float):
        return v
    try:
        return int(v)
    except (TypeError, ValueError):
        return str(v)


# --------------------------------------------------------------------------
# Module-level switchboard: the instrumentation hooks call these. With no
# tracer installed, span() costs one global load + None check + returning
# the shared _NULL_SPAN — the near-zero-overhead contract.
# --------------------------------------------------------------------------
_tracer: Optional[Tracer] = None


def enable_tracing(*, fine: bool = False) -> Tracer:
    """Install (and return) a fresh global tracer."""
    global _tracer
    _tracer = Tracer(fine=fine)
    return _tracer


def disable_tracing() -> Optional[Tracer]:
    """Remove the global tracer; returns it (events intact) if any."""
    global _tracer
    t, _tracer = _tracer, None
    return t


def get_tracer() -> Optional[Tracer]:
    return _tracer


def span(name: str, *, rank: int = -1, cat: str = "", **args):
    """A context manager timing one phase (no-op when disabled)."""
    t = _tracer
    if t is None:
        return _NULL_SPAN
    return t.span(name, rank=rank, cat=cat, **args)


def instant(name: str, *, rank: int = -1, cat: str = "", **args) -> None:
    t = _tracer
    if t is not None:
        t.instant(name, rank=rank, cat=cat, **args)


def counter(name: str, value: float, *, rank: int = -1) -> None:
    t = _tracer
    if t is not None:
        t.counter(name, value, rank=rank)


def fine_enabled() -> bool:
    """True iff a tracer is installed AND fine-grained (per-cache-entry)
    events were requested — the gate in the cache hot paths."""
    t = _tracer
    return t is not None and t.fine


# --------------------------------------------------------------------------
# Set-up record: kept whether or not a tracer is installed.
# --------------------------------------------------------------------------
SETUP_LIMIT = 64  # names the set-up record keeps; the oldest go first

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "setup.lower",
    # a compile, or a load from the persistent compilation cache
    "/jax/core/compile/backend_compile_duration": "setup.compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class _SetupRecord:
    """``{name: {"s": seconds, **counts}}``, oldest first, at most
    ``limit`` names. ``put`` replaces a name's entry (the newest span of
    that name); ``add`` keeps a running total."""

    def __init__(self, limit: int = SETUP_LIMIT):
        self._limit = limit
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()  # compiles may finish on any thread

    def _store(self, name: str, entry: Dict[str, Any]) -> None:
        self._entries.pop(name, None)
        self._entries[name] = entry
        while len(self._entries) > self._limit:
            del self._entries[next(iter(self._entries))]

    def put(self, name: str, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._store(name, entry)

    def add(self, name: str, **counts: float) -> None:
        with self._lock:
            entry = dict(self._entries.get(name, {}))
            for k, v in counts.items():
                entry[k] = entry.get(k, 0) + v
            self._store(name, entry)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}


_setup = _SetupRecord()


class _SetupSpan:
    """One live set-up span: on exit, replaces its name's entry in the
    set-up record with its seconds (``s``) and its arguments."""

    __slots__ = ("name", "args", "_t0", "_ann")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = args

    def set(self, **args) -> None:
        """Attach counts known only once the work is done."""
        self.args.update(args)

    def __enter__(self) -> "_SetupSpan":
        self._ann = _annotation(self.name) if _tracer is not None else None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _setup.put(self.name, {"s": dur, **{k: _jsonable(v)
                                            for k, v in self.args.items()}})
        return False


def setup_span(name: str, **args) -> _SetupSpan:
    """A context manager timing one piece of set-up into the set-up
    record, tracer or not (``set()`` attaches counts on the way)."""
    return _SetupSpan(name, args)


def setup_record() -> Dict[str, Dict[str, Any]]:
    """A copy of the set-up record: ``{name: {"s": seconds, **counts}}``
    for the newest set-up span of each name, and ``setup.lower`` /
    ``setup.compile`` with the process's total lowering and compile
    seconds, their number ``n``, and (on ``setup.compile``) the
    persistent cache's ``cache_hits`` and ``cache_misses``."""
    return _setup.snapshot()


def _on_compile_duration(event: str, duration: float, **_kw) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        _setup.add(name, s=duration, n=1)


def _on_cache_event(event: str, **_kw) -> None:
    key = CACHE_EVENTS.get(event)
    if key is not None:
        _setup.add("setup.compile", **{key: 1})


_compile_listener_installed = False


def install_compile_listener() -> None:
    """Feed JAX's compile timings and cache events into the set-up
    record, from now on; a second call does nothing."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    monitoring.register_event_listener(_on_cache_event)
    _compile_listener_installed = True
