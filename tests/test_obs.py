"""Observability plane: span tracer, labeled metric registry, ledger
adapters, stat-merge edge cases, and the trace/metric validators CI
runs against every ``--trace``/``--metrics`` smoke."""
import dataclasses
import json
import types

import numpy as np
import pytest

from conftest import powerlaw_graph

from repro.core.cache import (
    CacheStats,
    merge_cache_stats,
    merge_counter_dataclasses,
)
from repro.core.runtime import ProviderStats, ShardedRuntime
from repro.obs import trace as obs_trace
from repro.obs.metrics import (
    MetricRegistry,
    fold_trace,
    imbalance,
    load_snapshot,
    record_collective_ledger,
    record_latency,
    record_reconciliation,
    record_runtime,
)
from repro.obs.validate import validate_metrics, validate_trace
from repro.serving.metrics import LatencyRecorder
from repro.streaming import DynamicCSR


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    obs_trace.disable_tracing()


def _runtime(p=4, n=80, seed=0):
    csr = powerlaw_graph(n, 5, seed=seed)
    store = DynamicCSR.from_csr(csr)
    return ShardedRuntime(store, p), store


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def test_disabled_tracing_is_a_shared_noop():
    assert obs_trace.get_tracer() is None
    s1 = obs_trace.span("fetch_rows", rank=2, cat="runtime", n=9)
    s2 = obs_trace.span("all_to_all")
    assert s1 is s2  # one shared null object: no per-call allocation
    with s1 as s:
        s.set(bytes=123)  # late-arg attachment must also be a no-op
    obs_trace.instant("cache_admit", key=1)
    obs_trace.counter("queue_depth", 5)
    assert not obs_trace.fine_enabled()
    assert obs_trace.get_tracer() is None


def test_span_nesting_ranks_and_export(tmp_path):
    tracer = obs_trace.enable_tracing()
    with obs_trace.span("stream_batch", rank=0, cat="streaming", n=4):
        with obs_trace.span("intersect_kernel", rank=0, pairs=7):
            pass
        with obs_trace.span("fetch_rows", rank=0, n=2):
            pass
    with obs_trace.span("fetch_rows", rank=3, n=1):
        pass
    obs_trace.counter("queue_depth", 2, rank=1)
    obs_trace.instant("cache_invalidate", rank=1, n=3)
    assert obs_trace.disable_tracing() is tracer
    assert len(tracer) == 6

    chrome = tracer.to_chrome()
    assert validate_trace(chrome) == []
    names = [e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert set(names) == {"stream_batch", "intersect_kernel", "fetch_rows"}
    # rank -> tid lane (+1), so Perfetto gets one swim-lane per rank
    lanes = {e["tid"] for e in chrome["traceEvents"] if e["ph"] == "X"}
    assert lanes == {1, 4}
    # thread_name metadata names each rank lane
    th = {e["tid"]: e["args"]["name"] for e in chrome["traceEvents"]
          if e["ph"] == "M" and e["name"] == "thread_name"}
    assert th[1] == "rank 0" and th[4] == "rank 3"

    path = tmp_path / "t.json"
    tracer.export(str(path))
    with open(path) as f:
        assert validate_trace(json.load(f)) == []


def test_phase_totals_roll_up_time_calls_bytes():
    tracer = obs_trace.enable_tracing()
    for _ in range(3):
        with obs_trace.span("all_to_all", payload_bytes=100, wire_bytes=50):
            pass
    with obs_trace.span("fetch_rows", n=5):
        pass
    obs_trace.disable_tracing()
    tot = tracer.phase_totals()
    assert tot["all_to_all"]["calls"] == 3
    assert tot["all_to_all"]["bytes"] == 3 * 150  # every *bytes arg sums
    assert tot["all_to_all"]["total_s"] > 0
    assert tot["fetch_rows"] == pytest.approx(tot["fetch_rows"] | {
        "calls": 1, "bytes": 0.0})


def test_span_set_attaches_late_args():
    tracer = obs_trace.enable_tracing()
    with obs_trace.span("residency_patch") as s:
        s.set(bytes=77, admits=2)
    obs_trace.disable_tracing()
    (ev,) = tracer.events
    assert ev["args"] == {"bytes": 77, "admits": 2}


def test_fine_mode_gates_per_entry_instants():
    obs_trace.enable_tracing()
    assert not obs_trace.fine_enabled()
    obs_trace.disable_tracing()
    tracer = obs_trace.enable_tracing(fine=True)
    assert obs_trace.fine_enabled()
    obs_trace.instant("cache_admit", key=4, bytes=64)
    obs_trace.disable_tracing()
    assert [e["ph"] for e in tracer.events] == ["i"]


# ---------------------------------------------------------------------------
# set-up record, device clock, compile listener
# ---------------------------------------------------------------------------
def test_setup_span_recorded_without_tracer_newest_kept_and_bounded():
    assert obs_trace.get_tracer() is None
    with obs_trace.setup_span("test.setup.a", n=1) as s:
        s.set(m=np.int64(5))
    with obs_trace.setup_span("test.setup.a", n=2):
        pass
    rec = obs_trace.setup_record()
    entry = rec["test.setup.a"]
    assert entry["n"] == 2 and "m" not in entry  # the newest span only
    assert entry["s"] >= 0
    assert list(rec)[-1] == "test.setup.a"
    json.dumps(rec)  # counts are plain numbers
    # a copy: changing it leaves the record as it was
    rec["test.setup.a"]["n"] = 99
    assert obs_trace.setup_record()["test.setup.a"]["n"] == 2

    names = [f"test.setup.bound.{i}" for i in range(obs_trace.SETUP_LIMIT + 5)]
    for name in names:
        with obs_trace.setup_span(name):
            pass
    rec = obs_trace.setup_record()
    assert list(rec) == names[-obs_trace.SETUP_LIMIT:]  # the oldest went


def test_setup_spans_leave_tracer_events_unchanged():
    tracer = obs_trace.enable_tracing()
    with obs_trace.span("fetch_rows", rank=0, n=2):
        with obs_trace.setup_span("test.setup.inside", n=3):
            pass
    with obs_trace.setup_span("test.setup.outside"):
        pass
    obs_trace.disable_tracing()
    assert len(tracer) == 1
    assert [e["name"] for e in tracer.to_chrome()["traceEvents"]
            if e["ph"] == "X"] == ["fetch_rows"]
    assert set(tracer.phase_totals()) == {"fetch_rows"}
    rec = obs_trace.setup_record()
    assert rec["test.setup.inside"]["n"] == 3 and "test.setup.outside" in rec


def test_span_opens_trace_annotation_only_with_tracer(monkeypatch):
    import jax

    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            opened.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with obs_trace.span("fetch_rows", rank=1):
        pass
    with obs_trace.setup_span("test.setup.quiet"):
        pass
    assert opened == []  # no tracer: no annotation

    obs_trace.enable_tracing()
    with obs_trace.span("stream_batch"):
        with obs_trace.span("fetch_rows", rank=1):
            pass
    with obs_trace.setup_span("test.setup.loud"):
        pass
    obs_trace.disable_tracing()
    assert opened == [("enter", "stream_batch"), ("enter", "fetch_rows"),
                      ("exit", "fetch_rows"), ("exit", "stream_batch"),
                      ("enter", "test.setup.loud"),
                      ("exit", "test.setup.loud")]


def test_compile_listener_counts_cache_miss_then_hit(tmp_path, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.chip import enable_compile_cache

    # with the variable set, enable_compile_cache leaves the config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    enable_compile_cache()  # the listener is installed once
    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1,
            "jax_enable_compilation_cache": True}
    old = {k: getattr(jax.config, k) for k in keys}

    def make():
        def triple_plus_one(x):
            return x * 3 + 1
        return triple_plus_one

    def counts():
        rec = obs_trace.setup_record()
        lower = rec.get("setup.lower", {})
        comp = rec.get("setup.compile", {})
        return (lower.get("n", 0), lower.get("s", 0.0), comp.get("n", 0),
                comp.get("cache_misses", 0), comp.get("cache_hits", 0))

    x = np.arange(8, dtype=np.float32)
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        seen = [counts()]
        for _ in range(2):  # a new function object each time: no in-memory hit
            jax.jit(make()).lower(x).compile()
            seen.append(counts())
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    (l0, s0, c0, m0, h0), (l1, s1, c1, m1, h1), (l2, s2, c2, m2, h2) = seen
    assert (l1 - l0, c1 - c0, m1 - m0, h1 - h0) == (1, 1, 1, 0)  # a miss
    assert (l2 - l1, c2 - c1, m2 - m1, h2 - h1) == (1, 1, 0, 1)  # then a hit
    assert s2 > s1 > s0


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------
def test_registry_semantics_and_snapshot_roundtrip(tmp_path):
    reg = MetricRegistry()
    reg.counter("hits", 2, rank=0, tier="host_cache")
    reg.counter("hits", 3, rank=0, tier="host_cache")  # counters add
    reg.counter("hits", 5, rank=1, tier="host_cache")
    reg.gauge("load_imbalance", 2.0, tier="host")
    reg.gauge("load_imbalance", 1.5, tier="host")  # gauges overwrite
    reg.observe("latency_s", [0.1, 0.2, 0.3], tier="serving")
    assert reg.get_counter("hits", rank=0, tier="host_cache") == 5
    assert reg.total("hits", tier="host_cache") == 10
    assert reg.total("hits", rank=1) == 5
    assert reg.get_gauge("load_imbalance", tier="host") == 1.5
    assert reg.get_gauge("nope") is None
    assert reg.ranks() == [0, 1]

    path = tmp_path / "m.json"
    reg.save(str(path))
    snap = load_snapshot(str(path))
    assert snap == reg.to_dict()
    (h,) = snap["histograms"]
    assert h["count"] == 3 and h["sum"] == pytest.approx(0.6)
    assert h["p50"] == pytest.approx(0.2)  # 'lower': an observed value

    bad = tmp_path / "bad.json"
    bad.write_text('{"schema": "other/v9"}')
    with pytest.raises(ValueError):
        load_snapshot(str(bad))


def test_imbalance_definition():
    assert imbalance([3, 3, 3, 3]) == 1.0
    assert imbalance([4, 0, 0, 0]) == 4.0
    assert imbalance([]) == 0.0
    assert imbalance([0, 0]) == 0.0  # no load => 0, not NaN


# ---------------------------------------------------------------------------
# stat merges (the aggregation primitives the adapters lean on)
# ---------------------------------------------------------------------------
def test_merge_cache_stats_empty_list_is_zero():
    merged = merge_cache_stats([])
    assert merged == CacheStats()
    for f in dataclasses.fields(CacheStats):
        assert getattr(merged, f.name) == 0


def test_merge_cache_stats_single_rank_is_identity():
    one = CacheStats(gets=7, hits=4, misses=3, bytes_hit=64)
    merged = merge_cache_stats([one])
    assert merged == one
    assert merged is not one  # a fresh aggregate, not the input


def test_merge_mixed_zero_and_nonzero_counters():
    merged = merge_cache_stats([
        CacheStats(),
        CacheStats(gets=5, hits=5, bytes_hit=10),
        CacheStats(gets=2, misses=2, comm_time=0.5),
        CacheStats(),
    ])
    assert (merged.gets, merged.hits, merged.misses) == (7, 5, 2)
    assert merged.bytes_hit == 10
    assert merged.comm_time == pytest.approx(0.5)


def test_merge_counter_dataclasses_covers_every_provider_field():
    a = ProviderStats(local_reads=1, remote_reads=2, cache_hits=1,
                      cache_misses=1, bytes_fetched=100, modeled_comm_s=0.1,
                      tenant_requests={"t0": 3}, tenant_bytes_fetched={"t0": 64})
    b = ProviderStats(local_reads=4, device_hits=3, bytes_fetched=50,
                      tenant_requests={"t0": 1, "t1": 2})
    merged = merge_counter_dataclasses(ProviderStats, [a, b])
    for f in dataclasses.fields(ProviderStats):
        va, vb, vm = (getattr(x, f.name) for x in (a, b, merged))
        if isinstance(va, dict):
            expect = dict(va)
            for k, v in vb.items():
                expect[k] = expect.get(k, 0) + v
            assert vm == expect, f.name
        else:
            assert vm == va + vb, f.name


def test_aggregate_stats_equals_per_rank_sums_p4():
    rt, store = _runtime(p=4)
    for rank in range(4):
        rt.fetch_rows(rank, range(store.n))
    agg = rt.aggregate_stats()
    for f in dataclasses.fields(ProviderStats):
        vals = [getattr(s, f.name) for s in rt.stats]
        if isinstance(vals[0], dict):
            want = {}
            for d in vals:
                for k, v in d.items():
                    want[k] = want.get(k, 0) + v
            assert getattr(agg, f.name) == want, f.name
        else:
            assert getattr(agg, f.name) == pytest.approx(sum(vals)), f.name
    cagg = rt.merged_cache_stats()
    for f in dataclasses.fields(CacheStats):
        want = sum(getattr(c.stats, f.name) for c in rt.caches)
        assert getattr(cagg, f.name) == pytest.approx(want), f.name


# ---------------------------------------------------------------------------
# adapters + validator on a real runtime
# ---------------------------------------------------------------------------
def _fake_ledger(rt, *, bytes_off=0):
    return types.SimpleNamespace(
        rows_shipped=np.asarray(rt.serve_rows, np.int64),
        bytes_payload=sum(s.bytes_fetched for s in rt.stats) + bytes_off,
        bytes_on_wire=10_000,
        n_collectives=2,
        n_pairs=11,
        device_wall_s=0.01,
    )


def test_record_runtime_snapshot_satisfies_invariants():
    rt, store = _runtime(p=4)
    for rank in range(4):
        rt.fetch_rows(rank, range(0, store.n, 1 + rank))
    reg = MetricRegistry()
    record_runtime(reg, rt)
    snap = reg.to_dict()
    assert validate_metrics(snap) == []
    assert reg.get_gauge("load_imbalance", tier="host") > 0
    assert reg.get_gauge("serve_matrix_skew", tier="wire") > 0
    # the anchor: every row each rank asked for is accounted once
    assert reg.total("row_requests", tier="host") == sum(
        s.local_reads + s.remote_reads for s in rt.stats
    )


def test_reconciliation_agreement_and_mismatch():
    rt, store = _runtime(p=4)
    for rank in range(4):
        rt.fetch_rows(rank, range(store.n))

    reg = MetricRegistry()
    record_runtime(reg, rt)
    record_collective_ledger(reg, _fake_ledger(rt))
    record_reconciliation(reg, rt, _fake_ledger(rt))
    assert reg.get_gauge("rma_agreement", tier="wire") == 1.0
    assert validate_metrics(reg.to_dict()) == []

    reg2 = MetricRegistry()
    record_runtime(reg2, rt)
    record_collective_ledger(reg2, _fake_ledger(rt, bytes_off=8))
    record_reconciliation(reg2, rt, _fake_ledger(rt, bytes_off=8))
    assert reg2.get_gauge("rma_agreement", tier="wire") == 0.0
    bad = validate_metrics(reg2.to_dict())
    assert any("rma_bytes" in m for m in bad)
    assert any("rma_agreement" in m for m in bad)


def test_reconciliation_without_ledger_records_nothing():
    rt, _ = _runtime(p=2)
    reg = MetricRegistry()
    record_reconciliation(reg, rt, None)
    assert reg.get_gauge("rma_agreement", tier="wire") is None


def test_fold_trace_adds_the_time_dimension():
    tracer = obs_trace.enable_tracing()
    with obs_trace.span("all_to_all", payload_bytes=64):
        pass
    with obs_trace.span("all_to_all", payload_bytes=36):
        pass
    obs_trace.disable_tracing()
    reg = MetricRegistry()
    fold_trace(reg, tracer)
    assert reg.get_counter("phase_calls", phase="all_to_all") == 2
    assert reg.get_counter("phase_bytes", phase="all_to_all") == 100
    assert reg.get_counter("phase_time_s", phase="all_to_all") > 0


# ---------------------------------------------------------------------------
# latency recorder: division guards + per-class breakdowns
# ---------------------------------------------------------------------------
def test_empty_recorder_rates_are_zero_not_nan():
    s = LatencyRecorder().summary()
    assert s.count == 0
    assert s.shed_rate == 0.0
    assert s.throughput_qps == 0.0


def test_zero_wall_reports_zero_throughput():
    rec = LatencyRecorder()
    rec.record(0.010)
    s = rec.summary()
    assert s.wall_s == 0.0
    assert s.throughput_qps == 0.0  # "unknown", not served / 1e-12


def test_per_class_latency_and_shed_breakdown():
    rec = LatencyRecorder()
    for ms in (1, 2, 3):
        rec.record(ms * 1e-3, cls="lcc")
    rec.record(9e-3, cls="count")
    rec.record(5e-3)  # unclassified: overall only
    rec.record_shed("deadline", 2, cls="count")
    rec.record_wall(0.5)

    assert rec.classes() == ["count", "lcc"]
    by = rec.by_class()
    assert len(by["lcc"]) == 3 and by["count"] == [9e-3]
    by["lcc"].append(99.0)  # defensive copy: must not leak back
    assert len(rec.by_class()["lcc"]) == 3

    overall = rec.summary()
    assert overall.count == 5
    assert overall.shed == 2
    assert overall.shed_rate == pytest.approx(2 / 7)

    per = rec.summary_by_class()
    assert per["lcc"].count == 3 and per["lcc"].shed == 0
    assert per["count"].count == 1 and per["count"].shed == 2
    assert per["count"].shed_rate == pytest.approx(2 / 3)
    # wall clock is shared across classes: no per-class throughput claim
    assert per["lcc"].wall_s == 0.0 and per["lcc"].throughput_qps == 0.0


def test_provider_hit_rate_division_guards():
    st = ProviderStats()
    assert st.hit_rate == 0.0
    assert st.remote_hit_rate == 0.0
    st = ProviderStats(remote_reads=10, cache_hits=6, cache_misses=2,
                       device_hits=2)
    assert st.hit_rate == pytest.approx(6 / 8)  # of host-cache lookups
    assert st.remote_hit_rate == pytest.approx(8 / 10)  # either tier


# ---------------------------------------------------------------------------
# validator negative paths
# ---------------------------------------------------------------------------
def test_validator_rejects_overlapping_spans():
    trace = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 0, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 0, "tid": 1},
    ]}
    bad = validate_trace(trace)
    assert len(bad) == 1 and "overlaps" in bad[0]
    # same intervals on different lanes are fine (ranks run concurrently)
    trace["traceEvents"][1]["tid"] = 2
    assert validate_trace(trace) == []


def test_validator_requires_ts_except_on_metadata():
    trace = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
         "args": {"name": "x"}},
        {"name": "a", "ph": "X", "dur": 1.0, "pid": 0, "tid": 1},
    ]}
    bad = validate_trace(trace)
    assert len(bad) == 1 and "'a'" in bad[0] and "ts" in bad[0]


def test_validator_flags_unbalanced_host_counters():
    rt, store = _runtime(p=2)
    rt.fetch_rows(0, range(store.n))
    reg = MetricRegistry()
    record_runtime(reg, rt)
    reg.counter("cache_misses", 1, rank=0, tier="host")  # cook the books
    bad = validate_metrics(reg.to_dict())
    assert any("remote row requests" in m for m in bad)
