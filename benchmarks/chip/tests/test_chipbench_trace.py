"""The trace reduction, on traces recorded on a TPU v5e and on a small
synthetic one."""
import json
from pathlib import Path

import pytest

import _paths  # noqa: F401
from benchmarks.chip import devtrace
from benchmarks.chip.counting import epoch_compares
from benchmarks.chip.harness import RunRecord, load_reader

DATA = Path(__file__).parent / "data"


def _recorded(name):
    return devtrace.Trace([devtrace.Event(*e) for e in
                           json.loads((DATA / name).read_text())])


def test_recorded_epoch_trace():
    # one S14 epoch (16 rounds) traced on a TPU v5e: the numbers the run
    # itself reported for this trace
    tr = _recorded("epoch_trace.json")
    assert tr.devices == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(10.668186123, rel=1e-12)
    assert tr.busy_s == pytest.approx(10.663170112, rel=1e-9)
    assert tr.idle_share == pytest.approx(0.047018405, rel=1e-6)
    top = tr.top_ops()
    assert top[0][0] == "%convert_reduce_fusion.2"
    assert not any(name.startswith("%while") for name, _ in top)
    stats = {"module_name": "jit__shard_body", "epochs": 1,
             "compares_per_epoch": epoch_compares(426560, 3618)}
    gcps = load_reader("compare_gcps.epoch")(RunRecord(None, stats, tr))
    assert gcps == pytest.approx(523.6376183201586, rel=1e-9)
    idle = load_reader("idle_share.epoch")(RunRecord(None, stats, tr))
    assert idle == tr.idle_share


def test_recorded_epoch_idle_gaps():
    # every idle nanosecond of the window is attributed once, to the
    # bench.* span covering it or to no span
    tr = _recorded("epoch_trace.json")
    gaps = tr.idle_gaps(k=100)
    assert sum(s for _, s in gaps) == pytest.approx(tr.window_s - tr.busy_s,
                                                    rel=1e-9)
    assert {n for n, _ in gaps} <= {"bench.epoch", devtrace.NO_SPAN}
    assert len(tr.idle_gaps(k=1)) == 1


def _ev(plane, line, name, start, dur):
    return devtrace.Event(plane, line, name, float(start), float(dur))


def test_synthetic_union_and_gaps():
    dev, host = "/device:TPU:0", "/host:CPU"
    tr = devtrace.Trace([
        _ev(host, "python3", "bench.window", 0, 100),
        _ev(host, "python3", "bench.flush", 10, 30),   # 10..40
        _ev(host, "python3", "bench.wait", 40, 60),    # 40..100
        _ev(dev, "XLA Ops", "%a = s32[] add()", 5, 10),      # 5..15
        _ev(dev, "XLA Ops", "%b = s32[] mul()", 12, 8),      # 12..20 (overlaps a)
        _ev(dev, "XLA Ops", "%a = s32[] add()", 50, 10),     # 50..60
        _ev(dev, "XLA Ops", "%c = s32[] sub()", 95, 20),     # 95..115, clipped
        _ev(dev, "XLA Modules", "jit_f(1)", 5, 15),
    ])
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s == pytest.approx((15 + 10 + 5) * 1e-9)
    assert tr.idle_share == pytest.approx(70.0)
    assert dict(tr.top_ops()) == pytest.approx(
        {"%a": 20e-9, "%b": 8e-9, "%c": 5e-9})
    # gaps: 0..5 (no span), 20..40 (flush), 40..50 and 60..95 (wait)
    assert dict(tr.idle_gaps()) == pytest.approx(
        {devtrace.NO_SPAN: 5e-9, "bench.flush": 20e-9, "bench.wait": 45e-9})
    assert [e.name for e in tr.modules()] == ["jit_f(1)"]


def test_trace_without_device():
    tr = devtrace.Trace([_ev("/host:CPU", "python3", "bench.window", 0, 10)])
    assert tr.busy_s == 0.0 and tr.idle_share is None
    assert load_reader("idle_share.epoch")(RunRecord(None, {}, tr)) is None
    with pytest.raises(RuntimeError):
        devtrace.Trace([])


def test_load_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.epoch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.Trace(devtrace.load(str(tmp_path)))
    assert tr.window_s > 0
    assert [e.name for e in tr.host_spans("bench.epoch")] == ["bench.epoch"]
