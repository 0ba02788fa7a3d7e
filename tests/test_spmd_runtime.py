"""SPMD execution of the sharded runtime's rank views.

In-process: the executor vs a numpy oracle and the loop-vs-spmd
field-for-field property at p=1 (the suite sees one device). Multi
device: a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(jax pins the device count at first init, and the rest of the suite must
see 1 device) runs the same property at p in {4, 8}, with and without
the device-resident tier — answers, per-rank cache stats, serve matrix,
coherence ledgers, and residency stats must all agree, and the measured
collective traffic must equal the modeled serve-matrix delta.
"""
import dataclasses
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --------------------------------------------------------------------------
# executor vs oracle (p=1 in-process)
# --------------------------------------------------------------------------
class _FakeStore:
    def __init__(self, rows):
        self.rows = rows

    def row(self, v):
        return self.rows[int(v)]


@pytest.mark.parametrize("use_kernel", [False, True])
def test_executor_matches_oracle_p1(use_kernel):
    from repro.core.partition import partition_1d
    from repro.distributed.spmd_runtime import (
        ShardWork,
        SpmdIntersectExecutor,
    )

    rng = np.random.default_rng(3)
    n = 32
    rows = {
        v: np.sort(
            rng.choice(n, size=int(rng.integers(0, 9)), replace=False)
        ).astype(np.int32)
        for v in range(n)
    }
    store = _FakeStore(rows)
    part = partition_1d(n, 1)
    a = rng.integers(0, n, size=20).astype(np.int64)
    b = rng.integers(0, n, size=20).astype(np.int64)
    held = {int(v): rows[int(v)] for v in np.unique(np.concatenate([a, b]))}
    ex = SpmdIntersectExecutor(part, n, use_kernel=use_kernel)
    counts, unit = ex.run(
        [ShardWork(0, a, b, held)], store
    )
    want = np.array(
        [
            len(np.intersect1d(rows[int(x)], rows[int(y)]))
            for x, y in zip(a, b)
        ],
        np.int64,
    )
    assert np.array_equal(counts[0], want)
    assert unit.rows_shipped.sum() == 0  # p=1: nothing is remote


def test_executor_empty_unit_is_free():
    from repro.core.partition import partition_1d
    from repro.distributed.spmd_runtime import (
        ShardWork,
        SpmdIntersectExecutor,
    )

    part = partition_1d(16, 1)
    ex = SpmdIntersectExecutor(part, 16)
    z = np.zeros(0, np.int64)
    counts, unit = ex.run([ShardWork(0, z, z, {})], _FakeStore({}))
    assert counts[0].size == 0 and unit.n_collectives == 0


def test_ensure_host_devices_preserves_existing_flags(monkeypatch):
    from repro.distributed.spmd_runtime import ensure_host_devices

    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=7")
    ensure_host_devices(1)
    flags = os.environ["XLA_FLAGS"]
    assert "--xla_foo=7" in flags  # user flag survived
    assert "--xla_force_host_platform_device_count=1" in flags
    # an explicit external device-count directive always wins
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1 --xla_bar=2"
    )
    ensure_host_devices(1)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_force_host_platform_device_count=1 --xla_bar=2"
    )


def test_ensure_host_devices_parses_existing_value(monkeypatch):
    """An externally pinned device count is parsed, not just detected:
    a larger pin satisfies the request untouched; a smaller pin fails
    early with a message naming the conflicting value."""
    from repro.distributed.spmd_runtime import ensure_host_devices

    # larger external pin: honored verbatim (no second directive
    # appended, no override) — the suite's jax is already pinned to one
    # device, so probe non-strict
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    ensure_host_devices(4, strict=False)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_force_host_platform_device_count=8"
    )
    # smaller external pin: early, specific error naming the pinned
    # value (not a late generic jax device shortage)
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_bar=1 --xla_force_host_platform_device_count=2"
    )
    with pytest.raises(RuntimeError, match=r"pins.*=2.*smaller"):
        ensure_host_devices(4)
    assert os.environ["XLA_FLAGS"].count(
        "--xla_force_host_platform_device_count"
    ) == 1
    # whitespace around '=' still parses as an existing directive
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count = 16"
    )
    ensure_host_devices(1)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_force_host_platform_device_count = 16"
    )


def test_ensure_host_devices_counts_chips_on_a_tpu(monkeypatch):
    """On an accelerator the host-device flag shapes nothing: the
    environment is left as it was, the chips themselves are counted, and
    a shortage names the platform."""
    from repro.distributed import spmd_runtime

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(spmd_runtime.jax, "devices", lambda: [chip])
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=7")
    assert spmd_runtime.ensure_host_devices(1) == 1
    assert os.environ["XLA_FLAGS"] == "--xla_foo=7"
    with pytest.raises(RuntimeError, match="this tpu host has 1"):
        spmd_runtime.ensure_host_devices(4)
    monkeypatch.delenv("XLA_FLAGS")
    assert spmd_runtime.ensure_host_devices(4, strict=False) == 1
    assert "XLA_FLAGS" not in os.environ


# --------------------------------------------------------------------------
# property: loop-mode and spmd-mode executions agree field-for-field
# --------------------------------------------------------------------------
def _provider_stats(runtime):
    return [dataclasses.asdict(s) for s in runtime.stats]


def _device_stats(runtime):
    """Per-view residency stats (replicated: one view with rank=-1;
    per_rank scope: one view per rank)."""
    return [
        (dv.rank, dataclasses.asdict(dv.stats))
        for dv in runtime.device_views()
    ]


def _ledger_dict(led):
    """CollectiveLedger as comparable counters — wall-clock fields are
    timing, not semantics, so they are excluded from equality."""
    d = led.to_dict()
    d.pop("device_wall_s", None)
    d.pop("overlap_wait_s", None)
    return d


def _run_serving(
    execution, p, seed, device_slots=0, pipeline=False,
    device_scope="replicated",
):
    from repro.graphs.rmat import rmat_graph
    from repro.serving import LiveQueryService
    from repro.serving.workload import read_write_stream

    csr = rmat_graph(7, 8, seed=seed)
    svc = LiveQueryService(
        csr,
        p=p,
        cross_rank=True,
        execution=execution,
        device_slots=device_slots,
        device_width=256,
        pipeline=pipeline,
        device_scope=device_scope,
    )
    results = []
    for ev in read_write_stream(
        lambda: svc.store.degrees,
        csr.n,
        n_events=10,
        write_frac=0.3,
        queries_per_event=24,
        updates_per_event=24,
        kind="zipf",
        seed=seed,
    ):
        if ev.is_update:
            svc.apply_updates(ev.update)
        else:
            results.extend(svc.scheduler.run(ev.queries))
    svc.verify()
    return svc, results


def _results_agree(r_l, r_s):
    assert len(r_l) == len(r_s) and len(r_l) > 0
    for a, b in zip(r_l, r_s):
        assert a.query == b.query and a.value == b.value
        assert (a.ids is None) == (b.ids is None)
        if a.ids is not None:
            assert np.array_equal(a.ids, b.ids)


def _serving_agrees(p, seed, device_slots=0, device_scope="replicated"):
    svc_l, r_l = _run_serving(
        "loop", p, seed, device_slots, device_scope=device_scope
    )
    svc_s, r_s = _run_serving(
        "spmd", p, seed, device_slots, device_scope=device_scope
    )
    _results_agree(r_l, r_s)
    # per-rank cache stats, serve matrix, coherence ledger: identical
    assert _provider_stats(svc_l.runtime) == _provider_stats(svc_s.runtime)
    assert np.array_equal(svc_l.runtime.serve_rows, svc_s.runtime.serve_rows)
    assert (
        svc_l.runtime.invalidations_sent == svc_s.runtime.invalidations_sent
    )
    assert svc_l.engine.n_pairs_total == svc_s.engine.n_pairs_total
    assert svc_l.engine.n_pairs_raw == svc_s.engine.n_pairs_raw
    assert svc_l.engine.n_pairs_resident == svc_s.engine.n_pairs_resident
    if device_slots:
        assert _device_stats(svc_l.runtime) == _device_stats(svc_s.runtime)
    # measured collective traffic == modeled serve matrix (cumulative)
    led = svc_s.engine.spmd.ledger
    assert np.array_equal(led.rows_shipped, svc_s.runtime.serve_rows)
    assert led.bytes_payload == sum(
        s.bytes_fetched for s in svc_s.runtime.stats
    )
    return True


def _serving_pipeline_agrees(p, seed, device_slots=0):
    """Pipelined (double-buffered windows) SPMD serving is bit-exact vs
    the unpipelined SPMD path, ledger field-for-field included."""
    svc_u, r_u = _run_serving("spmd", p, seed, device_slots)
    svc_p, r_p = _run_serving(
        "spmd", p, seed, device_slots, pipeline=True
    )
    _results_agree(r_u, r_p)
    assert _provider_stats(svc_u.runtime) == _provider_stats(svc_p.runtime)
    assert np.array_equal(svc_u.runtime.serve_rows, svc_p.runtime.serve_rows)
    assert svc_u.engine.n_pairs_total == svc_p.engine.n_pairs_total
    assert svc_u.engine.n_pairs_resident == svc_p.engine.n_pairs_resident
    assert _ledger_dict(svc_u.engine.spmd.ledger) == (
        _ledger_dict(svc_p.engine.spmd.ledger)
    )
    return True


def _run_streaming(
    execution, p, seed, device_slots=0, pipeline=False,
    device_scope="replicated",
):
    from repro.graphs.rmat import rmat_stream
    from repro.streaming import StreamingCacheCoherence, StreamingLCCEngine

    n = 1 << 7
    coh = StreamingCacheCoherence(
        n, np.zeros(n, np.int64), p=p, cache_rows=32
    )
    eng = StreamingLCCEngine.empty(
        n, coherence=coh, execution=execution, pipeline=pipeline
    )
    if device_slots:
        eng.runtime.enable_device_tier(device_slots, 256, scope=device_scope)
    batch_results = []
    for batch in rmat_stream(
        7, 8, batch_size=256, delete_frac=0.2, seed=seed
    ):
        batch_results.append(eng.apply_batch(batch))
    eng.verify()
    return eng, batch_results

def _streaming_agrees(p, seed, device_slots=0, device_scope="replicated"):
    e_l, br_l = _run_streaming(
        "loop", p, seed, device_slots, device_scope=device_scope
    )
    e_s, br_s = _run_streaming(
        "spmd", p, seed, device_slots, device_scope=device_scope
    )
    assert br_l == br_s  # BatchResult dataclasses, field-for-field
    assert np.array_equal(e_l.t, e_s.t)
    assert np.array_equal(e_l.lcc, e_s.lcc)
    assert np.array_equal(e_l.shard_pairs, e_s.shard_pairs)
    assert e_l.oo_host_rows == e_s.oo_host_rows
    assert e_l.oo_host_bytes == e_s.oo_host_bytes
    assert e_l.oo_resident_pairs == e_s.oo_resident_pairs
    assert _provider_stats(e_l.runtime) == _provider_stats(e_s.runtime)
    if device_slots:
        assert _device_stats(e_l.runtime) == _device_stats(e_s.runtime)
    assert e_s.spmd.ledger.n_pairs == e_s.delta_pairs_total
    return True


def _streaming_pipeline_agrees(p, seed, device_slots=0):
    """Pipelined SPMD streaming (overlapped delete/insert phase
    dispatches) is bit-exact vs the unpipelined SPMD path."""
    e_u, br_u = _run_streaming("spmd", p, seed, device_slots)
    e_p, br_p = _run_streaming(
        "spmd", p, seed, device_slots, pipeline=True
    )
    assert br_u == br_p
    assert np.array_equal(e_u.t, e_p.t)
    assert np.array_equal(e_u.lcc, e_p.lcc)
    assert np.array_equal(e_u.shard_pairs, e_p.shard_pairs)
    assert _provider_stats(e_u.runtime) == _provider_stats(e_p.runtime)
    assert _ledger_dict(e_u.spmd.ledger) == _ledger_dict(e_p.spmd.ledger)
    return True


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_serving_loop_vs_spmd_p1(seed):
    assert _serving_agrees(1, seed)


@pytest.mark.parametrize("seed", [0, 1])
def test_streaming_loop_vs_spmd_p1(seed):
    assert _streaming_agrees(1, seed)


def test_streaming_loop_vs_spmd_p1_device_tier():
    assert _streaming_agrees(1, 0, device_slots=32)


@pytest.mark.parametrize("seed", [0, 1])
def test_serving_pipeline_p1(seed):
    assert _serving_pipeline_agrees(1, seed)


@pytest.mark.parametrize("seed", [0, 1])
def test_streaming_pipeline_p1(seed):
    assert _streaming_pipeline_agrees(1, seed)


def test_serving_loop_vs_spmd_p1_device_per_rank():
    assert _serving_agrees(1, 0, device_slots=32, device_scope="per_rank")


# --------------------------------------------------------------------------
# resident buffer: steady-state reuse and invalidation
# --------------------------------------------------------------------------
def test_resident_buffer_reuse_and_invalidation():
    """Re-running a unit over the same rows uploads only what changed:
    the second unit's rows come from the resident device buffer
    (upload_bytes_saved > 0, few patches), and an invalidate() forces a
    re-upload whose counts track the mutated store, not the stale
    mirror."""
    from repro.core.partition import partition_1d
    from repro.distributed.spmd_runtime import (
        ShardWork,
        SpmdIntersectExecutor,
    )

    rng = np.random.default_rng(11)
    n = 32
    rows = {
        v: np.sort(
            rng.choice(n, size=int(rng.integers(1, 9)), replace=False)
        ).astype(np.int32)
        for v in range(n)
    }
    store = _FakeStore(rows)
    part = partition_1d(n, 1)
    a = rng.integers(0, n, size=24).astype(np.int64)
    b = rng.integers(0, n, size=24).astype(np.int64)
    held = {int(v): rows[int(v)] for v in np.unique(np.concatenate([a, b]))}

    def oracle():
        return np.array(
            [
                len(np.intersect1d(rows[int(x)], rows[int(y)]))
                for x, y in zip(a, b)
            ],
            np.int64,
        )

    ex = SpmdIntersectExecutor(part, n)
    counts1, unit1 = ex.run([ShardWork(0, a, b, held)], store)
    assert np.array_equal(counts1[0], oracle())
    assert unit1.bytes_uploaded > 0  # cold: everything ships
    assert unit1.upload_bytes_saved == 0

    counts2, unit2 = ex.run([ShardWork(0, a, b, held)], store)
    assert np.array_equal(counts2[0], oracle())
    assert unit2.upload_bytes_saved > 0  # warm: resident rows reused
    assert unit2.bytes_uploaded == 0  # nothing changed -> no patch
    assert unit2.upload_bytes_saved == unit1.bytes_uploaded

    # mutate one row in place (same width — the sharpest case: the
    # buffer cannot tell from geometry alone, only invalidate() marks
    # it stale)
    v = int(a[0])
    old = rows[v]
    new = old
    while np.array_equal(new, old):
        new = np.sort(
            rng.choice(n, size=old.size, replace=False)
        ).astype(np.int32)
    rows[v] = new
    held[v] = new
    ex.invalidate([v])
    counts3, unit3 = ex.run([ShardWork(0, a, b, held)], store)
    assert np.array_equal(counts3[0], oracle())  # fresh, not stale
    assert unit3.bytes_uploaded == new.size * 4  # only the one patch
    assert unit3.n_patches == 1


# --------------------------------------------------------------------------
# multi-device: the same property at p in {4, 8} on 8 host devices
# --------------------------------------------------------------------------
MULTIDEV_SCRIPT = r"""
from repro.distributed.spmd_runtime import ensure_host_devices
ensure_host_devices(8)  # preserves external XLA_FLAGS; must precede jax init
import json
import sys
sys.path.insert(0, {test_dir!r})
from test_spmd_runtime import _serving_agrees, _streaming_agrees

from test_spmd_runtime import (
    _serving_pipeline_agrees,
    _streaming_pipeline_agrees,
)

out = {{}}
for p in (4, 8):
    out[f"serving_p{{p}}"] = _serving_agrees(p, seed=0)
    out[f"streaming_p{{p}}"] = _streaming_agrees(p, seed=0)
    out[f"serving_p{{p}}_pipeline"] = _serving_pipeline_agrees(p, seed=0)
    out[f"streaming_p{{p}}_pipeline"] = _streaming_pipeline_agrees(p, seed=0)
out["serving_p4_seed1"] = _serving_agrees(4, seed=1)
out["streaming_p4_seed1"] = _streaming_agrees(4, seed=1)
out["serving_p4_device"] = _serving_agrees(4, seed=0, device_slots=32)
out["streaming_p4_device"] = _streaming_agrees(4, seed=0, device_slots=32)
out["serving_p4_device_per_rank"] = _serving_agrees(
    4, seed=0, device_slots=32, device_scope="per_rank"
)
print(json.dumps(out))
"""


def test_multidevice_loop_vs_spmd():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    script = MULTIDEV_SCRIPT.format(
        test_dir=os.path.dirname(os.path.abspath(__file__))
    )
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res and all(res.values()), res
