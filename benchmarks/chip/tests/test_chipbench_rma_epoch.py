"""The four-chip cell ``g500-s17-p4.rma-epoch`` on the CPU: runs through
the harness on four forced host devices at a small size (correct, and
incorrect with one fetched row's id changed), and its four readers on a
synthetic four-device trace."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _paths
from benchmarks.chip import devtrace, exchange, harness

CELL = "g500-s17-p4.rma-epoch"
SEED = 2**33 + 5  # the driver's seeds pass 32 signed bits
READERS = ["exchange_fill.rma", "cache_hit_share.rma", "exchange_s.rma",
           "ici_share.rma"]

# scale 10 with 16 cache rows: rows fetched in two classes, hub rows
# served from the cache
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import dataclasses, json, sys, time
sys.path[:0] = [ROOT, ROOT + "/src"]
import numpy as np
import jax
from benchmarks.chip import harness
from repro.core import async_engine, rma

spec = json.loads(open(ROOT + "/BENCHMARK.json").read())
for c in spec["configs"]:
    cfg = json.loads(open(ROOT + "/" + c["file"]).read())
    cfg["scale"] = 10
    cfg["engine"]["cache_rows"] = min(cfg["engine"].get("cache_rows", 0), 16)
    c["file"] = os.path.join(TMP, c["name"] + ".json")
    open(c["file"], "w").write(json.dumps(cfg))
open(os.path.join(TMP, "BENCHMARK.json"), "w").write(json.dumps(spec))


def cpu_devices(chips):
    d = jax.devices()
    assert len(d) >= chips
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def run():
    return harness.run_cell(CELL, SEED, 0.5, False,
                            t_start=time.perf_counter(), devices=cpu_devices,
                            root=__import__("pathlib").Path(TMP))


out = {"clean": run()}
layout = rma.class_layout


def one_fetched_row_changed(prob):
    # the first row device 0 serves device 1 becomes the next of device
    # 0's rows
    lay = layout(prob)
    idx = lay.fetch_idx.copy()
    r, j = np.argwhere(idx[0, :, 1] < prob.n_loc)[0]
    idx[0, r, 1, j] = (idx[0, r, 1, j] + 1) % (prob.n_loc - 1)
    return dataclasses.replace(lay, fetch_idx=idx)


async_engine.class_layout = one_fetched_row_changed
out["fault"] = run()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    tmp = tmp_path_factory.mktemp("root")
    p = subprocess.run(
        [sys.executable, "-c",
         f"ROOT = {str(_paths.ROOT)!r}\nTMP = {str(tmp)!r}\nCELL = {CELL!r}\n"
         f"SEED = {SEED}\n" + SCRIPT],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_runs_correct_on_four_devices(runs):
    r = runs["clean"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"epoch_s", "setup_s"}
    assert r["device"]["count"] == 4
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


def test_one_fetched_row_changed_reads_incorrect(runs):
    r = runs["fault"]
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0
    assert r["checks"]["tri_mismatch"]["value"] > 0


def _problem():
    """A p=4 schedule with fetched rows and cache rows; its set-up span
    ``setup.schedule`` is the newest in the program's record."""
    from repro.core.cache import build_static_degree_cache
    from repro.core.rma import build_sharded_problem, schedule_counts
    from repro.graphs.rmat import rmat_graph

    csr = rmat_graph(10, 16, seed=0)
    prob = build_sharded_problem(
        csr, 4, n_rounds=2, cache=build_static_degree_cache(csr.degrees, 8))
    return prob, schedule_counts(prob, csr.degrees)


def _ev(dev, name, start, dur):
    return devtrace.Event(f"/device:TPU:{dev}", devtrace.OP_LINE, name,
                          float(start), float(dur))


def _four_device_trace(bytes_per_epoch, epochs, peak_bytes_per_s):
    """A 4-device window of ``epochs`` epochs: device 0 exchanges with
    split start/done ops at exactly the interconnect peak, device 1 with
    a shorter synchronous op, devices 2-3 with one op each; ops named
    after the exchange that are not one (the reshape that feeds it, a
    fusion that reads it) do not count."""
    ns = bytes_per_epoch / peak_bytes_per_s * 1e9  # one epoch's exchange
    shape = "s32[4,1,256]{2,1,0:T(1,128)S(1)}"
    evs = [devtrace.Event("/host:CPU", "python3", devtrace.WINDOW_SPAN,
                          0.0, 1e12)]
    for e in range(epochs):
        t = 1e9 + e * 1e10
        evs += [
            _ev(0, f"%all_to_all.15 = {shape} reshape(%pad_add_fusion.2)",
                t - 100, 100),
            _ev(0, f"%all-to-all-start.1 = (({shape}), {shape}) "
                   f"all-to-all-start({shape} %all_to_all.15)", t, ns / 4),
            _ev(0, f"%fusion.7 = s32[8]{{0}} fusion(%all_to_all.15)",
                t + ns / 4, ns / 2),
            _ev(0, f"%all-to-all-done.1 = {shape} all-to-all-done("
                   f"(({shape}), {shape}) %all-to-all-start.1)",
                t + 3 * ns / 4, ns / 4),
            _ev(0, f"%copy_bitcast_fusion.2 = s32[4,256] fusion({shape} "
                   "%all-to-all-done.1)", t + ns, 50),
            _ev(1, f"%all_to_all.16 = {shape} all-to-all(%all_to_all.15)",
                t, ns / 2),
        ] + [_ev(d, f"%all_to_all.16 = {shape} all-to-all(%x)", t, ns / 3)
             for d in (2, 3)]
    return devtrace.Trace(evs), ns * 1e-9


def test_readers_on_a_four_device_trace():
    prob, counts = _problem()
    epochs = 3
    stats = {"epochs": epochs, "device_kind": "TPU v5 lite",
             "wire_bytes_per_epoch": exchange.wire_bytes(
                 4, prob.n_rounds, _layout_fetch(prob))}
    peaks = json.loads((harness.BENCH_DIR / "peaks.json").read_text())
    peak = peaks["devices"]["TPU v5 lite"]["ici_bits_per_s"] / 8
    tr, epoch_s = _four_device_trace(stats["wire_bytes_per_epoch"], epochs,
                                     peak)
    assert tr.devices == [f"/device:TPU:{d}" for d in range(4)]
    run = harness.RunRecord(None, stats, tr)
    got = {m: harness.load_reader(m)(run) for m in READERS}
    assert got["exchange_fill.rma"] == pytest.approx(
        100.0 * counts["payload_bytes"] / counts["wire_bytes"], rel=1e-12)
    assert got["cache_hit_share.rma"] == pytest.approx(
        100.0 * counts["cache_reads"] / counts["remote_reads"], rel=1e-12)
    assert 0 < got["exchange_fill.rma"] < 100
    assert 0 < got["cache_hit_share.rma"] < 100
    # the busiest chip's start-to-done time, per epoch
    assert got["exchange_s.rma"] == pytest.approx(epoch_s, rel=1e-9)
    # a transfer at the interconnect peak reads 100%, never more
    assert got["ici_share.rma"] == pytest.approx(100.0, rel=1e-9)
    assert got["ici_share.rma"] <= 100.0 * (1 + 1e-12)


def _layout_fetch(prob):
    from repro.core.rma import class_layout

    return class_layout(prob).fetch


def test_wire_bytes_of_the_shapes_match_the_program_counter():
    """The benchmark's byte count from the program's shapes is the
    program's own ``wire_bytes`` counter, and both exceed the payload."""
    prob, counts = _problem()
    assert exchange.wire_bytes(4, prob.n_rounds, _layout_fetch(prob)) == (
        counts["wire_bytes"]) > counts["payload_bytes"] > 0
    assert exchange.wire_bytes(1, 8, ()) == 0


@pytest.mark.parametrize("metric", READERS)
def test_reader_needs_a_device(metric):
    _problem()
    stats = {"epochs": 1, "device_kind": "TPU v5 lite",
             "wire_bytes_per_epoch": 1}
    host_only = devtrace.Trace([devtrace.Event(
        "/host:CPU", "python3", devtrace.WINDOW_SPAN, 0.0, 10.0)])
    read = harness.load_reader(metric)
    assert read(harness.RunRecord(None, stats, host_only)) is None
    assert read(harness.RunRecord(None, stats)) is None


def test_device_readers_need_an_exchange():
    """A device trace without an all-to-all (a one-chip epoch) gives the
    trace readers nothing to read."""
    tr = devtrace.Trace([
        devtrace.Event("/host:CPU", "python3", devtrace.WINDOW_SPAN, 0.0,
                       100.0),
        _ev(0, "%convert_reduce_fusion.2 = s32[8]{0} fusion(%all_to_all.1)",
            10, 50)])
    stats = {"epochs": 1, "device_kind": "TPU v5 lite",
             "wire_bytes_per_epoch": 1}
    for m in ("exchange_s.rma", "ici_share.rma"):
        assert harness.load_reader(m)(harness.RunRecord(None, stats, tr)) is None


def test_threaded_reference_counts_what_the_reference_counts():
    from benchmarks.chip.graph import kronecker_edges
    from benchmarks.chip.reference import Reference

    driver = harness.load_driver({"driver": "rma_epoch"})
    cfg = {"scale": 9, "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
           "kronecker_seed": 1}
    e = kronecker_edges(cfg, SEED)
    want = Reference(e, 1 << 9)
    got = driver.ThreadedReference(e, 1 << 9)
    assert got.triangles.sum() > 0
    assert (got.triangles == want.triangles).all()
    assert (got.lcc() == want.lcc()).all()


def test_every_seed_gets_the_same_dataset(monkeypatch):
    """The run's seed changes nothing the program is given: the same edge
    list, labels included, for every seed."""
    from repro.core import csr as csr_mod

    built = []

    def from_edges(edges, n, **kw):
        built.append(np.asarray(edges).copy())
        raise StopIteration  # set-up needs to go no further
    monkeypatch.setattr(csr_mod, "from_edges", from_edges)
    cell = harness.load_cell(CELL)
    cell.config = dict(cell.config, scale=8)
    driver = harness.load_driver(cell.traffic)
    for seed in (SEED, SEED + 1):
        with pytest.raises(StopIteration):
            driver.prepare(cell, seed, 0.0)
    a, b = built
    assert np.array_equal(a, b)
