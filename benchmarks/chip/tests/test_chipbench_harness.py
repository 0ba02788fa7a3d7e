"""The harness end to end on the CPU at a small size: cells found by
name, correct runs, the controls and the planted faults reading
``correct: false``, and a run without a TPU printing no result."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import _paths
from benchmarks.chip import harness

SCALE = 7
SEED = 2**33 + 5  # the driver's seeds pass 32 signed bits
EPOCH = "g500-s15.epoch"


def cpu_devices(chips):
    """Stands in for the harness's look for a chip."""
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


@pytest.fixture
def small_root(tmp_path):
    """A root whose BENCHMARK.json is the repository's, with each
    configuration cut to scale 7."""
    spec = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((_paths.ROOT / c["file"]).read_text())
        cfg["scale"] = SCALE
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def run(root, cell, trace=False, seconds=0.5, **kw):
    return harness.run_cell(cell, SEED, seconds, trace,
                            t_start=time.perf_counter(), devices=cpu_devices,
                            root=root, **kw)


@pytest.mark.parametrize("cell,metrics", [
    (EPOCH, {"epoch_s", "setup_s"}),
])
def test_cell_runs_correct(small_root, cell, metrics):
    r = run(small_root, cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == metrics
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert r["device"]["platform"] == "cpu"


def test_traced_epoch_run(small_root):
    r = run(small_root, EPOCH, trace=True)
    assert r["correct"] is True
    # the CPU trace has no TPU plane: the device readers find nothing
    # and the line leaves their metrics out
    assert r["metrics"] == {}
    assert r["device"]["busy_s"] == 0.0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _wrap_epoch(monkeypatch, alter):
    from repro.core import async_engine

    real = async_engine.make_lcc_fn

    def make(*a, **kw):
        fn = real(*a, **kw)

        class Broken:
            def lower(self, *args):
                compiled = fn.lower(*args).compile()

                class C:
                    def as_text(self):
                        return compiled.as_text()

                    def memory_analysis(self):
                        return compiled.memory_analysis()

                    def __call__(self, *xs):
                        return alter(*compiled(*xs))
                return type("L", (), {"compile": lambda _self: C()})()
        return Broken()
    monkeypatch.setattr(async_engine, "make_lcc_fn", make)


def _half_the_edges(monkeypatch):
    from repro.core import rma

    real = rma.build_sharded_problem

    def build(*a, **kw):
        prob = real(*a, **kw)
        prob.edge_mask[:, prob.e_max // 2:] = False
        return prob
    monkeypatch.setattr(rma, "build_sharded_problem", build)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "answer_altered"])
def test_epoch_faults_read_incorrect(small_root, monkeypatch, fault):
    if fault == "state_unchanged":   # the epoch hands back its zeroed state
        _wrap_epoch(monkeypatch, lambda t, c: (t * 0, c * 0))
    elif fault == "half_the_batch":  # half of the edges never counted
        _half_the_edges(monkeypatch)
    else:                            # one vertex's count off by one
        _wrap_epoch(monkeypatch, lambda t, c: (t.at[0, 3].add(1), c))
    r = run(small_root, EPOCH)
    assert r["correct"] is False and r["failed"] == r["attempted"]


@pytest.mark.parametrize("cell", [EPOCH])
def test_controls_read_incorrect(small_root, cell):
    """The reference in the program's place, one precision below the one
    the configuration states, fails the check; the program passes."""
    c = harness.load_cell(cell, small_root)
    driver = harness.load_driver(c.traffic)
    session = driver.prepare(c, SEED, 0.5)
    session.window(0.5)
    session.release()
    checks, _, _ = session.check()
    assert all(ch.ok for ch in checks)
    assert not all(ch.ok for ch in driver.control_checks(session))


def test_new_config_mix_and_metric_found_by_name(small_root, tmp_path):
    """A cell added as new files and entries alone runs, with no edit to
    a file the benchmark has."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / "g500-s15.json").read_text())
    cfg.update(scale=6, kronecker_seed=2)
    (bench / "configs" / "g500-s6.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "epoch-once.json").write_text(json.dumps(
        {"driver": "epoch", "what": "the same epochs, another mix file"}))
    (bench / "metrics" / "epochs.once.py").write_text(
        "def read(run):\n    return run.stats['epochs']\n")
    spec["configs"].append({"name": "g500-s6", "source": "test",
                            "file": str(bench / "configs" / "g500-s6.json"),
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "g500-s6.epoch-once",
                              "config": "g500-s6", "traffic": "epoch-once",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "epochs.once", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "static epoch engine",
                              "moves": "epoch_s",
                              "workloads": ["g500-s6.epoch-once"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and EPOCH in m["workloads"]:
            m["workloads"].append("g500-s6.epoch-once")
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("g500-s6.epoch-once", small_root, bench)
    assert cell.config["scale"] == 6 and cell.config["kronecker_seed"] == 2
    assert [m["name"] for m in cell.per_layer] == ["epochs.once"]
    r = run(small_root, "g500-s6.epoch-once", trace=True, bench_dir=bench)
    assert r["correct"] is True
    assert r["metrics"] == {"epochs.once": {"value": r["attempted"],
                                            "unit": "count"}}
    r = run(small_root, "g500-s6.epoch-once", bench_dir=bench)
    assert set(r["metrics"]) == {"epoch_s", "setup_s"}
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such.cell", small_root, bench)


def test_calibrate_reads_program_control_and_other_graphs(small_root):
    """The readings the limits are set from: the program under its
    limits, the control over one, on the configuration's graph and on
    another quadrant draw."""
    from benchmarks.chip import calibrate

    cell = harness.load_cell(EPOCH, small_root)
    limits = cell.config["limits"]
    lines = list(calibrate.readings(cell, [SEED], [SEED, SEED + 1], 0.1))
    assert [ln["seed"] for ln in lines] == [SEED, SEED + 1]
    assert all(ln["program"][k] <= limits[k] for k in limits
               for ln in lines[:1])
    assert "program" not in lines[1]
    for ln in lines:
        assert any(ln["control"][k] > limits[k] for k in limits)
    other = dict(cell.config, kronecker_seed=cell.config["kronecker_seed"] + 1)
    (ln,) = calibrate.readings(
        harness.Cell(cell.name, cell.chips, other, cell.traffic,
                     cell.end_to_end, cell.per_layer), [SEED], [], 0.1)
    assert ln["kronecker_seed"] == other["kronecker_seed"]
    assert ln["program"]["tri_mismatch"] == 0


def test_memory_peak_counts_program_temporaries():
    # the allocator's peak leaves the temporaries out: what the chip held
    # as the window began plus the program's temporaries is the peak
    before = [{"bytes_in_use": 800}, {"bytes_in_use": 900}]
    after = [{"peak_bytes_in_use": 1000}, {"peak_bytes_in_use": 950}]
    assert harness._memory_peak(before, after, 5000) == 5900
    # an allocator peak above that is taken as it is
    assert harness._memory_peak(before, [{"peak_bytes_in_use": 7000}, {}],
                                5000) == 7000
    # a backend without memory stats
    assert harness._memory_peak([{}], [{}], 0) == 0


def test_run_without_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH_DIR / "run.py"), "--workload",
         EPOCH, "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=str(_paths.ROOT))
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr
