"""The readers of the program's own set-up record, on the CPU: the epoch
driver's set-up at scale 7, read beside the epoch trace recorded on a
TPU v5e, and beside a trace with no device."""
import json
from pathlib import Path

import numpy as np
import pytest

import _paths
from benchmarks.chip import devtrace, harness

DATA = Path(__file__).parent / "data"
EPOCH = "g500-s15.epoch"
SCALE = 7
SEED = 2**33 + 11
READERS = ["graph_build_s.epoch", "schedule_build_s.epoch", "place_s.epoch",
           "compile_s.epoch", "compare_fill.epoch"]


@pytest.fixture(scope="module")
def session(tmp_path_factory, monkeypatch_module):
    """The epoch driver's set-up of the cell, its configuration cut to
    scale 7, with the compile listener installed as the harness does."""
    from repro.launch.chip import enable_compile_cache

    root = tmp_path_factory.mktemp("root")
    spec = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((_paths.ROOT / c["file"]).read_text())
        cfg["scale"] = SCALE
        path = root / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    # with the variable set, enable_compile_cache leaves the config alone
    monkeypatch_module.setenv("JAX_COMPILATION_CACHE_DIR",
                              str(tmp_path_factory.mktemp("cache")))
    enable_compile_cache()
    cell = harness.load_cell(EPOCH, root)
    return harness.load_driver(cell.traffic).prepare(cell, SEED, 0.0)


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as mp:
        yield mp


def _v5e_trace():
    return devtrace.Trace([devtrace.Event(*e) for e in
                           json.loads((DATA / "epoch_trace.json").read_text())])


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_a_number(session, metric):
    run = harness.RunRecord(session.cell, session.stats, _v5e_trace())
    v = harness.load_reader(metric)(run)
    assert isinstance(v, float) and v > 0


def test_compare_fill_matches_the_edge_list(session):
    # the simple graph of the raw edge list, both directions, built here
    # without the program
    e = np.asarray(session.edges, np.int64)
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.concatenate([e, e[:, ::-1]]), axis=0)
    deg = np.bincount(e[:, 0], minlength=session.n)
    rounds = session.cell.config["engine"]["n_rounds"]
    e_max = -(-len(e) // rounds) * rounds  # whole chunks of edge slots
    width = int(deg.max())
    want = 100.0 * float(np.sum(deg[e[:, 0]] * deg[e[:, 1]])) / (
        e_max * width * width)
    run = harness.RunRecord(session.cell, session.stats, _v5e_trace())
    got = harness.load_reader("compare_fill.epoch")(run)
    assert got == pytest.approx(want, rel=1e-12)
    assert 0 < got < 100


@pytest.mark.parametrize("metric", READERS)
def test_reader_needs_a_device(session, metric):
    host_only = devtrace.Trace([devtrace.Event(
        "/host:CPU", "python3", devtrace.WINDOW_SPAN, 0.0, 10.0)])
    read = harness.load_reader(metric)
    assert read(harness.RunRecord(session.cell, session.stats,
                                  host_only)) is None
    assert read(harness.RunRecord(session.cell, session.stats)) is None
