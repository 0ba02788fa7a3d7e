"""Readings the correctness limits are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload g500-s15.epoch \
        --seeds 11,12,13 --control-seeds 11,12,13 --kronecker-seeds 1,2,3

For each of ``--seeds`` it runs the cell as a run does (set-up, a
window of ``--seconds``, the comparison with the reference) and prints
the numbers compared; for each of ``--control-seeds`` it prints the same
numbers with the reference, computed in the precision below the one the
configuration states, put in the program's place (the control). The
largest program reading is a limit's lower reading, the smallest
control reading its upper one. ``--kronecker-seeds`` repeats all of it
on the graphs of other quadrant draws than the configuration's (the
timed cell keeps its own): the run's seed only relabels a graph, so
these are the readings on different graphs. Needs a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def _ints(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def readings(cell, seeds, control_seeds, seconds: float):
    """One reading per seed of ``seeds`` and ``control_seeds``: the
    program's checks, the control's, or both, as dicts."""
    from benchmarks.chip import harness

    driver = harness.load_driver(cell.traffic)
    controls = set(control_seeds)
    for seed in list(seeds) + sorted(controls - set(seeds)):
        session = driver.prepare(cell, seed, seconds)
        line = {"kronecker_seed": cell.config["kronecker_seed"], "seed": seed}
        if seed in seeds:
            session.window(seconds)
            session.release()
            checks, attempted, failed = session.check()
            line["program"] = {c.name: c.value for c in checks}
            line["attempted"], line["failed"] = attempted, failed
        else:
            session.release()
        if seed in controls:
            line["control"] = {c.name: c.value
                               for c in driver.control_checks(session)}
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--kronecker-seeds", default="",
                    help="quadrant draws to read on (default: the "
                         "configuration's own)")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.tpu_devices(cell.chips)
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    harness._enable_compile_cache()
    for ks in _ints(args.kronecker_seeds) or [cell.config["kronecker_seed"]]:
        c = copy.deepcopy(cell)
        c.config["kronecker_seed"] = ks
        for line in readings(c, _ints(args.seeds), _ints(args.control_seeds),
                             args.seconds):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
