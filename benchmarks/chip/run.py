"""On-chip benchmark of the graph engine: one run of one cell.

    python3 benchmarks/chip/run.py --workload g500-s15.epoch --seed 7 \
        --seconds 51 --trace 0

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the
checkout root; ``harness.py`` says how a run goes. The last line of
standard output is the result as one JSON object. A run that finds no
TPU, or fewer chips than the cell asks for, exits 1 and prints no
result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    return harness.main(args, t_start=T_START)


if __name__ == "__main__":
    raise SystemExit(main())
