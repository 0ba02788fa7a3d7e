"""Share of the busiest chip's remote reads that the degree-score cache
rows serve, in percent: 100 x ``cache_reads`` / ``remote_reads`` of the
program's set-up span ``setup.schedule``
(``core/rma.py::schedule_counts``): edge slots whose v row another chip
owns, and of those the ones whose row is a replicated cache row."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    entry = setup_entry(run, "setup.schedule")
    if entry is None or not entry.get("remote_reads"):
        return None
    return 100.0 * entry["cache_reads"] / entry["remote_reads"]
