"""Share of the epoch's padded all-pairs compares that compare two real
neighbours, in percent: on the busiest device, the sum over real edge
slots of ``deg(u) * deg(v)`` over ``e_max * width**2`` (the counts
``real_pair_compares`` and ``padded_compares`` of the program's set-up
span ``setup.schedule``, ``core/rma.py::schedule_counts``)."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    entry = setup_entry(run, "setup.schedule")
    if entry is None or not entry.get("padded_compares"):
        return None
    return 100.0 * entry["real_pair_compares"] / entry["padded_compares"]
