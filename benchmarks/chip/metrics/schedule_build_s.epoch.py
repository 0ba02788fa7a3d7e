"""Seconds the program took to compile the static pull schedule in
set-up (``core/rma.py::build_sharded_problem``, its set-up span
``setup.schedule``)."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    entry = setup_entry(run, "setup.schedule")
    return None if entry is None else entry["s"]
