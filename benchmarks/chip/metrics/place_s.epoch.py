"""Seconds the program took to place the epoch's inputs on the device,
until they were there (``core/async_engine.py::device_args``, its
set-up span ``setup.place``)."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    entry = setup_entry(run, "setup.place")
    return None if entry is None else entry["s"]
