"""Seconds the program took to build the graph store from the edge list
in set-up (``core/csr.py::from_edges``, its set-up span
``setup.graph``)."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    entry = setup_entry(run, "setup.graph")
    return None if entry is None else entry["s"]
