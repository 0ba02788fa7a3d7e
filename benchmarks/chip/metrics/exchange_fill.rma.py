"""Share of the ids the epoch's exchanges move off the busiest chip that
are real neighbour ids, in percent: 100 x ``payload_bytes`` /
``wire_bytes`` of the program's set-up span ``setup.schedule``
(``core/rma.py::schedule_counts``; the rest is each class's padding)."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    entry = setup_entry(run, "setup.schedule")
    if entry is None or not entry.get("wire_bytes"):
        return None
    return 100.0 * entry["payload_bytes"] / entry["wire_bytes"]
