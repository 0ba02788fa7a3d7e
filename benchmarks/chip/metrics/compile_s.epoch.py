"""Seconds JAX spent lowering programs and compiling them or loading
them from the persistent compilation cache, in the process up to the
reading (``setup.lower`` + ``setup.compile`` of the set-up record, fed
by the compile listener that ``repro.launch.chip.enable_compile_cache``
installs). The epoch cell compiles in set-up only: its window calls a
compiled program (``window_compiles`` 0)."""
from benchmarks.chip.program_record import setup_entry


def read(run):
    parts = [setup_entry(run, name) for name in ("setup.lower",
                                                 "setup.compile")]
    parts = [p["s"] for p in parts if p is not None]
    return sum(parts) if parts else None
