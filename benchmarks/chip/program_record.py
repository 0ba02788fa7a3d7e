"""The program's own set-up record, as the per-layer readers see it.

The program keeps, whether or not a tracer is installed, the newest
set-up span of each name and the process's compile seconds and cache
counts (``repro.obs.trace.setup_record``). A reader gets an entry of it
only in a run whose trace shows a device, as the device readers do, and
gets None from a program that keeps no such record.
"""
from __future__ import annotations

from typing import Optional

__all__ = ["setup_entry"]


def setup_entry(run, name: str) -> Optional[dict]:
    """The set-up record's entry ``name`` (``{"s": seconds, **counts}``),
    or None."""
    if run.trace is None or not run.trace.devices:
        return None
    try:
        from repro.obs.trace import setup_record
    except ImportError:  # a program that keeps no set-up record
        return None
    return setup_record().get(name)
