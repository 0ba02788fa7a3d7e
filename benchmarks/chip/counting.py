"""Work of one call, computed from its shapes.

The static epoch's ``pairwise`` intersection compares every slot of the
padded row of ``u`` with every slot of the padded row of ``v``, for
every edge slot of the schedule, padding included: the epoch program
evaluates ``e_max`` edge slots (``n_rounds`` chunks of ``e_max /
n_rounds``) of ``width x width`` int32 compares each, and counts the
matches by a sum over both slot axes.
"""
from __future__ import annotations

__all__ = ["epoch_compares"]


def epoch_compares(e_max: int, width: int) -> int:
    """Padded int32 compares in one epoch on the busiest device."""
    return int(e_max) * int(width) * int(width)
