"""Intersect query rows against device-resident slots.

The device tier (``repro.device.ResidencyManager``) keeps the
degree-scored hot adjacency rows persistently resident in a padded
``[slots, max_width]`` device buffer. The host-side intersection path
would gather those rows back to host, re-pack and re-upload them per
kernel call — exactly the per-epoch refetch cost the paper's CLaMPI
cache removes one level up. Here the resident operand never leaves the
device: each pair's resident row is gathered on device by slot index
and counted by the same Pallas ``intersect_count`` kernel every other
path uses. Two layouts:

- ``rows_b`` given   — resident slot vs a packed (uploaded) query row;
- ``slots_b`` given  — both sides resident: two gathers, zero upload.

Shapes are bounded by the shared power-of-2 bucketing
(``kernels.bucketing``): the pair count pads to the next power of two
(phantom pairs hit slot 0 with an all-sentinel query row, contributing
0), and callers bucket ragged query widths before calling in.

Rows follow the repo-wide invariant: sorted ascending, deduplicated,
ids < sentinel (padding never matches). The pure-jnp oracle is
``kernels.ref.resident_intersect_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from .bucketing import pow2_ceil
from .intersect_count import intersect_count
from .ops import default_interpret

__all__ = ["resident_intersect", "resident_intersect_counts"]


@functools.partial(
    jax.jit, static_argnames=("sentinel", "block_e", "interpret")
)
def resident_intersect(
    residency: jnp.ndarray,  # [S, W] int32 resident rows, sentinel-padded
    slots_a: jnp.ndarray,  # [E] int32 slot per pair
    rows_b: Optional[jnp.ndarray] = None,  # [E, WB] packed query rows
    *,
    slots_b: Optional[jnp.ndarray] = None,  # [E] both-resident variant
    sentinel: int,
    block_e: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """``|residency[slots_a[e]] ∩ B[e]|`` per pair (int32 [E]).

    ``B`` is ``rows_b[e]`` (one uploaded side) or
    ``residency[slots_b[e]]`` (fully resident). E must be a multiple of
    ``block_e`` — use ``resident_intersect_counts`` for ragged batches.
    """
    assert (rows_b is None) != (slots_b is None), "pass rows_b XOR slots_b"
    a = jnp.take(residency, slots_a, axis=0)
    b = rows_b if slots_b is None else jnp.take(residency, slots_b, axis=0)
    return intersect_count(
        a, b, sentinel=sentinel, block_e=block_e, interpret=interpret
    )


def resident_intersect_counts(
    residency,  # [S, W] int32 (jnp: stays on device; np is uploaded once)
    slots_a: np.ndarray,  # [E] slot indices (all >= 0)
    rows_b: Optional[np.ndarray] = None,  # [E, WB] int32 sorted, padded
    *,
    slots_b: Optional[np.ndarray] = None,
    sentinel: int,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """Ragged-friendly wrapper: any E >= 0, returns int64 [E].

    Pads the pair batch to the next power of two (phantom pairs reuse
    slot 0 and are sliced off the result) so the number of compiled
    grid shapes stays logarithmic in the batch size.
    """
    assert (rows_b is None) != (slots_b is None), "pass rows_b XOR slots_b"
    slots_a = np.ascontiguousarray(slots_a, np.int32)
    e = slots_a.shape[0]
    if e == 0:
        return np.zeros((0,), np.int64)
    if interpret is None:
        interpret = default_interpret()
    res = (
        residency
        if isinstance(residency, jnp.ndarray)
        else jnp.asarray(np.ascontiguousarray(residency, np.int32))
    )
    e_pad = pow2_ceil(e, 8)
    sa = np.zeros(e_pad, np.int32)
    sa[:e] = slots_a
    kw = dict(sentinel=sentinel, block_e=min(128, e_pad), interpret=interpret)
    obs_trace.instant(
        "pallas_kernel", cat="kernel", kernel="resident_intersect",
        layout="rows" if slots_b is None else "slots",
        interpret=bool(interpret), pairs=e_pad, w=int(res.shape[1]),
    )
    if slots_b is not None:
        slots_b = np.ascontiguousarray(slots_b, np.int32)
        assert slots_b.shape[0] == e
        sb = np.zeros(e_pad, np.int32)
        sb[:e] = slots_b
        cnt = resident_intersect(res, jnp.asarray(sa), slots_b=jnp.asarray(sb),
                                 **kw)
    else:
        rows_b = np.ascontiguousarray(rows_b, np.int32)
        assert rows_b.shape[0] == e
        rb = np.full((e_pad, rows_b.shape[1]), sentinel, np.int32)
        rb[:e] = rows_b
        cnt = resident_intersect(res, jnp.asarray(sa), jnp.asarray(rb), **kw)
    return np.asarray(cnt[:e], np.int64)
