"""Batched delta-intersect wrapper over the Pallas ``intersect_count`` kernel.

The streaming engine's hot loop is the same primitive as the static
pipeline — |adj(u) ∩ adj(v)| over padded sorted rows — but a streaming
batch has a data-dependent number of row pairs, while ``intersect_count``
requires the edge dimension to be a multiple of ``block_e``. This wrapper:

- pads the pair batch up to the next power of two (at least 8) with
  all-sentinel phantom rows (they intersect nothing, so the padding
  counts are 0), so the compiled grid shapes stay logarithmic in the
  batch size, and
- clamps ``block_e`` down for tiny batches so a 3-edge delta doesn't pay
  a 128-row program (one block then spans the whole padded batch).

``delta_intersect_masks`` is the companion membership primitive: the
incremental LCC update needs the *identities* of the closing vertices
(every common neighbor w of a new edge (u,v) gains a triangle), not just
the count. It is a vectorized binary-search membership over the same
padded-row layout; counts derived from the mask equal the kernel counts —
the streaming tests cross-check the two paths.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from .bucketing import pow2_ceil
from .intersect_count import intersect_count as _intersect
from .ops import default_interpret

__all__ = ["delta_intersect_counts", "delta_intersect_masks"]


def _pad_pairs(rows: np.ndarray, e_pad: int, sentinel: int) -> np.ndarray:
    e, w = rows.shape
    if e == e_pad:
        return rows
    out = np.full((e_pad, w), sentinel, rows.dtype)
    out[:e] = rows
    return out


def delta_intersect_counts(
    rows_a: np.ndarray,  # [E, WA] int32 sorted, sentinel-padded
    rows_b: np.ndarray,  # [E, WB]
    *,
    sentinel: int,
    block_e: int = 128,
    interpret: bool | None = None,
) -> np.ndarray:
    """|rows_a[e] ∩ rows_b[e]| per pair, any E >= 0. Returns int64 [E]."""
    rows_a = np.ascontiguousarray(rows_a, np.int32)
    rows_b = np.ascontiguousarray(rows_b, np.int32)
    e = rows_a.shape[0]
    assert rows_b.shape[0] == e
    if e == 0:
        return np.zeros((0,), np.int64)
    if interpret is None:
        interpret = default_interpret()
    be = min(block_e, pow2_ceil(e, 8))
    e_pad = -(-pow2_ceil(e, 8) // be) * be
    obs_trace.instant(
        "pallas_kernel", cat="kernel", kernel="intersect_count",
        interpret=bool(interpret), pairs=e_pad, wa=rows_a.shape[1],
        wb=rows_b.shape[1],
    )
    cnt = _intersect(
        jnp.asarray(_pad_pairs(rows_a, e_pad, sentinel)),
        jnp.asarray(_pad_pairs(rows_b, e_pad, sentinel)),
        sentinel=sentinel,
        block_e=be,
        interpret=interpret,
    )
    return np.asarray(cnt[:e], np.int64)


def delta_intersect_masks(
    rows_a: np.ndarray,  # [E, WA]
    rows_b: np.ndarray,  # [E, WB]
    *,
    sentinel: int,
) -> np.ndarray:
    """Membership mask [E, WA]: mask[e, s] == (rows_a[e, s] ∈ rows_b[e]).

    Padding slots (>= sentinel) are always False. Vectorized host-side
    binary search (numpy), so the streaming engine can scatter triangle
    credit to the matched ids without a device round-trip.
    """
    rows_a = np.asarray(rows_a, np.int64)
    rows_b = np.asarray(rows_b, np.int64)
    e, wa = rows_a.shape
    if e == 0 or rows_b.shape[1] == 0:
        return np.zeros((e, wa), bool)
    # per-row searchsorted via rank trick: offset each row into its own
    # disjoint key space, then one global searchsorted.
    wb = rows_b.shape[1]
    span = int(sentinel) + 1
    off = np.arange(e, dtype=np.int64)[:, None] * span
    flat_b = (rows_b + off).ravel()  # sorted within rows, rows ascending
    keys = (rows_a + off).ravel()
    idx = np.searchsorted(flat_b, keys)
    idx = np.minimum(idx, flat_b.size - 1)
    hit = flat_b[idx] == keys
    hit &= (rows_a < sentinel).ravel()
    return hit.reshape(e, wa)
