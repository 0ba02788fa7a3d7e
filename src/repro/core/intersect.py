"""Adjacency-list intersection (paper §II-C, Algorithms 1 & 2, Eq. 3).

Three layers:

1. **Scalar reference** (`ssi_scalar`, `binary_search_scalar`) — literal
   transcriptions of the paper's Algorithms 1/2. Used as oracles and for
   the Table III benchmark.
2. **Vectorized host versions** (`*_np`) — numpy batch implementations used
   by the benchmarks (the CPU stand-ins for the OpenMP parallel region of
   §III-C).
3. **Device versions** (`*_jnp`) — jnp implementations for padded sorted
   rows with sentinel padding. These are the TPU adaptation: merge-SSI is
   sequential and anti-SIMD on a VPU, so the SSI regime is realized as an
   all-pairs tile compare (SIMD compare-all) and the binary-search regime
   as a vectorized ``searchsorted`` membership count. The hybrid decision
   rule (Eq. 3) is re-derived for this cost model in `tpu_regime_rule`.

Rows are sorted ascending; any id >= ``sentinel`` is padding and never
counted (the sentinel is chosen > every real id, so sorted order holds).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "ssi_scalar",
    "binary_search_scalar",
    "hybrid_scalar",
    "eq3_ssi_faster",
    "count_bsearch_np",
    "count_pairwise_np",
    "count_bsearch_jnp",
    "count_pairwise_jnp",
    "count_equal_pairs_jnp",
    "count_bitmap_jnp",
    "tpu_regime_rule",
    "count_hybrid_jnp",
]


# --------------------------------------------------------------------------
# 1. Scalar references — Algorithms 1 and 2, verbatim semantics.
# --------------------------------------------------------------------------
def ssi_scalar(a: np.ndarray, b: np.ndarray) -> int:
    """Sorted set intersection (Algorithm 2): O(|A| + |B|)."""
    counter = 0
    i = j = 0
    na, nb = len(a), len(b)
    while i < na and j < nb:
        if a[i] == b[j]:
            counter += 1
            i += 1
            j += 1
        elif a[i] < b[j]:
            i += 1
        else:
            j += 1
    return counter


def binary_search_scalar(a: np.ndarray, b: np.ndarray) -> int:
    """Binary search (Algorithm 1): |A| lookups in B, O(|A| log |B|)."""
    counter = 0
    nb = len(b)
    for x in a:
        lo, hi = 0, nb
        while lo < hi:
            mid = (lo + hi) // 2
            if b[mid] < x:
                lo = mid + 1
            else:
                hi = mid
        if lo < nb and b[lo] == x:
            counter += 1
    return counter


def eq3_ssi_faster(len_a: int, len_b: int) -> bool:
    """Paper Eq. 3: SSI is (theoretically) faster iff |B|/|A| <= log2|B|-1.

    ``a`` is the shorter list.
    """
    if len_a == 0 or len_b == 0:
        return True
    if len_a > len_b:
        len_a, len_b = len_b, len_a
    return (len_b / len_a) <= max(np.log2(max(len_b, 2)) - 1.0, 0.0)


def hybrid_scalar(a: np.ndarray, b: np.ndarray) -> int:
    """Hybrid method (§III-C): pick by Eq. 3, always search the longer list."""
    if len(a) > len(b):
        a, b = b, a
    if eq3_ssi_faster(len(a), len(b)):
        return ssi_scalar(a, b)
    return binary_search_scalar(a, b)


# --------------------------------------------------------------------------
# 2. Vectorized host (numpy) versions — used by the shared-memory benchmarks.
# --------------------------------------------------------------------------
def count_bsearch_np(a: np.ndarray, b: np.ndarray) -> int:
    """Vectorized binary-search membership |a ∩ b| for 1-D sorted arrays."""
    if a.size == 0 or b.size == 0:
        return 0
    idx = np.searchsorted(b, a)
    idx = np.minimum(idx, b.size - 1)
    return int((b[idx] == a).sum())


def count_pairwise_np(a: np.ndarray, b: np.ndarray) -> int:
    """All-pairs compare (the SIMD-friendly SSI substitute), O(|A||B|)."""
    if a.size == 0 or b.size == 0:
        return 0
    return int((a[:, None] == b[None, :]).sum())


# --------------------------------------------------------------------------
# 3. Device (jnp) versions on padded sorted rows.
#    rows_a: [..., Wa] int32 sorted w/ sentinel padding; rows_b: [..., Wb].
# --------------------------------------------------------------------------
def count_bsearch_jnp(rows_a: jnp.ndarray, rows_b: jnp.ndarray, sentinel: int):
    """Membership count via vectorized binary search of A's elements in B.

    Batched over leading dims. Padding (>= sentinel) never matches.
    """
    idx = jax.vmap(jnp.searchsorted)(rows_b, rows_a) if rows_a.ndim == 2 else (
        jnp.searchsorted(rows_b, rows_a)
    )
    idx = jnp.minimum(idx, rows_b.shape[-1] - 1)
    hit = jnp.take_along_axis(rows_b, idx, axis=-1) == rows_a
    hit = hit & (rows_a < sentinel)
    return hit.sum(axis=-1).astype(jnp.int32)


def count_pairwise_jnp(rows_a: jnp.ndarray, rows_b: jnp.ndarray, sentinel: int):
    """All-pairs tile compare: counts[e] = sum_{s,t} (A[e,s] == B[e,t]).

    O(Wa*Wb) compares but pure vector ops — the TPU 'SSI regime'.
    """
    eq = rows_a[..., :, None] == rows_b[..., None, :]
    eq = eq & (rows_a[..., :, None] < sentinel)
    return eq.sum(axis=(-1, -2)).astype(jnp.int32)


def count_equal_pairs_jnp(rows_a: jnp.ndarray, rows_b: jnp.ndarray):
    """counts[e] = sum_{s,t} (A[e,s] == B[e,t]), with no padding mask: for
    rows whose paddings never compare equal (A padded with the sentinel,
    B with a larger id). Five ``lax`` ops, so a program with many calls
    traces and lowers quickly, and one compare per pair on the device."""
    nd = rows_a.ndim
    shape = rows_a.shape + rows_b.shape[-1:]
    on_a = tuple(range(nd))
    eq = jax.lax.eq(
        jax.lax.broadcast_in_dim(rows_a, shape, on_a),
        jax.lax.broadcast_in_dim(rows_b, shape, on_a[:-1] + (nd,)),
    )
    return jax.lax.reduce_sum(
        jax.lax.convert_element_type(eq, jnp.int32), (nd - 1, nd))


def count_bitmap_jnp(words_a: jnp.ndarray, words_b: jnp.ndarray):
    """Bitmap AND + popcount over uint32 words (batched)."""
    both = jnp.bitwise_and(words_a, words_b)
    # popcount via jax.lax.population_count (uint32-safe)
    pc = jax.lax.population_count(both)
    return pc.sum(axis=-1).astype(jnp.int32)


def tpu_regime_rule(deg_a: jnp.ndarray, deg_b: jnp.ndarray, width_b: int):
    """Eq. 3 re-derived for the vectorized cost model.

    bsearch-regime cost ~ |A| * ceil(log2 Wb) vector gathers;
    pairwise-regime cost ~ |A| * Wb lane-compares (cheaper per op by ~G,
    the gather-vs-compare cost ratio; G ~= 8 on VPU-class hardware).
    pairwise (SSI regime) wins iff Wb <= G * log2(Wb)  ==  the same
    log-ratio structure as paper Eq. 3 with the constant re-fit.
    """
    g = 8.0
    log_wb = jnp.ceil(jnp.log2(jnp.maximum(width_b, 2).astype(jnp.float32)))
    lo = jnp.minimum(deg_a, deg_b).astype(jnp.float32)
    hi = jnp.maximum(deg_a, deg_b).astype(jnp.float32)
    # ratio rule, mirroring |B|/|A| <= log2|B| - 1 with vector constants
    return (hi / jnp.maximum(lo, 1.0)) <= g * jnp.maximum(log_wb - 1.0, 1.0)


def count_hybrid_jnp(
    rows_a: jnp.ndarray,
    rows_b: jnp.ndarray,
    deg_a: jnp.ndarray,
    deg_b: jnp.ndarray,
    sentinel: int,
):
    """Hybrid device intersection: per-edge regime select (paper §III-C).

    Both regimes are computed on the (cheap, padded) rows and selected by
    the rule; the static split into two streams (so only one regime runs
    per edge) is done by the distributed engine at preprocessing time —
    see ``core/async_engine.py``.
    """
    use_pairwise = tpu_regime_rule(deg_a, deg_b, rows_b.shape[-1])
    c_pw = count_pairwise_jnp(rows_a, rows_b, sentinel)
    c_bs = count_bsearch_jnp(rows_a, rows_b, sentinel)
    return jnp.where(use_pairwise, c_pw, c_bs)
