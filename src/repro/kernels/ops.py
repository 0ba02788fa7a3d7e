"""jit'd public wrappers over the Pallas kernels.

The kernels target the TPU. ``default_interpret`` is the one place that
decides how they run: compiled by Mosaic on a TPU, in the Pallas
interpreter on the CPU (where the tests validate them against
``ref.py``), and an error on any other platform rather than a silent
fallback. Every wrapper's ``interpret=None`` resolves through it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .bitmap_popcount import bitmap_intersect_count as _bitmap
from .embedding_bag import embedding_bag as _bag
from .flash_attention import flash_attention as _flash
from .intersect_count import intersect_count as _intersect
from .segment_sum_sorted import segment_sum_sorted as _segsum

__all__ = [
    "default_interpret",
    "intersect_count",
    "bitmap_intersect_count",
    "embedding_bag",
    "segment_sum_sorted",
    "flash_attention_gqa",
]


def default_interpret() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (compiled);
    raises on any other platform."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on a TPU or interpreted on the "
        f"CPU; JAX's default backend is {platform!r}"
    )


def intersect_count(rows_a, rows_b, *, sentinel, block_e=128, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _intersect(rows_a, rows_b, sentinel=sentinel, block_e=block_e,
                      interpret=interpret)


def bitmap_intersect_count(words_a, words_b, *, block_e=256, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _bitmap(words_a, words_b, block_e=block_e, interpret=interpret)


def embedding_bag(table, ids, mask, *, mode="sum", block_b=8, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _bag(table, ids, mask, mode=mode, block_b=block_b,
                interpret=interpret)


def segment_sum_sorted(values, seg_ids, *, num_segments, block_e=512,
                       rows=256, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _segsum(values, seg_ids, num_segments=num_segments,
                   block_e=block_e, rows=rows, interpret=interpret)


def flash_attention_gqa(q, k, v, *, scale, causal=True, window=0,
                        softcap=0.0, block_q=128, block_k=128,
                        interpret=None):
    """GQA wrapper: q [B,S,K,G,dh], k/v [B,T,K,dh] -> [B,S,K,G,dh].

    Folds (B, K, G) into the kernel batch dim (K/V repeated per group —
    the kernel-side view; on-chip the repeat is a broadcast, not a copy).
    """
    if interpret is None:
        interpret = default_interpret()
    b, s, kh, g, dh = q.shape
    t = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(b * kh * g, s, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(b * kh, t, dh), g, axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(b * kh, t, dh), g, axis=0)
    out = _flash(qf, kf, vf, scale=scale, causal=causal, window=window,
                 softcap=softcap, block_q=block_q, block_k=block_k,
                 interpret=interpret)
    return out.reshape(b, kh, g, s, dh).transpose(0, 3, 1, 2, 4)
