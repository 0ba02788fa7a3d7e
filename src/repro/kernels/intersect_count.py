"""Pallas TPU kernel: batched sorted-row intersection counting.

The compute hot-spot of the paper (edge-centric |adj(u) ∩ adj(v)|),
adapted to the TPU: merge-SSI is sequential and anti-SIMD, so each edge's
pair of padded sorted rows is intersected by an **all-pairs compare** on
the VPU (the SIMD set-intersection idiom).

Layout: pairs run along the 128 lanes and row slots along the sublanes,
so every operation is a plain 2-D vector op that Mosaic lowers at any
row width:

  grid: (E / BLOCK_E,)  — one program per block of BLOCK_E pairs
  in:   a_t [WA, BLOCK_E] i32 (VMEM), b_t [WB, BLOCK_E] i32 (VMEM)
  out:  counts [1, BLOCK_E] i32

Inside the program, B is walked in chunks of ``_CHUNK`` slots held in
vector registers; for each chunk, a loop over A's WA slots broadcasts
slot i of every pair (one ``[1, BLOCK_E]`` row) down the chunk and adds
the matches into a ``[_CHUNK, BLOCK_E]`` accumulator, reduced over the
sublanes once at the end. Only slots of A below ``sentinel`` count, so
padding never matches. The wrapper transposes the ``[E, W]`` inputs and
pads WB to a whole number of chunks with the sentinel.

The paper's hybrid decision rule (Eq. 3) lives one level up: the engine
statically routes (skew-split) edge streams either here or to the bitmap
kernel — see core/intersect.py::tpu_regime_rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["intersect_count"]

_CHUNK = 64  # B slots per register-resident chunk (8 vregs at 128 pairs)


def _kernel(a_ref, b_ref, out_ref, *, sentinel: int, wa: int, ch: int,
            n_chunks: int):
    be = out_ref.shape[-1]

    def chunk(c, acc):
        b = b_ref[pl.ds(pl.multiple_of(c * ch, ch), ch), :]  # [ch, BE]

        def slot(i, acc):
            a_i = a_ref[pl.ds(i, 1), :]  # [1, BE]: slot i of every pair
            valid = (a_i < sentinel).astype(jnp.int32)
            return acc + jnp.where(b == a_i, valid, 0)

        return jax.lax.fori_loop(0, wa, slot, acc)

    acc = jax.lax.fori_loop(
        0, n_chunks, chunk, jnp.zeros((ch, be), jnp.int32)
    )
    out_ref[...] = jnp.sum(acc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("sentinel", "block_e", "interpret"))
def intersect_count(
    rows_a: jnp.ndarray,  # [E, WA] int32 sorted, sentinel-padded
    rows_b: jnp.ndarray,  # [E, WB]
    *,
    sentinel: int,
    block_e: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """``|rows_a[e] ∩ rows_b[e]|`` per pair (int32 ``[E]``).

    ``E`` must be a multiple of ``block_e``; on the chip ``block_e`` is
    a multiple of 128 or equal to ``E``."""
    e, wa = rows_a.shape
    _, wb = rows_b.shape
    assert e % block_e == 0, (e, block_e)
    if wa == 0 or wb == 0:
        return jnp.zeros((e,), jnp.int32)
    ch = min(wb, _CHUNK)
    n_chunks = -(-wb // ch)
    if n_chunks * ch != wb:
        rows_b = jnp.pad(
            rows_b, ((0, 0), (0, n_chunks * ch - wb)),
            constant_values=sentinel,
        )
    out = pl.pallas_call(
        functools.partial(
            _kernel, sentinel=sentinel, wa=wa, ch=ch, n_chunks=n_chunks
        ),
        grid=(e // block_e,),
        in_specs=[
            pl.BlockSpec((wa, block_e), lambda i: (0, i)),
            pl.BlockSpec((n_chunks * ch, block_e), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_e), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        interpret=interpret,
    )(rows_a.T, rows_b.T)
    return out[0]
