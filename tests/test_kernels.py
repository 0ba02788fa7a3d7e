"""Per-kernel interpret-mode validation: sweep shapes/dtypes, assert
allclose vs the pure-jnp oracle in kernels/ref.py."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


def pad_sorted(rng, e, w, sentinel, max_fill=None):
    out = np.full((e, w), sentinel, np.int32)
    for i in range(e):
        k = rng.integers(0, (max_fill or w) + 1)
        vals = np.unique(rng.integers(0, sentinel, size=k))
        out[i, : len(vals)] = vals
    return out


@pytest.mark.parametrize("e,wa,wb,block_e", [
    (128, 16, 32, 64),
    (256, 64, 128, 128),
    (128, 8, 200, 128),  # non-multiple-of-128 width
    (256, 130, 70, 128),  # B padded to whole chunks with the sentinel
    (8, 3, 37, 8),  # tiny streaming batch: one block spans it
])
def test_intersect_count(e, wa, wb, block_e):
    rng = np.random.default_rng(0)
    sent = 4096
    a = jnp.asarray(pad_sorted(rng, e, wa, sent))
    b = jnp.asarray(pad_sorted(rng, e, wb, sent))
    got = ops.intersect_count(a, b, sentinel=sent, block_e=block_e,
                              interpret=True)
    want = ref.intersect_count_ref(a, b, sentinel=sent)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend,want", [
    ("cpu", True), ("tpu", False), ("gpu", None),
])
def test_default_interpret_by_platform(monkeypatch, backend, want):
    """Interpreted on the CPU, compiled on a TPU, refused elsewhere."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.default_interpret()
    else:
        assert ops.default_interpret() is want


@pytest.mark.parametrize("e,w,block_e", [(256, 8, 128), (512, 33, 256)])
def test_bitmap_popcount(e, w, block_e):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    got = ops.bitmap_intersect_count(a, b, block_e=block_e, interpret=True)
    want = ref.bitmap_intersect_count_ref(a, b)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,d,b,l,mode,dtype", [
    (64, 16, 16, 4, "sum", np.float32),
    (128, 32, 8, 7, "mean", np.float32),
    (64, 8, 16, 3, "sum", np.float16),
])
def test_embedding_bag(n, d, b, l, mode, dtype):
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(size=(n, d)).astype(dtype))
    ids = jnp.asarray(rng.integers(0, n, size=(b, l)).astype(np.int32))
    mask = jnp.asarray(rng.random((b, l)) < 0.8)
    got = ops.embedding_bag(table, ids, mask, mode=mode, block_b=4,
                            interpret=True)
    want = ref.embedding_bag_ref(table, ids, mask, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("e,d,n,block_e,rows", [
    (512, 16, 64, 128, 32),
    (1024, 64, 200, 512, 128),
])
def test_segment_sum_sorted(e, d, n, block_e, rows):
    rng = np.random.default_rng(3)
    seg = np.sort(rng.integers(0, n, size=e)).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(e, d)).astype(np.float32))
    got = ops.segment_sum_sorted(vals, jnp.asarray(seg), num_segments=n,
                                 block_e=block_e, rows=rows, interpret=True)
    want = ref.segment_sum_sorted_ref(vals, jnp.asarray(seg), num_segments=n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 64, 0.0), (True, 0, 30.0), (False, 0, 0.0),
])
def test_flash_attention_kernel(causal, window, softcap):
    rng = np.random.default_rng(4)
    b, s, dh = 2, 256, 32
    q = jnp.asarray(rng.normal(size=(b, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, dh)).astype(np.float32))
    from repro.kernels.flash_attention import flash_attention

    got = flash_attention(q, k, v, scale=0.2, causal=causal, window=window,
                          softcap=softcap, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=0.2, causal=causal,
                                   window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gqa_wrapper():
    rng = np.random.default_rng(5)
    b, s, kh, g, dh = 1, 128, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, kh, g, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, kh, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, kh, dh)).astype(np.float32))
    got = ops.flash_attention_gqa(q, k, v, scale=0.25, block_q=64,
                                  block_k=64, interpret=True)
    from repro.models.attention import flash_attention_jnp

    want = flash_attention_jnp(q, k, v, scale=0.25, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_bitmap_vs_rows_cross_check():
    """bitmap kernel == intersect kernel on the same underlying sets."""
    from repro.core.csr import rows_to_bitmap_words

    rng = np.random.default_rng(6)
    e, w, sent = 128, 24, 512
    a = pad_sorted(rng, e, w, sent)
    b = pad_sorted(rng, e, w, sent)
    c1 = ops.intersect_count(jnp.asarray(a), jnp.asarray(b), sentinel=sent,
                             block_e=64, interpret=True)
    wa = jnp.asarray(rows_to_bitmap_words(a, sent))
    wb = jnp.asarray(rows_to_bitmap_words(b, sent))
    c2 = ops.bitmap_intersect_count(wa, wb, block_e=64, interpret=True)
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
