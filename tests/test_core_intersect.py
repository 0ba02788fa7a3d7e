import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import intersect as it


def sorted_unique(rng, hi, k):
    return np.unique(rng.integers(0, hi, size=k))


@pytest.mark.parametrize("seed", range(5))
def test_scalar_methods_agree(seed):
    rng = np.random.default_rng(seed)
    a = sorted_unique(rng, 500, rng.integers(0, 80))
    b = sorted_unique(rng, 500, rng.integers(0, 200))
    want = len(np.intersect1d(a, b))
    assert it.ssi_scalar(a, b) == want
    assert it.binary_search_scalar(a, b) == want
    assert it.hybrid_scalar(a, b) == want
    assert it.count_bsearch_np(a, b) == want
    assert it.count_pairwise_np(a, b) == want


def test_eq3_rule():
    # balanced lists -> SSI; skewed -> binary search
    assert it.eq3_ssi_faster(100, 128)
    assert not it.eq3_ssi_faster(2, 4096)


def pad(a, w, sent):
    out = np.full(w, sent, np.int32)
    out[: len(a)] = a
    return out


@pytest.mark.parametrize("method", ["bsearch", "pairwise", "equal_pairs"])
def test_jnp_counts_match_oracle(method):
    rng = np.random.default_rng(42)
    sent = 1000
    wa, wb = 32, 64
    rows_a, rows_b, want = [], [], []
    for _ in range(50):
        a = sorted_unique(rng, sent, rng.integers(0, wa))
        b = sorted_unique(rng, sent, rng.integers(0, wb))
        rows_a.append(pad(a, wa, sent))
        # equal_pairs has no padding mask: B's padding must differ from A's
        rows_b.append(pad(b, wb, sent + (method == "equal_pairs")))
        want.append(len(np.intersect1d(a, b)))
    rows_a = jnp.asarray(np.stack(rows_a))
    rows_b = jnp.asarray(np.stack(rows_b))
    if method == "bsearch":
        got = it.count_bsearch_jnp(rows_a, rows_b, sent)
    elif method == "equal_pairs":
        got = it.count_equal_pairs_jnp(rows_a, rows_b)
    else:
        got = it.count_pairwise_jnp(rows_a, rows_b, sent)
    assert np.array_equal(np.asarray(got), np.array(want))


def test_hybrid_jnp_matches():
    rng = np.random.default_rng(3)
    sent = 500
    w = 48
    rows_a, rows_b, want = [], [], []
    for _ in range(30):
        a = sorted_unique(rng, sent, rng.integers(1, w))
        b = sorted_unique(rng, sent, rng.integers(1, w))
        rows_a.append(pad(a, w, sent))
        rows_b.append(pad(b, w, sent))
        want.append(len(np.intersect1d(a, b)))
    got = it.count_hybrid_jnp(
        jnp.asarray(np.stack(rows_a)),
        jnp.asarray(np.stack(rows_b)),
        jnp.asarray([int((r < sent).sum()) for r in rows_a]),
        jnp.asarray([int((r < sent).sum()) for r in rows_b]),
        sent,
    )
    assert np.array_equal(np.asarray(got), np.array(want))


def test_bitmap_count():
    from repro.core.csr import rows_to_bitmap_words

    rng = np.random.default_rng(9)
    sent = 256
    rows_a, rows_b, want = [], [], []
    for _ in range(20):
        a = sorted_unique(rng, sent, 30)
        b = sorted_unique(rng, sent, 50)
        rows_a.append(pad(a, 40, sent))
        rows_b.append(pad(b, 64, sent))
        want.append(len(np.intersect1d(a, b)))
    wa = rows_to_bitmap_words(np.stack(rows_a), sent)
    wb = rows_to_bitmap_words(np.stack(rows_b), sent)
    got = it.count_bitmap_jnp(jnp.asarray(wa), jnp.asarray(wb))
    assert np.array_equal(np.asarray(got), np.array(want))
