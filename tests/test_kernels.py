"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.bitmap_filter import bitmap_filter_pallas
from repro.kernels.group_intersect import group_match_pallas


@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("G", [1, 7, 128, 1000])
@pytest.mark.parametrize("m,W", [(1, 2), (2, 8), (3, 4), (4, 2)])
def test_bitmap_filter_sweep(k, G, m, W):
    rng = np.random.default_rng(k * 1000 + G + m * 10 + W)
    imgs = rng.integers(0, 1 << 32, size=(k, G, m, W),
                        dtype=np.uint64).astype(np.uint32)
    imgs[rng.random((k, G, m, W)) < 0.6] = 0
    x = jnp.asarray(imgs)
    out_ref = np.asarray(ref.bitmap_filter_ref(x))
    out_pal = np.asarray(bitmap_filter_pallas(x, interpret=True))
    np.testing.assert_array_equal(out_ref, out_pal)


@pytest.mark.parametrize("dtype", [np.uint32, np.int32])
def test_bitmap_filter_dtypes(dtype):
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 1 << 31, size=(2, 64, 2, 8), dtype=np.int64).astype(dtype)
    x = jnp.asarray(imgs)
    out_ref = np.asarray(ref.bitmap_filter_ref(x))
    out_pal = np.asarray(bitmap_filter_pallas(x, interpret=True))
    np.testing.assert_array_equal(out_ref, out_pal)


def test_bitmap_filter_all_pass_all_fail():
    ones = jnp.full((3, 32, 2, 4), 0xFFFFFFFF, dtype=jnp.uint32)
    assert np.asarray(bitmap_filter_pallas(ones, interpret=True)).all()
    zeros = jnp.zeros((3, 32, 2, 4), dtype=jnp.uint32)
    assert not np.asarray(bitmap_filter_pallas(zeros, interpret=True)).any()


# S crosses the 128-row lane chunk and the 2,048-row grid step; the widths
# cover the cell's (32, 32) and the 8-sublane padding of ga
@pytest.mark.parametrize("S", [1, 8, 57, 256, 127, 129, 2 * 2048 + 3])
@pytest.mark.parametrize("ga,gb", [(8, 8), (16, 32), (40, 16), (128, 128),
                                   (32, 32), (32, 64), (8, 128)])
def test_group_match_sweep(S, ga, gb):
    rng = np.random.default_rng(S * 100 + ga + gb)
    a = rng.integers(0, 500, size=(S, ga)).astype(np.int32)
    b = rng.integers(0, 500, size=(S, gb)).astype(np.int32)
    a[rng.random((S, ga)) < 0.25] = -1
    b[rng.random((S, gb)) < 0.25] = -1
    # rows that are all padding, on either side; at S = 1 the one row stays
    # real so that the case still compares matches
    if S > 1:
        a[0] = -1
        b[S - 1] = -1
    out_ref = np.asarray(ref.group_match_ref(jnp.asarray(a), jnp.asarray(b)))
    out_pal = np.asarray(
        group_match_pallas(jnp.asarray(a), jnp.asarray(b), interpret=True))
    np.testing.assert_array_equal(out_ref, out_pal)


@pytest.mark.parametrize("B", [1, 3, 9])
@pytest.mark.parametrize("G", [7, 128, 300])
def test_bitmap_filter_batched_folds_grid(B, G):
    """(B, k, G, m, W) batch axis == B independent unbatched calls."""
    rng = np.random.default_rng(B * 17 + G)
    imgs = rng.integers(0, 1 << 32, size=(B, 3, G, 2, 8),
                        dtype=np.uint64).astype(np.uint32)
    imgs[rng.random(imgs.shape) < 0.6] = 0
    x = jnp.asarray(imgs)
    out_ref = np.asarray(ref.bitmap_filter_ref(x))
    assert out_ref.shape == (B, G)
    out_pal = np.asarray(bitmap_filter_pallas(x, interpret=True))
    np.testing.assert_array_equal(out_ref, out_pal)
    for b in range(B):
        np.testing.assert_array_equal(
            out_ref[b], np.asarray(bitmap_filter_pallas(x[b], interpret=True)))


@pytest.mark.parametrize("B,S", [(1, 8), (4, 13), (6, 64), (3, 127),
                                 (2, 2051)])
def test_group_match_batched_folds_rows(B, S):
    rng = np.random.default_rng(B * 31 + S)
    a = rng.integers(0, 300, size=(B, S, 16)).astype(np.int32)
    b = rng.integers(0, 300, size=(B, S, 24)).astype(np.int32)
    a[rng.random(a.shape) < 0.25] = -1
    b[rng.random(b.shape) < 0.25] = -1
    a[0, S - 1] = -1
    b[B - 1, 0] = -1
    out_ref = np.asarray(ref.group_match_ref(jnp.asarray(a), jnp.asarray(b)))
    assert out_ref.shape == (B, S, 16)
    out_pal = np.asarray(
        group_match_pallas(jnp.asarray(a), jnp.asarray(b), interpret=True))
    np.testing.assert_array_equal(out_ref, out_pal)
    for i in range(B):
        np.testing.assert_array_equal(
            out_ref[i],
            np.asarray(group_match_pallas(jnp.asarray(a[i]), jnp.asarray(b[i]),
                                          interpret=True)))


def test_group_match_sentinel_never_matches():
    a = jnp.full((4, 8), -1, dtype=jnp.int32)
    b = jnp.full((4, 8), -1, dtype=jnp.int32)
    out = np.asarray(group_match_pallas(a, b, interpret=True))
    assert not out.any()


def test_ops_dispatch_paths_agree():
    rng = np.random.default_rng(7)
    imgs = jnp.asarray(rng.integers(0, 1 << 32, size=(2, 200, 2, 8),
                                    dtype=np.uint64).astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(ops.bitmap_filter(imgs, use_pallas=True)),
        np.asarray(ops.bitmap_filter(imgs, use_pallas=False)),
    )
    a = jnp.asarray(rng.integers(0, 99, size=(16, 16)).astype(np.int32))
    b = jnp.asarray(rng.integers(0, 99, size=(16, 24)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.group_match(a, b, use_pallas=True)),
        np.asarray(ops.group_match(a, b, use_pallas=False)),
    )


def test_vocab_mask_roundtrip_and_and():
    rng = np.random.default_rng(3)
    v = 50257
    m1 = rng.random(v) < 0.3
    m2 = rng.random(v) < 0.5
    p1 = ops.pack_vocab_mask(jnp.asarray(m1))
    p2 = ops.pack_vocab_mask(jnp.asarray(m2))
    both = ops.vocab_mask_and(jnp.stack([p1, p2]))
    un = np.asarray(ops.unpack_vocab_mask(both, v))
    np.testing.assert_array_equal(un, m1 & m2)
