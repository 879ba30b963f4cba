"""SPMD coded ops: block-MDS CodedLinear, BPCC batch streaming, row coding."""
import itertools

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.coded_ops import (
    CodedLinear,
    block_mds_generator,
    block_rows,
    bpcc_batched_matvec,
    encode_blocks,
    row_coded_matvec,
)
from repro.core.encoding import GaussianCode


def test_generator_any_ndata_rows_invertible():
    b = np.asarray(block_mds_generator(16, 12), np.float64)
    for pat in itertools.combinations(range(16), 4):
        keep = np.ones(16, bool)
        keep[list(pat)] = False
        s = np.linalg.svd(b[keep], compute_uv=False)
        assert s[-1] > 1e-6  # full rank for EVERY 4-erasure pattern


def test_coded_linear_exhaustive_erasures():
    cl = CodedLinear(n_data=12, n_parity=4, out_features=100)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((100, 64)).astype(np.float32)
    wc = cl.encode(jnp.asarray(w))
    x = rng.standard_normal((64, 8)).astype(np.float32)
    ref = w @ x
    scale = np.abs(ref).max()
    worst = 0.0
    for pat in itertools.combinations(range(16), 4):
        m = np.ones(16, np.float32)
        m[list(pat)] = 0.0
        y = np.asarray(cl.apply(wc, jnp.asarray(x), jnp.asarray(m)))
        worst = max(worst, np.abs(y - ref).max() / scale)
    assert worst < 1e-3  # float32 worst pattern stays ~bf16-noise level


def test_coded_linear_full_mask_systematic():
    cl = CodedLinear(n_data=14, n_parity=2, out_features=57)
    rng = np.random.default_rng(1)
    w = rng.standard_normal((57, 31)).astype(np.float32)
    wc = cl.encode(jnp.asarray(w))
    x = rng.standard_normal((31, 3)).astype(np.float32)
    y = np.asarray(cl.apply(wc, jnp.asarray(x), jnp.ones(16)))
    assert np.allclose(y, w @ x, atol=2e-4 * np.abs(w @ x).max() + 1e-5)


def test_encode_blocks_systematic_prefix():
    w = np.arange(24, dtype=np.float32).reshape(12, 2)
    coded = np.asarray(encode_blocks(jnp.asarray(w), n_data=4, n_parity=2))
    assert coded.shape == (48, 2)  # 6 blocks x 8 rows (3 rounded to the tile)
    assert np.allclose(coded[:12], w)  # systematic prefix intact
    assert not coded[12:32].any()  # the data blocks' padding rows are zero


@pytest.mark.parametrize("out, n_data, dtype, want", [
    (151_552, 14, jnp.float32, 10_832),   # glm4-9b head: ceil 10826
    (151_552, 13, jnp.float32, 11_664),   # after a parity raise: ceil 11658
    (32_064, 14, jnp.float32, 2_296),     # phi3-mini head: ceil 2291
    (512, 14, jnp.float32, 40),           # ceil 37
    (112, 14, jnp.float32, 8),            # already aligned: unchanged
    (1, 14, jnp.float32, 8),
    (100, 12, jnp.bfloat16, 16),          # 2-byte tile is 16 rows
    (100, 3, jnp.int8, 64),               # 1-byte tile is 32 rows: ceil 34
])
def test_block_rows_rounds_the_ceil_up_to_the_dtype_tile(out, n_data, dtype, want):
    tile = 8 * (4 // jnp.dtype(dtype).itemsize)
    ceil = -(-out // n_data)
    br = block_rows(out, n_data, dtype)
    assert br == want
    assert br % tile == 0 and ceil <= br < ceil + tile  # the least such multiple


@pytest.mark.parametrize("out", [57, 100, 112, 8 * 14 * 3 + 1])
def test_coded_linear_block_rows_is_the_stored_block_height(out):
    cl = CodedLinear(n_data=14, n_parity=2, out_features=out)
    wc = cl.encode(jnp.zeros((out, 4), jnp.float32))
    assert cl.block_rows == wc.shape[0] // cl.n_blocks
    assert wc.shape[0] == cl.n_blocks * cl.block_rows


def test_exact_recovery_with_tile_padding_under_every_erasure():
    """57 rows over 14 data blocks: the ceil pads 13 rows, the tile 55.
    Every pattern of at most n_parity erased blocks still recovers W x,
    and the padding rows of the data blocks are zero."""
    n_data, n_parity, out = 14, 2, 57
    cl = CodedLinear(n_data=n_data, n_parity=n_parity, out_features=out)
    br = cl.block_rows
    assert out % n_data and n_data * br - out > n_data * -(-out // n_data) - out
    rng = np.random.default_rng(7)
    w = rng.standard_normal((out, 24)).astype(np.float32)
    wc = cl.encode(jnp.asarray(w))
    assert not np.asarray(wc)[out:n_data * br].any()
    x = rng.standard_normal((24, 5)).astype(np.float32)
    ref = w @ x
    n_blocks = n_data + n_parity
    for k in range(n_parity + 1):
        for pat in itertools.combinations(range(n_blocks), k):
            m = np.ones(n_blocks, np.float32)
            m[list(pat)] = 0.0
            y = np.asarray(cl.apply(wc, jnp.asarray(x), jnp.asarray(m)))
            assert y.shape == ref.shape
            np.testing.assert_allclose(y, ref, rtol=0, atol=1e-3 * np.abs(ref).max())


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("n_data, n_parity", [(14, 2), (13, 3)])
def test_encode_blocks_device_keeps_the_tile_aligned_shape(mode, n_data, n_parity):
    """The engine's parity top-up re-encodes 14+2 as 13+3 on device: the
    same aligned block height and values as the offline encode."""
    from repro.kernels.ops import encode_blocks_device

    rng = np.random.default_rng(8)
    w = rng.standard_normal((150, 16)).astype(np.float32)
    want = np.asarray(encode_blocks(jnp.asarray(w), n_data, n_parity))
    got = np.asarray(encode_blocks_device(w, n_data, n_parity, mode=mode))
    assert got.shape == want.shape == (16 * block_rows(150, n_data), 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())


def test_bpcc_batched_matvec_arrival_mask():
    rng = np.random.default_rng(2)
    a = rng.standard_normal((20, 6)).astype(np.float32)
    x = rng.standard_normal(6).astype(np.float32)
    arrived = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
    y, rows = bpcc_batched_matvec(jnp.asarray(a), jnp.asarray(x), 5, arrived)
    assert float(rows) == 12.0
    y = np.asarray(y)
    assert np.allclose(y[0:4], a[0:4] @ x, atol=1e-5)
    assert np.all(y[4:8] == 0)          # batch 2 never arrived
    assert np.allclose(y[8:16], a[8:16] @ x, atol=1e-5)
    assert np.all(y[16:20] == 0)


def test_row_coded_matvec():
    r = 30
    rng = np.random.default_rng(3)
    a = rng.standard_normal((r, 11)).astype(np.float32)
    plan = GaussianCode(r=r, seed=4).plan(44)
    g = jnp.asarray(plan.dense_generator())
    a_hat = jnp.asarray(plan.dense_generator() @ a)
    x = rng.standard_normal(11).astype(np.float32)
    mask = np.ones(44, np.float32)
    mask[rng.permutation(44)[:10]] = 0.0
    y = np.asarray(row_coded_matvec(a_hat, jnp.asarray(x), g, jnp.asarray(mask)))
    assert np.allclose(y, a @ x, atol=5e-2)
