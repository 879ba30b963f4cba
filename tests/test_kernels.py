"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # image without hypothesis: deterministic shim (minihyp)
    from minihyp import given, settings, strategies as st

from repro.core.coded_ops import CodedLinear
from repro.core.decoding import get_decoder_cache
from repro.core.encoding import LTCode, GaussianCode, encode_matrix
from repro.kernels import coded_matvec, coded_matvec_decode, lt_encode, ssd_forward
from repro.kernels import ref as R
from repro.kernels.ops import encode_blocks_device, encode_rows, gaussian_encode
from repro.models.ssm import ssd_chunked


@pytest.mark.parametrize("r,m,b", [
    (64, 64, 1), (100, 70, 1), (256, 512, 4), (300, 1000, 8),
    (1, 4096, 1), (513, 129, 3),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_coded_matvec_sweep(r, m, b, dtype):
    rng = np.random.default_rng(r * 1000 + m)
    a = rng.standard_normal((r, m)).astype(dtype)
    x = (rng.standard_normal((m, b)) if b > 1 else rng.standard_normal(m)).astype(dtype)
    got = np.asarray(coded_matvec(jnp.asarray(a), jnp.asarray(x),
                                  mode="interpret"))
    want = np.asarray(R.ref_coded_matvec(jnp.asarray(a), jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * max(1, np.abs(want).max()))


@settings(max_examples=10, deadline=None)
@given(r=st.integers(1, 200), m=st.integers(1, 300), b=st.integers(1, 8),
       br=st.sampled_from([32, 128, 256]), bm=st.sampled_from([64, 256, 512]))
def test_coded_matvec_property(r, m, b, br, bm):
    rng = np.random.default_rng(r * 7 + m)
    a = rng.standard_normal((r, m)).astype(np.float32)
    x = rng.standard_normal((m, b)).astype(np.float32)
    got = np.asarray(coded_matvec(jnp.asarray(a), jnp.asarray(x),
                                  mode="interpret", block_r=br, block_m=bm))
    np.testing.assert_allclose(got, a @ x, rtol=1e-4, atol=1e-4 * max(1, np.abs(a @ x).max()))


@pytest.mark.parametrize("n_data,n_parity,out,inner,b", [
    (6, 2, 100, 64, 8),     # odd out -> padded block rows
    (12, 4, 256, 32, 1),    # matvec-shaped decode batch
    (4, 2, 64, 129, 3),     # unaligned inner dim
])
def test_coded_matvec_decode_vs_oracle(n_data, n_parity, out, inner, b):
    """Fused Pallas matmul+decode == jnp oracle == true product, per mask."""
    rng = np.random.default_rng(n_data * 100 + out)
    cl = CodedLinear(n_data=n_data, n_parity=n_parity, out_features=out)
    w = rng.standard_normal((out, inner)).astype(np.float32)
    wc = jnp.asarray(np.asarray(cl.encode(jnp.asarray(w))))
    x = rng.standard_normal((inner, b)).astype(np.float32)
    if b == 1:
        x = x[:, 0]
    cache = get_decoder_cache(n_data, n_parity)
    ref = w @ (x if x.ndim == 2 else x[:, None])
    for erased in [(), (1,), tuple(range(n_parity))]:
        m = np.ones(n_data + n_parity, np.float32)
        m[list(erased)] = 0.0
        rec = cache.recovery(jnp.asarray(m))
        got = np.asarray(coded_matvec_decode(wc, jnp.asarray(x), rec, mode="interpret"))
        want = np.asarray(coded_matvec_decode(wc, jnp.asarray(x), rec, mode="off"))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        got2 = got[:out] if got.ndim == 2 else got[:out, None]
        np.testing.assert_allclose(
            got2, ref, rtol=1e-3, atol=1e-3 * max(1, np.abs(ref).max())
        )


@settings(max_examples=8, deadline=None)
@given(n_data=st.integers(2, 12), n_parity=st.integers(1, 4),
       inner=st.integers(1, 200), b=st.integers(1, 8),
       bt=st.sampled_from([32, 128]), bm=st.sampled_from([64, 512]))
def test_coded_matvec_decode_property(n_data, n_parity, inner, b, bt, bm):
    rng = np.random.default_rng(n_data * 31 + inner)
    nb = n_data + n_parity
    br = int(rng.integers(1, 40))
    wc = rng.standard_normal((nb * br, inner)).astype(np.float32)
    x = rng.standard_normal((inner, b)).astype(np.float32)
    rec = rng.standard_normal((n_data, nb)).astype(np.float32)
    got = np.asarray(coded_matvec_decode(
        jnp.asarray(wc), jnp.asarray(x), jnp.asarray(rec),
        mode="interpret", block_t=bt, block_m=bm))
    want = np.asarray(R.ref_coded_matvec_decode(
        jnp.asarray(wc), jnp.asarray(x), jnp.asarray(rec)))
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-3 * max(1, np.abs(want).max()))


@pytest.mark.parametrize("r,q,m", [(20, 40, 64), (50, 90, 333), (8, 8, 16)])
@pytest.mark.parametrize("code", ["lt", "gaussian"])
def test_lt_encode_sweep(r, q, m, code):
    rng = np.random.default_rng(q)
    a = rng.standard_normal((r, m)).astype(np.float32)
    plan = (LTCode(r=r, seed=1) if code == "lt" else GaussianCode(r=r, seed=1)).plan(q)
    got = np.asarray(lt_encode(jnp.asarray(a), jnp.asarray(plan.indices),
                               jnp.asarray(plan.coeffs), mode="interpret"))
    want = np.asarray(R.ref_lt_encode(jnp.asarray(a), jnp.asarray(plan.indices),
                                      jnp.asarray(plan.coeffs)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # and against the dense-generator definition
    np.testing.assert_allclose(got, plan.dense_generator() @ a, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("B,S,H,P,G,N,Q", [
    (2, 64, 4, 8, 2, 16, 16),
    (1, 32, 2, 16, 1, 8, 8),
    (2, 128, 8, 4, 4, 4, 32),
])
def test_ssd_forward_matches_model_oracle(B, S, H, P, G, N, Q):
    rng = np.random.default_rng(S)
    x = jnp.asarray(rng.standard_normal((B, S, H, P)) * 0.1, jnp.float32)
    da = jnp.asarray(-np.abs(rng.standard_normal((B, S, H))) * 0.3, jnp.float32)
    b_ = jnp.asarray(rng.standard_normal((B, S, G, N)) * 0.3, jnp.float32)
    c_ = jnp.asarray(rng.standard_normal((B, S, G, N)) * 0.3, jnp.float32)
    y_k, f_k = ssd_forward(x, da, b_, c_, chunk=Q, mode="interpret")
    y_o, f_o = ssd_chunked(x, da, b_, c_, chunk=Q)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_o), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_o), rtol=1e-4, atol=1e-5)


def test_ssd_forward_with_initial_state():
    rng = np.random.default_rng(9)
    B, S, H, P, G, N = 1, 16, 2, 4, 1, 8
    x = jnp.asarray(rng.standard_normal((B, S, H, P)) * 0.1, jnp.float32)
    da = jnp.asarray(-np.abs(rng.standard_normal((B, S, H))) * 0.3, jnp.float32)
    b_ = jnp.asarray(rng.standard_normal((B, S, G, N)) * 0.3, jnp.float32)
    c_ = jnp.asarray(rng.standard_normal((B, S, G, N)) * 0.3, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, H, P, N)) * 0.1, jnp.float32)
    y_k, f_k = ssd_forward(x, da, b_, c_, chunk=8, mode="interpret", h0=h0)
    y_o, f_o = ssd_chunked(x, da, b_, c_, chunk=8, h0=h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_o), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_o), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("q,r,m", [
    (37, 64, 129),    # nothing aligned
    (128, 200, 512),  # aligned output panel
    (5, 7, 3),        # degenerate tiny
    (1, 513, 640),    # single coded row, padded contraction
])
def test_gaussian_encode_kernel_vs_oracle(q, r, m):
    """Tiled dense encode kernel == the jnp oracle == plain G @ A."""
    rng = np.random.default_rng(q * 17 + m)
    g = rng.standard_normal((q, r)).astype(np.float32)
    a = rng.standard_normal((r, m)).astype(np.float32)
    got = np.asarray(gaussian_encode(jnp.asarray(g), jnp.asarray(a), mode="interpret"))
    want = np.asarray(R.ref_gaussian_encode(jnp.asarray(g), jnp.asarray(a)))
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-3 * max(1, np.abs(want).max()))
    np.testing.assert_allclose(got, g @ a, rtol=1e-3,
                               atol=1e-3 * max(1, np.abs(g @ a).max()))


@settings(max_examples=8, deadline=None)
@given(q=st.integers(1, 150), r=st.integers(1, 180), m=st.integers(1, 300),
       bq=st.sampled_from([32, 128]), bk=st.sampled_from([64, 512]))
def test_gaussian_encode_property(q, r, m, bq, bk):
    rng = np.random.default_rng(q * 13 + r)
    g = rng.standard_normal((q, r)).astype(np.float32)
    a = rng.standard_normal((r, m)).astype(np.float32)
    got = np.asarray(gaussian_encode(jnp.asarray(g), jnp.asarray(a),
                                     mode="interpret", block_q=bq, block_r=bk))
    want = g @ a
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-3 * max(1, np.abs(want).max()))


@pytest.mark.parametrize("code", ["lt", "gaussian"])
def test_encode_rows_matches_host_encode(code):
    """The reserve-slice device encode == the host encode_matrix slice —
    the executor's top-up rows decode against the same generator rows."""
    r, m, cap = 64, 48, 100
    rng = np.random.default_rng(3)
    a = rng.standard_normal((r, m)).astype(np.float32)
    plan = (LTCode(r, seed=1) if code == "lt" else GaussianCode(r, seed=1)).plan(cap)
    full = encode_matrix(a, plan)
    for mode in ("interpret", "off"):
        sl = np.asarray(encode_rows(a, plan, 70, cap, mode=mode))
        np.testing.assert_allclose(
            sl, full[70:cap], rtol=1e-3, atol=1e-3 * max(1, np.abs(full).max())
        )
    with pytest.raises(ValueError):
        encode_rows(a, plan, 80, cap + 1)


def test_encode_blocks_device_matches_einsum():
    """Block-MDS head re-encode through the kernel == coded_ops einsum."""
    from repro.core.coded_ops import encode_blocks

    rng = np.random.default_rng(4)
    w = rng.standard_normal((50, 16)).astype(np.float32)
    for n_data, n_parity in [(12, 4), (13, 3), (14, 2)]:
        want = np.asarray(encode_blocks(jnp.asarray(w), n_data, n_parity))
        for mode in ("interpret", "off"):
            got = np.asarray(encode_blocks_device(w, n_data, n_parity, mode=mode))
            np.testing.assert_allclose(
                got, want, rtol=1e-4, atol=1e-4 * max(1, np.abs(want).max())
            )


def test_kernel_off_mode_is_reference():
    rng = np.random.default_rng(10)
    a = rng.standard_normal((32, 48)).astype(np.float32)
    x = rng.standard_normal(48).astype(np.float32)
    got = np.asarray(coded_matvec(jnp.asarray(a), jnp.asarray(x), mode="off"))
    np.testing.assert_allclose(got, a @ x, rtol=1e-5, atol=1e-5)


def test_default_mode_follows_the_platform():
    """With no mode the wrappers take the platform's path: the jnp
    reference off a TPU (never the interpreter), bit for bit."""
    from repro.kernels.ops import platform_mode

    assert platform_mode() == "off"  # the tests run on the CPU
    rng = np.random.default_rng(12)
    a = jnp.asarray(rng.standard_normal((40, 24)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((24, 3)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(coded_matvec(a, x)), np.asarray(coded_matvec(a, x, mode="off"))
    )
    g = jnp.asarray(rng.standard_normal((5, 40)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(gaussian_encode(g, a)),
        np.asarray(gaussian_encode(g, a, mode="off")),
    )

