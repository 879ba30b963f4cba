"""HLO analyzer: trip-count expansion, dot FLOPs, collective accounting."""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.utils.hlo import analyze_hlo, collective_bytes, roofline


def test_xla_cost_analysis_counts_scan_once():
    """Documents WHY analyze_hlo exists: XLA counts while bodies once."""
    def body(x, _):
        return jnp.tanh(x @ x), None

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y.sum()

    c = jax.jit(scanned).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    ca = c.cost_analysis()
    xla_flops = (ca[0] if isinstance(ca, (list, tuple)) else ca)["flops"]
    per_iter = 2 * 64**3
    assert xla_flops < 2 * per_iter  # body counted once, not x10


@pytest.mark.parametrize("length", [1, 7, 13])
def test_analyzer_expands_trip_counts(length):
    def body(x, _):
        return jnp.tanh(x @ x), None

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=length)
        return y.sum()

    c = jax.jit(scanned).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    costs = analyze_hlo(c.as_text())
    expect = length * 2 * 128**3
    assert costs.flops == pytest.approx(expect, rel=0.05)


def test_analyzer_nested_scans():
    def inner(x, _):
        return jnp.tanh(x @ x), None

    def outer(x, _):
        y, _ = jax.lax.scan(inner, x, None, length=5)
        return y, None

    def nested(x):
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y.sum()

    c = jax.jit(nested).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    costs = analyze_hlo(c.as_text())
    assert costs.flops == pytest.approx(15 * 2 * 128**3, rel=0.05)


def test_analyzer_hbm_bytes_scale_with_trips():
    def body(x, _):
        return jnp.tanh(x @ x), None

    def make(n):
        def f(x):
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y.sum()
        c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
        return analyze_hlo(c.as_text()).hbm_bytes

    b2, b8 = make(2), make(8)
    assert 2.5 < b8 / b2 < 4.5  # ~4x modulo fixed overhead


def test_collective_bytes_text_parser():
    text = """
  %all-gather.1 = bf16[8,128]{1,0} all-gather(%x), replica_groups=...
  %all-reduce.2 = f32[256]{0} all-reduce(%y), to_apply=%add
  %ar.done = f32[256]{0} all-reduce-done(%ar.start)
  %all-to-all.3 = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%a, %b)
"""
    stats = collective_bytes(text)
    assert stats.bytes_by_op["all-gather"] == 8 * 128 * 2
    assert stats.bytes_by_op["all-reduce"] == 256 * 4 * 2  # 2x wire multiplier
    assert stats.bytes_by_op["all-to-all"] == 2 * 16 * 4
    assert stats.count == 3  # -done not counted


def test_analyzer_counts_sharded_collectives():
    """A sharded matmul inside a scan: collectives x trip count."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run via test_multidevice subprocess)")


def test_coded_decode_step_hlo_has_no_svd():
    """ISSUE 1 acceptance: the masked CodedLinear.apply step program must
    carry NO SVD (or any other) custom-call — the DecoderCache turns the
    per-step decode into gather + matmul.  The seed SVD path is kept as the
    positive control that the marker detection actually works."""
    from repro.core.coded_ops import CodedLinear, decode_blocks_svd

    cl = CodedLinear(n_data=12, n_parity=4, out_features=128)
    rng = np.random.default_rng(0)
    wc = cl.encode(jnp.asarray(rng.standard_normal((128, 32)).astype(np.float32)))
    x = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
    m = jnp.ones(16, jnp.float32)

    step = jax.jit(cl.apply).lower(wc, x, m).compile().as_text()
    assert "custom-call" not in step and "Svd" not in step

    def seed_apply(wc_, x_, m_):
        yc = (wc_ @ x_).reshape(cl.n_blocks, cl.block_rows, -1)
        return decode_blocks_svd(yc, m_, cl.n_data, cl.n_parity)

    control = jax.jit(seed_apply).lower(wc, x, m).compile().as_text()
    assert "custom-call" in control  # e.g. lapack_*gesdd on CPU


def test_roofline_terms():
    rl = roofline(flops=197e12, hbm_bytes=819e9, wire_bytes=50e9,
                  model_flops=98.5e12)
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(1.0)
    assert rl.collective_s == pytest.approx(1.0)
    assert rl.useful_fraction == pytest.approx(0.5)
    assert rl.mfu_bound == pytest.approx(0.5)
    rl2 = roofline(flops=1e12, hbm_bytes=819e9 * 3, wire_bytes=0)
    assert rl2.dominant == "memory"


def test_op_names_follow_named_scopes():
    """Every instruction of the entry computation maps to its ``op_name``;
    a named scope shows as a path segment; fusion bodies and reducers,
    which never run as operations of their own, are left out."""
    from repro.utils.hlo import op_names

    def f(w, x):
        with jax.named_scope("coded_head"):
            y = jnp.dot(w, x) * 2
        return jnp.argmax(y + 1)

    text = jax.jit(f).lower(jnp.ones((64, 32)), jnp.ones(32)).compile().as_text()
    names = op_names(text)
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert set(names) == set(re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) =", entry, re.M))
    assert any("/coded_head/" in op for op in names.values())
