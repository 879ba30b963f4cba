"""Cost model + dispatch table: the ``kernel_mode="auto"`` contract.

DESIGN.md §11: auto resolves explicit > table > analytical model, never
dispatches to the interpreter, and is bit-identical to the explicit mode it
resolves to (dispatch chooses WHICH compiled program runs, it must never
change what the program computes).  The committed table is validated here
too — winners inside the documented cost-model error bound, no
interpret-mode winners — so a bad regeneration fails the unit suite, not
just the bench gate.
"""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.coded_ops import CodedLinear
from repro.kernels import cost, dispatch
from repro.kernels.dispatch import (
    Decision,
    DispatchTable,
    choose_coded_linear,
    choose_encode,
    choose_matvec,
    default_table_path,
    set_table_path,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED = os.path.join(REPO, "reports", "bench", "autotune.json")


@pytest.fixture(autouse=True)
def _restore_table():
    """Every test leaves the dispatch singleton pointing at the default."""
    yield
    set_table_path(None)


def _apply_setup(out=256, inner=128, b=4, n_data=12, n_parity=4, seed=0):
    rng = np.random.default_rng(seed)
    cl = CodedLinear(n_data=n_data, n_parity=n_parity, out_features=out)
    w = rng.standard_normal((out, inner)).astype(np.float32)
    wc = jnp.asarray(np.asarray(cl.encode(jnp.asarray(w))))
    x = jnp.asarray(rng.standard_normal((inner, b)).astype(np.float32))
    m = np.ones(n_data + n_parity, np.float32)
    m[[1, 7]] = 0.0
    return cl, w, wc, x, jnp.asarray(m)


# --------------------------------------------------------------------------
# analytical cost model
# --------------------------------------------------------------------------
def test_cost_model_orders_candidates_sanely():
    """On the CPU preset the in-graph SVD must price above the cached
    default at serving shapes — that ordering is the seed's measured truth
    and what the analytical fallback must reproduce with no table."""
    hw = cost.preset("cpu")
    costs = cost.candidate_costs("coded_linear", "cpu", out=1024, inner=256,
                                 batch=8, n_data=12, n_parity=4)
    assert set(costs) >= {"default", "svd", "fused"}
    us = {k: v.predicted_us(hw) for k, v in costs.items()}
    assert us["svd"] > us["default"]
    assert us["svd"] > us["fused"]


def test_predict_best_returns_candidate_with_params():
    for backend in ("cpu", "tpu"):
        hw = cost.preset(backend)
        impl, us, params = cost.predict_best(
            "coded_linear", backend, hw,
            out=1024, inner=256, batch=8, n_data=12, n_parity=4)
        assert us > 0 and isinstance(params, dict)
    # TPU never picks the in-graph SVD (not lowerable into the step program)
    assert impl != "svd"


def test_tpu_tiles_fit_vmem_budget():
    for geom in [dict(out=4096, inner=1024, batch=8, n_data=12, n_parity=4),
                 dict(out=1024, inner=256, batch=8, n_data=12, n_parity=4)]:
        params = cost.tile_params("coded_linear", **geom)
        assert params, "tile chooser returned no tiles"
        for v in params.values():
            assert v > 0


def test_fit_hardware_recovers_constants():
    """NNLS calibration: synthesize timings from known constants, fit, and
    the fitted model must reprice the samples within the flag threshold."""
    true = cost.preset("cpu")
    samples = []
    for shape in [(1024, 256, 8), (256, 512, 4), (4096, 1024, 8)]:
        costs = cost.candidate_costs(
            "coded_linear", "cpu",
            out=shape[0], inner=shape[1], batch=shape[2],
            n_data=12, n_parity=4)
        for kc in costs.values():
            samples.append((kc, kc.predicted_us(true)))
    fitted = cost.fit_hardware(samples, base=true)
    for kc, us in samples:
        assert cost.model_error(kc.predicted_us(fitted), us) \
            <= cost.MODEL_ERROR_FLAG


# --------------------------------------------------------------------------
# the committed table
# --------------------------------------------------------------------------
@pytest.mark.skipif(not os.path.exists(COMMITTED),
                    reason="no committed autotune table")
def test_committed_table_is_healthy():
    tab = DispatchTable.load(COMMITTED)
    assert tab is not None, "committed table unparseable or wrong version"
    assert tab.entries, "committed table is empty"
    for e in tab.entries.values():
        where = f"{e['op']} {e['shape']} [{e['backend']}]"
        assert e.get("mode") != "interpret", \
            f"interpret-mode winner committed at {where}"
        if e.get("source") == "measured" and e.get("model_error") is not None:
            assert e["model_error"] <= cost.MODEL_ERROR_BOUND, \
                f"winner at {where} is {e['model_error']:.2f}x off the model"


@pytest.mark.skipif(not os.path.exists(COMMITTED),
                    reason="no committed autotune table")
def test_table_roundtrip_identical_decisions(tmp_path):
    """Save -> load -> every benched shape resolves to the same decision."""
    with open(COMMITTED) as f:
        doc = json.load(f)
    copy = tmp_path / "autotune.json"
    copy.write_text(json.dumps(doc))
    set_table_path(COMMITTED)
    before = [choose_coded_linear(1024, 256, 8, 12, 4, backend="cpu"),
              choose_encode("gaussian", 64, 256, 512, backend="cpu")]
    set_table_path(str(copy))
    after = [choose_coded_linear(1024, 256, 8, 12, 4, backend="cpu"),
             choose_encode("gaussian", 64, 256, 512, backend="cpu")]
    assert before == after
    assert all(d.source == "table" for d in before)


# --------------------------------------------------------------------------
# dispatch resolution
# --------------------------------------------------------------------------
def test_missing_table_falls_back_to_model(tmp_path):
    set_table_path(str(tmp_path / "nope.json"))
    d = choose_coded_linear(1024, 256, 8, 12, 4)
    assert d.source == "model" and d.predicted_us > 0
    # and apply still computes the right thing through the fallback
    cl, w, wc, x, m = _apply_setup()
    got = np.asarray(cl.apply(wc, x, m, kernel_mode="auto"))
    np.testing.assert_allclose(got, w @ np.asarray(x), rtol=1e-4, atol=1e-3)


def test_corrupt_table_falls_back_to_model(tmp_path):
    bad = tmp_path / "autotune.json"
    bad.write_text("{not json")
    set_table_path(str(bad))
    d = choose_matvec(512, 512, 4)
    assert d.source == "model"


def test_unseen_shape_uses_model_fallback(tmp_path):
    """A real table that has never seen the shape -> analytical fallback,
    priced with the table's FITTED hardware constants."""
    doc = {"version": 1,
           "hardware": {"cpu": cost.preset("cpu").as_dict()},
           "entries": [{"op": "coded_linear", "backend": "cpu",
                        "shape": "1024x256x8", "dtype": "float32",
                        "geometry": {"n_data": 12, "n_parity": 4},
                        "impl": "default", "mode": None, "params": {},
                        "source": "measured"}]}
    p = tmp_path / "autotune.json"
    p.write_text(json.dumps(doc))
    set_table_path(str(p))
    hit = choose_coded_linear(1024, 256, 8, 12, 4, backend="cpu")
    miss = choose_coded_linear(999, 333, 2, 12, 4, backend="cpu")
    assert hit.source == "table" and hit.impl == "default"
    assert miss.source == "model"
    # geometry mismatch at the same shape is a miss too, not a wrong hit
    other_geom = choose_coded_linear(1024, 256, 8, 6, 2, backend="cpu")
    assert other_geom.source == "model"


def test_interpret_entries_are_never_dispatched(tmp_path):
    """A table built under the Pallas interpreter (mode="interpret") must
    be rejected at lookup — auto falls through to the model."""
    doc = {"version": 1, "hardware": {},
           "entries": [{"op": "coded_matvec", "backend": "cpu",
                        "shape": "512x512x4", "dtype": "float32",
                        "impl": "pallas", "mode": "interpret",
                        "params": {}, "source": "measured"}]}
    p = tmp_path / "autotune.json"
    p.write_text(json.dumps(doc))
    set_table_path(str(p))
    d = choose_matvec(512, 512, 4, backend="cpu")
    assert d.source == "model" and d.mode != "interpret"


def test_uncacheable_geometry_stays_on_default():
    d = choose_coded_linear(64, 32, 2, 19, 2)
    assert d.impl == "default" and d.kernel_mode is None


def test_decision_kernel_mode_mapping():
    assert Decision("coded_linear", "default", None).kernel_mode is None
    assert Decision("coded_linear", "svd", None).kernel_mode == "svd"
    assert Decision("coded_linear", "fused", "off").kernel_mode == "off"


# --------------------------------------------------------------------------
# auto == explicit, bit for bit
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(256, 128, 4), (1024, 256, 8)])
def test_auto_bit_identical_to_resolved_explicit(shape):
    """auto must run THE SAME compiled program as the mode it resolves to —
    jitted, like the serving step."""
    out, inner = shape[0], shape[1]
    cl, w, wc, x, m = _apply_setup(out=out, inner=inner, b=8)
    d = choose_coded_linear(out, inner, 8, 12, 4)
    f_auto = jax.jit(lambda wc_, x_, m_: cl.apply(wc_, x_, m_,
                                                  kernel_mode="auto"))
    f_exp = jax.jit(lambda wc_, x_, m_: cl.apply(
        wc_, x_, m_, kernel_mode=d.kernel_mode, **d.params))
    a, b_ = np.asarray(f_auto(wc, x, m)), np.asarray(f_exp(wc, x, m))
    np.testing.assert_array_equal(a, b_)


def test_env_override_points_singleton(tmp_path, monkeypatch):
    p = tmp_path / "autotune.json"
    p.write_text(json.dumps({"version": 1, "hardware": {}, "entries": []}))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(p))
    assert default_table_path() == str(p)
    dispatch.invalidate()
    assert dispatch.get_table() is not None
    assert dispatch.get_table().entries == {}


# --------------------------------------------------------------------------
# the serve-engine threading seam
# --------------------------------------------------------------------------
def test_head_kernel_mode_ctxvar():
    from repro.sharding.ctx import current_head_kernel_mode, head_kernel_mode

    assert current_head_kernel_mode() is None
    with head_kernel_mode("auto"):
        assert current_head_kernel_mode() == "auto"
        with head_kernel_mode(None):  # None = no-op passthrough
            assert current_head_kernel_mode() == "auto"
    assert current_head_kernel_mode() is None


def test_unknown_hardware_is_an_error():
    """Another chip's peaks are no prior: a backend or TPU device kind with
    no preset raises instead of borrowing the v5e numbers."""
    assert cost.preset("tpu", "TPU v5 lite") is cost.preset("tpu")
    assert cost.preset("cpu", "cpu") is cost.preset("cpu")
    with pytest.raises(ValueError, match="device kind"):
        cost.preset("tpu", "TPU v4")
    with pytest.raises(ValueError, match="backend"):
        cost.preset("gpu")
    # the dispatcher checks the process's own device on every resolution
    d = choose_matvec(512, 512, 4)
    assert d.mode in ("off", None)

