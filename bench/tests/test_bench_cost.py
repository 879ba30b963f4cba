"""Operation and byte counts by hand at smoke size, and the peaks table."""
import jax
import pytest

from harness import cost

SMOKE = dict(family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
             head_dim=16, d_ff=128, vocab=512, param_dtype="bfloat16",
             coded=True, coded_parity=2, name="smoke")


def _shapes(**over):
    from repro.models.config import ModelConfig
    from repro.models.registry import build_model

    model = build_model(ModelConfig(**{**SMOKE, **over}))
    return model.param_shapes(), model.cache_shapes(2, 32)


def test_decode_step_by_hand():
    params, cache = _shapes()
    shp = cost.DenseShape.of(SMOKE, 16)
    flops, nbytes = cost.decode_step_cost(params, cache, shp, [3, 10])
    # matmuls: 2 layers x (4 x 64*4*16 attention + 3 x 64*128 mlp) = 81920
    # params, 2 flops each for 2 tokens; attention over 4 + 11 keys, 4 heads
    # of 16, 2 layers; the coded head: 14 of 16 blocks of 40 rows of 64
    # (ceil(512 / 14) = 37 rows, stored rounded up to the float32 tile of 8)
    assert flops == 2 * 81920 * 2 + 4 * 2 * 4 * 16 * 15 + 2 * 560 * 64 * 2
    # bfloat16 matmul weights, float32 norms (2 x [2, 64] + [64]), keys and
    # values of 15 attended and 2 written positions (2 layers x 4 heads x 16
    # x bf16, k and v), 2 embedding rows, 14/16 of the float32 coded head
    # plus its input and output columns
    assert nbytes == (81920 * 2 + 2 * 2 * 64 * 4 + 64 * 4 + 17 * 2 * 2 * 4 * 16 * 2
                      + 2 * 64 * 2 + 640 * 64 * 4 * 14 / 16 + (64 + 560) * 2 * 4)


def test_counts_follow_the_dtype():
    b16 = cost.decode_step_cost(*_shapes(), cost.DenseShape.of(SMOKE, 16), [5])
    f32 = cost.decode_step_cost(*_shapes(param_dtype="float32"),
                                cost.DenseShape.of(SMOKE, 16), [5])
    assert f32[0] == b16[0]
    assert f32[1] - b16[1] == 81920 * 2 + 64 * 2  # weights and embedding row


def test_coded_head_matvec_by_hand():
    w = jax.ShapeDtypeStruct((592, 64), "float32")
    flops, nbytes = cost.coded_head_cost(w, 8, 14, 2)
    assert flops == 2 * 518 * 64 * 8
    assert nbytes == 592 * 64 * 4 * 14 / 16 + (64 + 518) * 8 * 4


def test_least_seconds_takes_the_larger_bound():
    pk = cost.peaks("TPU v5 lite")
    assert cost.least_seconds(197e12, 0.0, pk) == pytest.approx(1.0)
    assert cost.least_seconds(0.0, 819e9, pk) == pytest.approx(1.0)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no peaks"):
        cost.peaks(kind)
