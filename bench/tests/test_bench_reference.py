"""The engine's prefill-then-decode logits against the plain dense reference.

At a small size on the CPU: requests of different prompt lengths are
prefilled one at a time (B=1), spliced into the engine's batch cache and
decoded through it at ragged positions, with two of the coded head's 16
blocks erased at every step.  Then one more decode step's logits, taken
from the engine's cache, are compared with the reference's full forward
over each prompt and its served tokens.
"""
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from harness import spec
from harness.traffic import seed_key

SMALL = dict(name="small", family="dense", reference="dense", n_layers=2,
             d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=512,
             rope_theta=10000.0, norm_eps=1e-5, coded=True, coded_parity=2)
reference = spec.load().reference(SMALL)
MASK = np.ones(16, np.float32)
MASK[[3, 11]] = 0.0
# With float32 weights and activations the program still keeps cached keys
# and values in bfloat16 (8 significant bits): decode through the cache
# then reads ~1e-3 of the largest logit (1.0e-3 to 1.2e-3 over four seeds);
# summation order and the float32 erasure decode add ~1e-5.  bfloat16
# weights and activations read 4.4e-3 to 5.5e-3 on the same seeds, so
# 2.5e-3 passes the first with 2x room and fails the second.
RTOL = 2.5e-3
SEED = 2**33 + 17


def _engine_logits(dtype: str):
    from repro.models.config import ModelConfig
    from repro.models.registry import build_model
    from repro.serve import Request, ServeEngine

    cfg = {**SMALL, "param_dtype": dtype, "dtype": dtype}
    model = build_model(ModelConfig(**{k: v for k, v in cfg.items() if k != "reference"}))
    params = jax.jit(model.init)(seed_key(SEED))
    eng = ServeEngine(model, params, n_slots=3, s_max=64, mask_fn=lambda: MASK)
    g = np.random.default_rng(0)
    for uid, (n, new) in enumerate([(5, 6), (12, 5), (20, 8)]):
        eng.submit(Request(uid=uid, prompt=g.integers(0, 512, n).astype(np.int32),
                           max_new_tokens=new))
    eng.step()
    eng.step()
    reqs = [r for r in eng.slots if r is not None]
    assert len(reqs) == 3 and all(len(r.out_tokens) == 3 for r in reqs)
    last = jnp.asarray([r.out_tokens[-1] for r in reqs], jnp.int32)
    logits, _ = jax.jit(model.decode_step)(params, eng.cache, last, jnp.asarray(MASK))
    seqs = [np.concatenate([r.prompt, r.out_tokens]) for r in reqs]
    return cfg, np.asarray(logits, np.float64), seqs


def _rel_err(dtype: str) -> float:
    cfg, got, seqs = _engine_logits(dtype)
    ref = reference.last_logits(cfg, SEED, seqs, 64).astype(np.float64)
    assert got.shape == ref.shape == (3, 512)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def test_engine_agrees_with_reference_in_float32():
    assert _rel_err("float32") <= RTOL


def test_the_comparison_fails_in_bfloat16():
    assert _rel_err("bfloat16") > RTOL


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_draws_the_programs_weights(dtype):
    """The reference's weights from the seed are the program's, bit for bit
    (it takes nothing the program made, so they must be drawn alike)."""
    from repro.models.config import ModelConfig
    from repro.models.registry import build_model

    cfg = {**SMALL, "param_dtype": dtype}
    mcfg = ModelConfig(**{k: v for k, v in cfg.items() if k != "reference"})
    params = jax.jit(build_model(mcfg).init)(seed_key(SEED))
    dm = reference.Dims.of(cfg)
    key = seed_key(SEED)
    _, k_blocks, k_head = reference._top_keys(key)
    names = {"wq": ("attn_0", "w_q"), "wk": ("attn_0", "w_k"), "wv": ("attn_0", "w_v"),
             "wo": ("attn_0", "w_o"), "wg": ("mlp_0", "w_gate"), "wu": ("mlp_0", "w_up"),
             "wd": ("mlp_0", "w_down")}
    for i, lk in enumerate(jax.random.split(k_blocks, dm.n_layers)):
        w = reference._layer_weights(lk, dm)
        for mine, (grp, leaf) in names.items():
            np.testing.assert_array_equal(
                np.asarray(w[mine]), np.asarray(params["blocks"][grp][leaf][i], np.float32))
    head = reference._dense(k_head, (dm.d_model, dm.vocab), jnp.dtype(dtype), dm.d_model)
    np.testing.assert_array_equal(np.asarray(head), np.asarray(params["lm_head"], np.float32))


def test_dense_reference_is_unchanged_by_its_move():
    """``references/dense.py`` gives, to the bit, the gaps, the control's
    gaps and the last logits that ``bench/reference.py`` gave before it
    moved (``data/dense_reference_gaps.npz``, written by that module on
    the CPU for this configuration, seed and these requests)."""
    cfg = {**SMALL, "n_kv_heads": 2, "param_dtype": "bfloat16", "dtype": "bfloat16"}
    g = np.random.default_rng(1234)
    prompts = [g.integers(0, 512, n).astype(np.int32) for n in (5, 17, 9)]
    served = [g.integers(0, 512, n).astype(np.int32) for n in (6, 3, 11)]
    gaps, gaps_c = reference.logit_gaps(cfg, SEED, prompts, served, 32, control=True)
    last = reference.last_logits(
        cfg, SEED, [np.concatenate([p, s]) for p, s in zip(prompts, served)], 32)
    want = np.load(Path(__file__).parent / "data" / "dense_reference_gaps.npz")
    np.testing.assert_array_equal(np.concatenate(gaps), want["gaps"])
    np.testing.assert_array_equal(np.concatenate(gaps_c), want["gaps_c"])
    np.testing.assert_array_equal(last, want["last"])
