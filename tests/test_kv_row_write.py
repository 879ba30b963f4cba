"""The decode step's KV write: each step stores only its new key/value rows,
in place in the cache the engine donates.

The reference below is the step as it was written before: every layer
rewrote its whole cache with a one-hot select of the new row and attended
the positions ``<= pos``.  The row write attends the cached positions
``< pos`` and the new row in one softmax instead, so greedy tokens must not
change, whatever the cache holds at and beyond each slot's position.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from conftest import require_devices
from repro.models import ModelConfig, build_model
from repro.models.attention import _sdpa
from repro.models.layers import apply_rope, mlp_apply, rmsnorm
from repro.models.transformer import lm_forward
from repro.serve import Request, ServeEngine

DENSE = {
    "mha": ModelConfig(name="mha", family="dense", n_layers=3, d_model=32,
                       n_heads=4, n_kv_heads=4, d_ff=64, vocab=64),
    "gqa": ModelConfig(name="gqa", family="dense", n_layers=3, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab=64),
}
# every family whose decode step writes a self-attention cache
FAMILIES = {
    **DENSE,
    "moe": ModelConfig(name="moe", family="moe", n_layers=2, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, n_experts=4,
                       top_k=2, capacity_factor=4.0),
    "hybrid": ModelConfig(name="hybrid", family="hybrid", n_layers=4, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab=64, ssm_state=16,
                          ssm_head_dim=8, ssm_chunk=8, attn_every=2),
    "vlm": ModelConfig(name="vlm", family="vlm", n_layers=4, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                       cross_attn_every=2, img_tokens=8),
    "encdec": ModelConfig(name="encdec", family="encdec", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab=64, enc_layers=2),
}
POS = np.array([0, 3, 9, 5], np.int32)  # ragged, and one slot not yet written
S_MAX = 16


def _masked_write_step(params, cfg, cache, tokens):
    """The dense decode step with the masked whole-cache write."""
    pos = cache["pos"]
    x = params["embed"][tokens][:, None].astype(jnp.dtype(cfg.dtype))

    def body(x, inp):
        gp, cg = inp
        p, h = gp["attn_0"], rmsnorm(x, gp["ln1_0"], cfg.norm_eps)
        dt = h.dtype
        q, k, v = (jnp.einsum("bsd,dhk->bshk", h, p[w].astype(dt))
                   for w in ("w_q", "w_k", "w_v"))
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        ck, cv = cg["attn_0"]["k"], cg["attn_0"]["v"]
        s_max = ck.shape[1]
        hot = (jnp.arange(s_max)[None, :] == pos[:, None])[..., None, None]
        ck = jnp.where(hot, k.astype(ck.dtype), ck)
        cv = jnp.where(hot, v.astype(cv.dtype), cv)
        mask = (jnp.arange(s_max)[None, :] <= pos[:, None])[:, None, None, None, :]
        out = _sdpa(q, ck.astype(dt), cv.astype(dt), mask)
        x = x + jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(dt))
        x = x + mlp_apply(gp["mlp_0"], rmsnorm(x, gp["ln2_0"], cfg.norm_eps), cfg.mlp)
        return x, {"attn_0": {"k": ck, "v": cv}}

    x, blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = hidden[:, -1].astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits, {"pos": pos + 1, "blocks": blocks}


def _ragged_cache(model, stale: str):
    """A cache at positions ``POS`` whose rows below each position are drawn
    from a seed, and whose rows at and beyond it are zeros or left over
    from an earlier, longer request."""
    cache = model.init_cache(len(POS), S_MAX)
    live = jnp.arange(S_MAX)[None, :, None, None] < POS[:, None, None, None]
    keys = iter(jax.random.split(jax.random.key(3), 8))

    def fill(x):
        drawn = jax.random.normal(next(keys), x.shape, jnp.float32).astype(x.dtype)
        left = (jax.random.normal(next(keys), x.shape, jnp.float32) * 3).astype(x.dtype)
        return jnp.where(live, drawn, left if stale == "left-over" else 0)

    cache["blocks"] = jax.tree.map(fill, cache["blocks"])
    cache["pos"] = jnp.asarray(POS)
    return cache


def _greedy(step, params, cache, tok, n):
    toks, logits = [], []
    for _ in range(n):
        lg, cache = step(params, cache, tok)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        logits.append(np.asarray(lg))
    return np.stack(toks), np.stack(logits), cache


@pytest.mark.parametrize("name", list(DENSE))
def test_row_write_tokens_equal_the_masked_write(name):
    """MHA and GQA, ragged positions: the same greedy tokens as the masked
    whole-cache write, step after step, and the same cache below each
    slot's position."""
    cfg = DENSE[name]
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = _ragged_cache(model, "left-over")
    tok = jnp.asarray([5, 17, 33, 60], jnp.int32)
    got, got_lg, got_cache = _greedy(jax.jit(model.decode_step), params, cache, tok, 6)
    ref_step = jax.jit(lambda p, c, t: _masked_write_step(p, cfg, c, t))
    want, want_lg, want_cache = _greedy(ref_step, params, cache, tok, 6)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_lg, want_lg, rtol=0, atol=2e-2)
    written = np.arange(S_MAX)[None, :] < (POS + 6)[:, None]
    for t in ("k", "v"):
        a = np.asarray(got_cache["blocks"]["attn_0"][t], np.float32)
        b = np.asarray(want_cache["blocks"]["attn_0"][t], np.float32)
        np.testing.assert_allclose(a[:, written], b[:, written], rtol=0, atol=5e-2)


@pytest.mark.parametrize("name", list(DENSE))
def test_rows_at_and_beyond_pos_are_never_read(name):
    """A reused slot's left-over rows at and beyond its position change
    neither its logits nor its tokens: the step reads only ``< pos`` and
    writes ``pos`` before any later step reads it."""
    model = build_model(DENSE[name])
    params = model.init(jax.random.key(1))
    tok = jnp.asarray([2, 40, 7, 11], jnp.int32)
    step = jax.jit(model.decode_step)
    clean = _greedy(step, params, _ragged_cache(model, "zeros"), tok, 5)
    stale = _greedy(step, params, _ragged_cache(model, "left-over"), tok, 5)
    np.testing.assert_array_equal(clean[0], stale[0])
    np.testing.assert_array_equal(clean[1], stale[1])


def _inputs(cfg, batch, seq, frames):
    toks = (jnp.arange(batch * seq).reshape(batch, seq) * 7 + 3) % cfg.vocab
    extra = {}
    if cfg.family == "vlm":
        extra["img_embed"] = jnp.full((batch, cfg.img_tokens, cfg.d_model), 0.01,
                                      jnp.bfloat16)
    if cfg.family == "encdec":
        extra["frames"] = jnp.full((batch, frames, cfg.d_model), 0.01, jnp.bfloat16)
    return toks, extra


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_decode_steps_match_forward(fam):
    """Prefill, then four decode steps fed the true next tokens: each step's
    logits match a full forward at its position, so every row a step
    wrote is read back right by the steps after it (the hybrid's shared
    attention, per application, among them)."""
    cfg = FAMILIES[fam]
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt, n_dec = 6, 4
    toks, extra = _inputs(cfg, 2, prompt + n_dec, frames=prompt)
    if cfg.family == "encdec":
        from repro.models.encdec import encdec_forward

        hid = encdec_forward(params, cfg, extra["frames"], toks)
    else:
        hid, _ = lm_forward(params, cfg, toks, extra.get("img_embed"))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    ref = np.asarray(hid.astype(jnp.float32) @ head.astype(jnp.float32))
    batch = {"tokens": toks[:, :prompt], **extra}
    _, cache = jax.jit(lambda p, b: model.prefill(p, b, s_max=S_MAX))(params, batch)
    step = jax.jit(model.decode_step)
    for i in range(prompt, prompt + n_dec):
        lg, cache = step(params, cache, toks[:, i])
        err = np.abs(np.asarray(lg) - ref[:, i]).max() / np.abs(ref[:, i]).max()
        assert err < 0.05, f"{fam}: position {i} diverges from the forward ({err:.4f})"


def _tiny_engine_model():
    cfg = DENSE["gqa"]
    model = build_model(cfg)
    return model, model.init(jax.random.key(4))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 64, n).astype(np.int32)


@pytest.mark.parametrize("macro_steps", [1, 4])
def test_engine_step_donates_the_cache(macro_steps):
    """A decode launch consumes the cache it is handed: the previous
    cache's buffers are deleted, so the step wrote into them."""
    model, params = _tiny_engine_model()
    eng = ServeEngine(model, params, n_slots=2, s_max=S_MAX, macro_steps=macro_steps)
    for uid in range(2):
        eng.submit(Request(uid=uid, prompt=_prompt(uid, 4), max_new_tokens=10))
    eng.macro_step()  # admits both: the splice builds a fresh cache
    before = jax.tree.leaves(eng.cache)
    eng.macro_step()
    assert macro_steps == 1 or eng.macro_blocks == 1
    assert all(leaf.is_deleted() for leaf in before)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.cache))


def _serve(requests, **kw):
    model, params = _tiny_engine_model()
    eng = ServeEngine(model, params, s_max=S_MAX, **kw)
    for uid, prompt, n in requests:
        eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n))
    return eng, {r.uid: r.out_tokens for r in eng.run()}


def test_macro_steps_give_the_scalar_tokens():
    """Fused blocks of up to 4 steps emit the tokens of 4 scalar steps."""
    reqs = [(0, _prompt(0, 5), 11), (1, _prompt(1, 3), 9)]
    fused, got = _serve(reqs, n_slots=2, macro_steps=4)
    _, want = _serve(reqs, n_slots=2, macro_steps=1)
    assert fused.macro_blocks > 0
    assert got == want


def test_a_reused_slot_gives_a_fresh_slots_tokens():
    """One slot serves a long request, then a short one: the short request's
    tokens equal those of an engine that served it alone, though the long
    one left rows beyond the short one's positions."""
    long, short = (0, _prompt(2, 9), 6), (1, _prompt(3, 3), 5)
    _, reused = _serve([long, short], n_slots=1)
    _, alone = _serve([short], n_slots=1)
    assert reused[1] == alone[1]


def test_sequence_sharded_cache_takes_the_masked_write():
    """With the cache's sequence axis sharded (KV heads that do not divide
    the model axis), and that sharding stated as the ``kv_cache`` hint, the
    step writes by an elementwise select, never a per-slot update-slice,
    and emits the row write's logits."""
    require_devices(4)
    from repro.sharding.ctx import sharding_hints
    from repro.sharding.policy import make_policy

    cfg = DENSE["gqa"]
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = _ragged_cache(model, "left-over")
    tok = jnp.asarray([5, 17, 33, 60], jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    specs = make_policy(mesh, cfg, qkv_contraction=True).cache_specs(cache)
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    kv_sh = cache_sh["blocks"]["attn_0"]["k"]
    assert kv_sh.spec[-3] == "model"  # 2 KV heads over 4: the sequence is split
    rep = NamedSharding(mesh, P())
    step = jax.jit(model.decode_step, in_shardings=(rep, cache_sh, rep),
                   out_shardings=(rep, cache_sh))
    with mesh, sharding_hints({"kv_cache": kv_sh}):
        compiled = step.lower(params, cache, tok).compile()
        lg, _ = step(params, cache, tok)
    # every instruction of the scope, fusion bodies included
    writes = [line.split("=", 1)[1].split("(", 1)[0].split()[-1]
              for line in compiled.as_text().splitlines() if "/kv_write/" in line]
    assert "select" in writes, writes
    assert "dynamic-update-slice" not in writes, writes
    want, _ = jax.jit(model.decode_step)(params, cache, tok)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want), rtol=0, atol=1e-5)
