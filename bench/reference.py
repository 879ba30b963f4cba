"""Plain float32 reference of the dense decoder family, and its control.

The reference imports nothing of the program and takes nothing it made.
Its weights come from the seed: the same ``jax.random`` draws, in the same
order and with the same scales, as the dense family's initialisation, kept
here as a copy, and rounded to the configuration's parameter dtype as the
served weights are.  It computes everything else in float32 at
``Precision.HIGHEST``: teacher-forced full forward passes over the served
requests (prompt plus served tokens), causal attention with no cache, and
the plain ``[d_model, vocab]`` head where the program serves a coded one.

It works layer by layer: each layer's weights are drawn and applied in one
jitted call and then dropped, so the reference fits on the chip once the
program's state is freed.  All requests of a check are padded to one
length (causal attention leaves the padding unread), so one set of
programs serves every seed.

What it reads is the gap of each served token: the reference's best logit
at that position minus the served token's logit.  0 where the program
chose the reference's argmax; a small positive number where a rounding
tie went the other way; several units where a token is wrong.

The control (``control=True``) is the reference one precision step below
the configuration: weights and matmul inputs of the bfloat16 body in
float8 (e4m3, scaled per output channel and per token), and the float32
head at ``Precision.HIGH`` (three passes) instead of ``HIGHEST``.  Its
reading is the gap of the token that the control puts first.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["Dims", "seed_key", "logit_gaps", "last_logits"]

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
HEAD_ROWS = 256  # rows of the head's logits computed at once


def seed_key(seed: int):
    """A JAX key from any whole-number seed (more than 32 bits too)."""
    s = seed % 2**64
    key = jax.random.key(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    param_dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        if cfg.get("family") != "dense" or cfg.get("mlp", "swiglu") != "swiglu":
            raise ValueError("the reference covers the dense SwiGLU family")
        if cfg.get("tie_embeddings") or cfg.get("pad_heads"):
            raise ValueError("tied embeddings / padded heads are not covered")
        return cls(cfg["n_layers"], cfg["d_model"], cfg["n_heads"],
                   cfg["n_kv_heads"],
                   cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"],
                   cfg["d_ff"], cfg["vocab"], float(cfg["rope_theta"]),
                   float(cfg["norm_eps"]), cfg["param_dtype"])


# ---- weights from the seed ------------------------------------------------
def _dense(key, shape, pdt, fan_in):
    """Truncated normal in [-2, 2] over sqrt(fan_in), rounded to pdt."""
    std = 1.0 / math.sqrt(max(fan_in, 1))
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    return w.astype(pdt).astype(jnp.float32)


def _top_keys(key):
    k_embed, k_blocks, k_head, _ = jax.random.split(key, 4)
    return k_embed, k_blocks, k_head


def _layer_weights(key, dm: Dims) -> dict:
    d, h, kvh, hd, ff = dm.d_model, dm.n_heads, dm.n_kv_heads, dm.head_dim, dm.d_ff
    pdt = jnp.dtype(dm.param_dtype)
    ks = jax.random.split(key, 8)      # one layer's keys: attention, mlp, ...
    kq, kk, kv, ko = jax.random.split(ks[0], 4)
    kg, ku, kd = jax.random.split(ks[1], 3)
    return {
        "wq": _dense(kq, (d, h, hd), pdt, d),
        "wk": _dense(kk, (d, kvh, hd), pdt, d),
        "wv": _dense(kv, (d, kvh, hd), pdt, d),
        "wo": _dense(ko, (h, hd, d), pdt, h * hd),
        "wg": _dense(kg, (d, ff), pdt, d),
        "wu": _dense(ku, (d, ff), pdt, d),
        "wd": _dense(kd, (ff, d), pdt, ff),
    }


# ---- the float8 control's rounding ---------------------------------------
def _q8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _w8(w, n_in: int):
    """Weights: one scale per output channel (over the input axes)."""
    return _q8(w, tuple(range(n_in)))


def _a8(x):
    """Activations: one scale per token (over the feature axis)."""
    return _q8(x, (-1,))


# ---- forward ---------------------------------------------------------------
def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [B, S, H, hd] at positions 0..S-1, rotating pairs of halves."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("dm",))
def _embed(key, tokens, dm: Dims):
    k_embed, _, _ = _top_keys(key)
    table = jax.random.truncated_normal(
        k_embed, -2.0, 2.0, (dm.vocab, dm.d_model), jnp.float32)
    table = table.astype(jnp.dtype(dm.param_dtype)).astype(jnp.float32)
    return table[tokens]


@partial(jax.jit, static_argnames=("dm", "control"))
def _layer(x, key, index, dm: Dims, control: bool):
    """One layer: draw its weights from the seed and apply them."""
    _, k_blocks, _ = _top_keys(key)
    w = _layer_weights(jax.random.split(k_blocks, dm.n_layers)[index], dm)
    a = _a8 if control else (lambda t: t)
    if control:
        w = {k: _w8(v, 2 if k == "wo" else 1) for k, v in w.items()}
    ein = partial(jnp.einsum, precision=HIGHEST)
    b, s, _ = x.shape
    g = dm.n_heads // dm.n_kv_heads
    h = a(_rms(x, dm.norm_eps))
    q = _rope(ein("bsd,dhk->bshk", h, w["wq"]), dm.rope_theta)
    k = _rope(ein("bsd,dhk->bshk", h, w["wk"]), dm.rope_theta)
    v = ein("bsd,dhk->bshk", h, w["wv"])
    q = q.reshape(b, s, dm.n_kv_heads, g, dm.head_dim)
    logits = ein("bqgmd,bkgd->bgmqk", a(q), a(k)) / math.sqrt(dm.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = jnp.where(causal, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = ein("bgmqk,bkgd->bqgmd", a(p), a(v)).reshape(b, s, dm.n_heads, dm.head_dim)
    x = x + ein("bshk,hkd->bsd", a(o), w["wo"])
    h2 = a(_rms(x, dm.norm_eps))
    up = jax.nn.silu(ein("bsd,df->bsf", h2, w["wg"])) * ein("bsd,df->bsf", h2, w["wu"])
    return x + ein("bsf,fd->bsd", a(up), w["wd"])


@partial(jax.jit, static_argnames=("dm", "with_control"))
def _head_gaps(key, hid, hid_c, target, dm: Dims, with_control: bool):
    """Per position: the reference's best logit minus the target's, and
    (with the control) minus that of the control's first token."""
    _, _, k_head = _top_keys(key)
    head = _dense(k_head, (dm.d_model, dm.vocab), jnp.dtype(dm.param_dtype),
                  dm.d_model)
    n = hid.shape[0]
    pad = -n % HEAD_ROWS

    def rows(t):
        return jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)).reshape(
            (-1, HEAD_ROWS) + t.shape[1:])

    def block(args):
        h, hc, tgt = args
        lr = jnp.matmul(_rms(h, dm.norm_eps), head, precision=HIGHEST)
        best = lr.max(axis=-1)
        gap = best - jnp.take_along_axis(lr, jnp.maximum(tgt, 0)[:, None], -1)[:, 0]
        if not with_control:
            return gap, jnp.zeros_like(gap)
        lc = jnp.matmul(_rms(hc, dm.norm_eps), head, precision=HIGH)
        top = jnp.argmax(lc, axis=-1)
        return gap, best - jnp.take_along_axis(lr, top[:, None], -1)[:, 0]

    hc = hid_c if with_control else hid
    gap, gap_c = jax.lax.map(block, (rows(hid), rows(hc), rows(target)))
    return gap.reshape(-1)[:n], gap_c.reshape(-1)[:n]


def logit_gaps(cfg: dict, seed: int, prompts: list[np.ndarray],
               served: list[np.ndarray], s_pad: int,
               control: bool = False) -> tuple[list[np.ndarray], list[np.ndarray] | None]:
    """For each request, the gap of every served token (and, with
    ``control``, the gap of the control's first token at each of those
    positions).  ``s_pad`` is the one padded length of every check."""
    dm = Dims.of(cfg)
    key = seed_key(seed)
    r = len(prompts)
    tokens = np.zeros((r, s_pad), np.int32)
    target = np.full((r, s_pad), -1, np.int32)
    spans = []
    for i, (p, t) in enumerate(zip(prompts, served)):
        seq = np.concatenate([np.asarray(p), np.asarray(t)]).astype(np.int32)
        if len(seq) - 1 > s_pad:
            raise ValueError(f"request of {len(seq)} tokens exceeds {s_pad}")
        tokens[i, : len(seq) - 1] = seq[:-1]
        lo, hi = len(p) - 1, len(seq) - 1  # positions predicting served tokens
        target[i, lo:hi] = seq[lo + 1:]
        spans.append((lo, hi))
    tok = jnp.asarray(tokens)
    x = _embed(key, tok, dm)
    xc = x
    for layer in range(dm.n_layers):
        x = _layer(x, key, layer, dm, False)
        if control:
            xc = _layer(xc, key, layer, dm, True)
    d = dm.d_model
    gap, gap_c = _head_gaps(key, x.reshape(-1, d),
                            xc.reshape(-1, d),
                            jnp.asarray(target.reshape(-1)), dm, control)
    gap = np.asarray(gap).reshape(r, s_pad)
    gap_c = np.asarray(gap_c).reshape(r, s_pad)
    out = [gap[i, lo:hi] for i, (lo, hi) in enumerate(spans)]
    out_c = [gap_c[i, lo:hi] for i, (lo, hi) in enumerate(spans)] if control else None
    return out, out_c


def last_logits(cfg: dict, seed: int, seqs: list[np.ndarray], s_pad: int) -> np.ndarray:
    """[R, vocab] reference logits after each whole sequence (for tests at
    small sizes: the whole vocabulary is returned)."""
    dm = Dims.of(cfg)
    key = seed_key(seed)
    tokens = np.zeros((len(seqs), s_pad), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    x = _embed(key, jnp.asarray(tokens), dm)
    for layer in range(dm.n_layers):
        x = _layer(x, key, layer, dm, False)
    last = x[jnp.arange(len(seqs)), jnp.asarray([len(s) - 1 for s in seqs])]
    head = _dense(_top_keys(key)[2], (dm.d_model, dm.vocab),
                  jnp.dtype(dm.param_dtype), dm.d_model)
    return np.asarray(jnp.matmul(_rms(last, dm.norm_eps), head, precision=HIGHEST))
