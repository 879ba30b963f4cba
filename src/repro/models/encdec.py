"""Encoder-decoder LM (seamless-m4t backbone).

The modality frontend is a STUB per the assignment: ``input_specs()``
supplies precomputed frame embeddings [B, S_src, D] (what the w2v-BERT
speech encoder would emit); this module implements the transformer backbone
— bidirectional encoder over frames, causal decoder with cross-attention —
plus the serving path (decoder KV cache + precomputed cross K/V).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (
    attention_decode,
    attention_full,
    cross_attention,
    cross_attention_cached,
    init_attn,
    init_cross_attn,
    precompute_cross_kv,
    write_kv_rows,
)
from repro.models.config import ModelConfig
from repro.models.layers import Params, dense_init, embed_init, init_mlp, mlp_apply, rmsnorm
from repro.models.transformer import _last_logits, chunked_ce
from repro.sharding.ctx import shard_hint

__all__ = [
    "init_encdec",
    "encdec_encode",
    "encdec_forward",
    "encdec_loss",
    "encdec_init_cache",
    "encdec_prefill",
    "encdec_decode_step",
]


def _adt(cfg):
    return jnp.dtype(cfg.dtype)


def _init_enc_layer(key, cfg: ModelConfig, pdt):
    k1, k2 = jax.random.split(key)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "attn": init_attn(k1, d, cfg.n_heads, cfg.n_kv_heads, hd, pdt),
        "ln2": jnp.ones((d,), jnp.float32),
        "mlp": init_mlp(k2, d, cfg.d_ff, cfg.mlp, pdt),
    }


def _init_dec_layer(key, cfg: ModelConfig, pdt):
    k1, k2, k3 = jax.random.split(key, 3)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "attn": init_attn(k1, d, cfg.n_heads, cfg.n_kv_heads, hd, pdt),
        "lnx": jnp.ones((d,), jnp.float32),
        "xattn": init_cross_attn(k2, d, cfg.n_heads, cfg.n_kv_heads, hd, pdt),
        "ln2": jnp.ones((d,), jnp.float32),
        "mlp": init_mlp(k3, d, cfg.d_ff, cfg.mlp, pdt),
    }


def init_encdec(key, cfg: ModelConfig) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    ke, kd, kemb, kh = jax.random.split(key, 4)
    enc_keys = jax.random.split(ke, cfg.enc_layers)
    dec_keys = jax.random.split(kd, cfg.n_layers)
    return {
        "embed": embed_init(kemb, (cfg.vocab, cfg.d_model), pdt),
        "enc_blocks": jax.vmap(lambda k: _init_enc_layer(k, cfg, pdt))(enc_keys),
        "dec_blocks": jax.vmap(lambda k: _init_dec_layer(k, cfg, pdt))(dec_keys),
        "enc_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": dense_init(kh, (cfg.d_model, cfg.vocab), pdt),
    }


# --------------------------------------------------------------------------
def encdec_encode(params: Params, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames [B, S_src, D] (stub frontend output) -> encoder memory."""
    x = frames.astype(_adt(cfg))
    x = shard_hint(x, "act_bsd")
    positions = jnp.arange(x.shape[1])[None, :]

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        x = x + attention_full(lp["attn"], h, positions, cfg.rope_theta, causal=False)
        h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp_apply(lp["mlp"], h2, cfg.mlp), None

    scan_body = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(scan_body, x, params["enc_blocks"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def encdec_forward(
    params: Params, cfg: ModelConfig, frames: jnp.ndarray, tokens: jnp.ndarray
) -> jnp.ndarray:
    """Teacher-forced decode over [B, S_tgt] given source frames; returns
    final decoder hidden states [B, S_tgt, D]."""
    memory = encdec_encode(params, cfg, frames)
    x = params["embed"][tokens].astype(_adt(cfg))
    x = shard_hint(x, "act_bsd")
    positions = jnp.arange(tokens.shape[1])[None, :]

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        x = x + attention_full(lp["attn"], h, positions, cfg.rope_theta)
        hx = rmsnorm(x, lp["lnx"], cfg.norm_eps)
        x = x + cross_attention(lp["xattn"], hx, memory)
        h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp_apply(lp["mlp"], h2, cfg.mlp), None

    scan_body = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(scan_body, x, params["dec_blocks"])
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)


def encdec_loss(params: Params, cfg: ModelConfig, batch: dict) -> tuple[jnp.ndarray, dict]:
    hidden = encdec_forward(params, cfg, batch["frames"], batch["tokens"])
    ce, cnt = chunked_ce(hidden, params["lm_head"], batch["labels"], cfg.logit_chunk,
                         onehot_pick=cfg.onehot_ce)
    return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32), "tokens": cnt}


# --------------------------------------------------------------------------
def encdec_init_cache(cfg: ModelConfig, batch: int, s_max: int, s_src: int) -> Params:
    hd = cfg.resolved_head_dim
    kv_shape = (cfg.n_layers, batch, s_max, cfg.n_kv_heads, hd)
    cross_shape = (cfg.n_layers, batch, s_src, cfg.n_kv_heads, hd)
    return {
        "pos": jnp.zeros((batch,), jnp.int32),
        "k": jnp.zeros(kv_shape, jnp.bfloat16),
        "v": jnp.zeros(kv_shape, jnp.bfloat16),
        "ck": jnp.zeros(cross_shape, jnp.bfloat16),
        "cv": jnp.zeros(cross_shape, jnp.bfloat16),
    }


def encdec_prefill(
    params: Params,
    cfg: ModelConfig,
    frames: jnp.ndarray,
    tokens: jnp.ndarray,
    s_max: int | None = None,
) -> tuple[jnp.ndarray, Params]:
    """Encode source + teacher-forced pass over the target prefix, emitting
    decoder self-attn caches, precomputed cross K/V, and last logits."""
    memory = encdec_encode(params, cfg, frames)
    adt = _adt(cfg)
    b, s = tokens.shape
    x = params["embed"][tokens].astype(adt)
    positions = jnp.arange(s)[None, :]
    from repro.models.layers import apply_rope

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["w_k"].astype(adt))
        v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["w_v"].astype(adt))
        kv = {
            "k": apply_rope(k, positions, cfg.rope_theta).astype(jnp.bfloat16),
            "v": v.astype(jnp.bfloat16),
        }
        x = x + attention_full(lp["attn"], h, positions, cfg.rope_theta)
        hx = rmsnorm(x, lp["lnx"], cfg.norm_eps)
        x = x + cross_attention(lp["xattn"], hx, memory)
        ck, cv = precompute_cross_kv(lp["xattn"], memory)
        kv["ck"], kv["cv"] = ck.astype(jnp.bfloat16), cv.astype(jnp.bfloat16)
        h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp_apply(lp["mlp"], h2, cfg.mlp), kv

    x, kvs = jax.lax.scan(body, x, params["dec_blocks"])
    from repro.models.transformer import _pad_cache_seq

    kvs = _pad_cache_seq(kvs, s, s_max or s)
    cache = {"pos": jnp.full((b,), s, jnp.int32), **kvs}
    hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _last_logits(params, hidden), cache


def encdec_decode_step(
    params: Params, cfg: ModelConfig, cache: Params, tokens: jnp.ndarray
) -> tuple[jnp.ndarray, Params]:
    """One decoder step against cached self/cross K/V."""
    adt = _adt(cfg)
    pos = cache["pos"]
    x = params["embed"][tokens][:, None].astype(adt)

    def body(x, inp):
        lp, k, v, ck, cv = inp
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        out, nk, nv = attention_decode(lp["attn"], h, k, v, pos, cfg.rope_theta)
        x = x + out
        hx = rmsnorm(x, lp["lnx"], cfg.norm_eps)
        x = x + cross_attention_cached(lp["xattn"], hx, ck, cv)
        h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp_apply(lp["mlp"], h2, cfg.mlp), {"k": nk, "v": nv}

    x, rows = jax.lax.scan(
        body, x, (params["dec_blocks"], cache["k"], cache["v"], cache["ck"], cache["cv"])
    )
    with jax.named_scope("kv_write"):  # named in the compiled op_names
        new_cache = {"pos": pos + 1, "ck": cache["ck"], "cv": cache["cv"],
                     **{t: write_kv_rows(cache[t], rows[t], pos) for t in ("k", "v")}}
    hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _last_logits(params, hidden), new_cache
