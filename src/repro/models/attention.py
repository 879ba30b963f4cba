"""Grouped-query attention: full (train/prefill), decode (KV cache), cross.

Layout convention: activations [B, S, D]; per-head tensors [B, S, H, Hd];
KV caches [B, S_max, KVH, Hd].  Decode reads the cache and returns the new
rows; ``write_kv_rows`` stores them for every layer at once.  Softmax in
fp32.  TP shards the head axis (uneven head counts are allowed — GSPMD
pads; see DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import Params, apply_rope, dense_init
from repro.sharding.ctx import kv_seq_sharded

NEG_INF = -1e30


def init_attn(key, d_model: int, n_heads: int, n_kv: int, head_dim: int, dtype) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "w_q": dense_init(kq, (d_model, n_heads, head_dim), dtype, fan_in=d_model),
        "w_k": dense_init(kk, (d_model, n_kv, head_dim), dtype, fan_in=d_model),
        "w_v": dense_init(kv, (d_model, n_kv, head_dim), dtype, fan_in=d_model),
        "w_o": dense_init(ko, (n_heads, head_dim, d_model), dtype, fan_in=n_heads * head_dim),
    }


def _sdpa(q, k, v, mask) -> jnp.ndarray:
    """q [B,Sq,H,Hd], k/v [B,Sk,KVH,Hd], mask [B,1,1,Sq,Sk] or None."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    gs = h // kvh  # query heads per kv head
    q = q.reshape(b, sq, kvh, gs, hd)
    logits = jnp.einsum("bqgmd,bkgd->bgmqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgmqk,bkgd->bqgmd", probs, v)
    return out.reshape(b, sq, h, hd)


# threshold above which the S² logits tensor must not materialize
CHUNKED_ATTN_THRESHOLD = 8192


def _sdpa_chunked(
    q, k, v, *, causal: bool, q_chunk: int = 1024, kv_chunk: int = 2048
) -> jnp.ndarray:
    """Flash-style online-softmax SDPA: never materializes [Sq, Sk] logits.

    Outer ``lax.map`` over query chunks; inner ``lax.scan`` over KV chunks
    carrying (running max, denominator, weighted accumulator).  Causal
    chunks beyond the diagonal are masked (not skipped): fixed shapes keep
    XLA happy at the cost of <=2x attention FLOPs versus a triangular
    schedule — recorded as a §Perf candidate.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    gs = h // kvh
    qc, kc = min(q_chunk, sq), min(kv_chunk, sk)
    if sq % qc or sk % kc:
        return _sdpa(q, k, v, _causal_mask5(sq, sk) if causal else None)
    nq, nk = sq // qc, sk // kc
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qr = q.reshape(b, nq, qc, kvh, gs, hd).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(b, nk, kc, kvh, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, nk, kc, kvh, hd).transpose(1, 0, 2, 3, 4)

    def per_q_chunk(args):
        qi, qblk = args  # [B,qc,KVH,gs,Hd]
        qpos = qi * qc + jnp.arange(qc)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, kblk, vblk = inp
            logits = jnp.einsum(
                "bqgmd,bkgd->bqgmk", qblk.astype(jnp.float32),
                kblk.astype(jnp.float32)) * scale  # [B,qc,KVH,gs,kc]
            if causal:
                kpos = kj * kc + jnp.arange(kc)
                msk = (kpos[None, :] <= qpos[:, None])[None, :, None, None, :]
                logits = jnp.where(msk, logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqgmk,bkgd->bqgmd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, qc, kvh, gs), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, qc, kvh, gs), jnp.float32)
        a0 = jnp.zeros((b, qc, kvh, gs, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kr, vr))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(per_q_chunk, (jnp.arange(nq), qr))  # [nq,B,qc,KVH,gs,Hd]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, hd)
    return out.astype(v.dtype)


def _causal_mask5(sq: int, sk: int) -> jnp.ndarray:
    return (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None])[None, None, None]


def _mask_pad_heads(out: jnp.ndarray, n_real: int | None) -> jnp.ndarray:
    """Zero the outputs of padding heads (cfg.pad_heads): the function and
    its gradients then equal the unpadded model exactly — pad w_q/w_o slices
    receive zero gradient and stay inert, while the head axis divides TP."""
    if n_real is None or n_real >= out.shape[2]:
        return out
    mask = (jnp.arange(out.shape[2]) < n_real).astype(out.dtype)
    return out * mask[None, None, :, None]


def attention_full(
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float,
    *,
    causal: bool = True,
    n_real: int | None = None,
) -> jnp.ndarray:
    """Full self-attention over [B, S, D] (training / prefill)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["w_v"].astype(dt))
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    s = x.shape[1]
    if s >= CHUNKED_ATTN_THRESHOLD:
        out = _sdpa_chunked(q, k, v, causal=causal)
    else:
        mask = _causal_mask5(s, s) if causal else None
        out = _sdpa(q, k, v, mask)
    out = _mask_pad_heads(out, n_real)
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(dt))


def _sdpa_decode(q, cache_k, cache_v, k, v, pos) -> jnp.ndarray:
    """One query per sequence over its cached keys ``< pos`` and its own new
    key/value, in one softmax: the same as a mask ``<= pos`` over a cache
    that already holds the new row, without writing it first.

    q [B,1,H,Hd], cache_k/v [B,S_max,KVH,Hd], k/v [B,KVH,Hd], pos [B]."""
    b, _, h, hd = q.shape
    kvh = cache_k.shape[2]
    q = q.reshape(b, 1, kvh, h // kvh, hd)
    scale = jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bqgmd,bkgd->bgmqk", q, cache_k).astype(jnp.float32) / scale
    own = jnp.einsum("bqgmd,bgd->bgmq", q, k).astype(jnp.float32)[..., None] / scale
    cached = (jnp.arange(cache_k.shape[1])[None, :] < pos[:, None])[:, None, None, None, :]
    logits = jnp.where(cached, logits, NEG_INF)
    top = jnp.maximum(logits.max(axis=-1, keepdims=True), own)
    e, e_own = jnp.exp(logits - top), jnp.exp(own - top)
    denom = e.sum(axis=-1, keepdims=True) + e_own
    probs, p_own = (e / denom).astype(v.dtype), (e_own / denom).astype(v.dtype)
    out = jnp.einsum("bgmqk,bkgd->bqgmd", probs, cache_v,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bgmqk,bgd->bqgmd", p_own, v,
                           preferred_element_type=jnp.float32)
    return out.astype(v.dtype).reshape(b, 1, h, hd)


def attention_decode(
    p: Params,
    x: jnp.ndarray,            # [B, 1, D] — one new token per sequence
    cache_k: jnp.ndarray,      # [B, S_max, KVH, Hd]
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,          # [B] int — the new token's position per sequence
    theta: float,
    n_real: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step; returns (out [B,1,D], k, v): the new token's key and
    value rows [B, KVH, Hd] in the cache dtype, for ``write_kv_rows``.  The
    cache is only read."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["w_v"].astype(dt))
    q = apply_rope(q, pos[:, None], theta)
    k = apply_rope(k, pos[:, None], theta)
    # the new rows as the cache will hold them, so this step attends to
    # exactly what later steps read back
    k, v = k[:, 0].astype(cache_k.dtype), v[:, 0].astype(cache_v.dtype)
    out = _sdpa_decode(q, cache_k.astype(dt), cache_v.astype(dt),
                       k.astype(dt), v.astype(dt), pos)
    out = _mask_pad_heads(out, n_real)
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(dt)), k, v


def write_kv_rows(
    cache: jnp.ndarray,        # [*G, B, S_max, KVH, Hd]
    rows: jnp.ndarray,         # [*G, B, KVH, Hd]
    pos: jnp.ndarray,          # [B]
    aligned: bool = False,     # every sequence at pos[0] (batch-aligned decode)
) -> jnp.ndarray:
    """Store each sequence's new key or value row at its ``pos``, for every
    stacked layer ``G`` at once.

    One ``dynamic_update_slice`` per sequence (one for the whole batch when
    ``aligned``) writes ``[*G, KVH, Hd]`` in whatever layout the compiler
    gave the cache; in a step that donates its cache they run in place, so
    the step moves the new rows and never the cache.  (A scatter of all
    rows at once is one operation, but the TPU compiler runs it only in the
    major-to-minor layout, and relays out a cache it stores sequence-minor,
    as it does phi3-mini's 96-wide heads, twice a step.)

    The exception is a cache whose sequence axis is sharded
    (``sharding.ctx.kv_seq_sharded``: the dry run's caches whose KV heads
    do not divide the model axis).  Per-sequence write positions make the
    SPMD partitioner fall into pathological resharding there, so the rows
    go in by a masked select over the whole cache, which shards
    elementwise at the cost of rewriting it.

    Callers write every leaf and advance ``pos`` inside one
    ``jax.named_scope("kv_write")``: the step's appends to its cache."""
    rows = rows.astype(cache.dtype)[..., None, :, :]  # [*G, B, 1, KVH, Hd]
    if kv_seq_sharded():
        hot = jnp.arange(cache.shape[-3])[None, :] == pos[:, None]
        return jnp.where(hot[..., None, None], rows, cache)
    lead = (0,) * (rows.ndim - 4)
    if aligned:
        return jax.lax.dynamic_update_slice(cache, rows, lead + (0, pos[0], 0, 0))
    for b in range(rows.shape[-4]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[..., b:b + 1, :, :, :], lead + (b, pos[b], 0, 0))
    return cache


# --------------------------------------------------------------------------
# Cross-attention (VLM image layers, enc-dec decoder)
# --------------------------------------------------------------------------
def init_cross_attn(
    key, d_model: int, n_heads: int, n_kv: int, head_dim: int, dtype, gated: bool = False
) -> Params:
    p = init_attn(key, d_model, n_heads, n_kv, head_dim, dtype)
    if gated:
        p["gate"] = jnp.zeros((), jnp.float32)  # tanh-gated (llama-vision style)
    return p


def cross_attention(p: Params, x: jnp.ndarray, memory: jnp.ndarray) -> jnp.ndarray:
    """x [B,Sq,D] attends over memory [B,Sk,D] (no RoPE, no causal mask)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", memory.astype(dt), p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", memory.astype(dt), p["w_v"].astype(dt))
    if max(x.shape[1], memory.shape[1]) >= CHUNKED_ATTN_THRESHOLD:
        out = _sdpa_chunked(q, k, v, causal=False)
    else:
        out = _sdpa(q, k, v, None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(dt))
    if "gate" in p:
        y = jnp.tanh(p["gate"]).astype(dt) * y
    return y


def precompute_cross_kv(p: Params, memory: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Cache the cross-attention K/V once per request (decode fast path)."""
    k = jnp.einsum("bsd,dhk->bshk", memory, p["w_k"].astype(memory.dtype))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["w_v"].astype(memory.dtype))
    return k, v


def cross_attention_cached(
    p: Params, x: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
) -> jnp.ndarray:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(dt))
    out = _sdpa(q, k.astype(dt), v.astype(dt), None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(dt))
    if "gate" in p:
        y = jnp.tanh(p["gate"]).astype(dt) * y
    return y
