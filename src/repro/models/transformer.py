"""Decoder-only LM composition: dense / MoE / SSM / hybrid / VLM.

Layer stacking is organized in *groups* so heterogeneous cadences scan
cleanly with bounded HLO:

  dense, dbrx-moe, mamba2 : period R=1 (homogeneous stack)
  llama4 (moe_every=2)    : R=2 groups [dense-FFN layer, MoE layer]
  vlm (cross_attn_every=5): R=5 groups [4 plain layers, 1 layer w/ gated
                            image cross-attention]
  zamba2 (hybrid)         : unrolled Python loop (38 small Mamba blocks +
                            one *shared* attention block applied every 6;
                            weight sharing makes scan stacking pointless)

Group params are stacked on a leading group axis and consumed by
``lax.scan`` with optional per-group ``jax.checkpoint`` (remat).  KV /
recurrent caches mirror the same stacking, so decode scans (params, cache)
jointly; the scan reads the KV cache and emits each layer's new rows, and
one write per cache leaf stores them after it.  The CE loss is computed in
sequence chunks so [B, S, V] fp32 logits are never resident.
"""
from __future__ import annotations

import math
from functools import partial

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
from repro.models.layers import (
    Params,
    dense_init,
    embed_init,
    init_mlp,
    mlp_apply,
    rmsnorm,
)
from repro.models.moe import init_moe, moe_apply
from repro.models.ssm import init_mamba_state, init_mamba_block, mamba_block_apply
from repro.sharding.ctx import shard_hint

__all__ = [
    "init_lm",
    "lm_forward",
    "lm_loss",
    "lm_init_cache",
    "lm_prefill",
    "lm_decode_step",
    "chunked_ce",
    "group_period",
]


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _adt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def group_period(cfg: ModelConfig) -> int:
    if cfg.family == "moe":
        return max(cfg.moe_every, 1)
    if cfg.family == "vlm":
        return max(cfg.cross_attn_every, 1)
    return 1


# ==========================================================================
# init
# ==========================================================================
def _init_group(key, cfg: ModelConfig) -> Params:
    """Params for ONE group (un-stacked)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    pdt = _dt(cfg)
    r = group_period(cfg)
    ks = iter(jax.random.split(key, 4 * r + 4))
    g: Params = {}
    if cfg.family in ("ssm", "hybrid"):
        g["ln1"] = jnp.ones((d,), jnp.float32)
        g["mamba"] = init_mamba_block(next(ks), cfg, pdt)
        return g
    h_eff = cfg.n_heads + cfg.pad_heads  # padded heads divide TP (§Perf H3)
    for j in range(r):
        g[f"ln1_{j}"] = jnp.ones((d,), jnp.float32)
        g[f"attn_{j}"] = init_attn(next(ks), d, h_eff, cfg.n_kv_heads, hd, pdt)
        g[f"ln2_{j}"] = jnp.ones((d,), jnp.float32)
        is_moe = cfg.family == "moe" and j == r - 1
        if is_moe:
            g[f"moe_{j}"] = init_moe(
                next(ks), d, cfg.d_ff, cfg.n_experts, cfg.mlp, cfg.shared_expert, pdt
            )
        else:
            g[f"mlp_{j}"] = init_mlp(next(ks), d, cfg.d_ff, cfg.mlp, pdt)
        if cfg.family == "vlm" and j == r - 1:
            g[f"lnx_{j}"] = jnp.ones((d,), jnp.float32)
            g[f"xattn_{j}"] = init_cross_attn(
                next(ks), d, cfg.n_heads, cfg.n_kv_heads, hd, pdt, gated=True
            )
    return g


def init_lm(key, cfg: ModelConfig) -> Params:
    """Full parameter pytree.  Group params stacked on a leading axis."""
    r = group_period(cfg)
    if cfg.n_layers % r != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by period {r}")
    n_groups = cfg.n_layers // r
    k_embed, k_blocks, k_head, k_shared = jax.random.split(key, 4)
    pdt = _dt(cfg)
    params: Params = {
        "embed": embed_init(k_embed, (cfg.vocab, cfg.d_model), pdt),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab), pdt)
    if cfg.family == "hybrid":  # unrolled stack + one shared attn block
        keys = jax.random.split(k_blocks, cfg.n_layers)
        params["blocks"] = [_init_group(keys[i], cfg) for i in range(cfg.n_layers)]
        sk = jax.random.split(k_shared, 2)
        params["shared_attn"] = {
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": init_attn(
                sk[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, pdt
            ),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "mlp": init_mlp(sk[1], cfg.d_model, cfg.d_ff, cfg.mlp, pdt),
        }
    else:
        keys = jax.random.split(k_blocks, n_groups)
        params["blocks"] = jax.vmap(lambda k: _init_group(k, cfg))(keys)
    if cfg.coded:
        from repro.core.coded_ops import encode_blocks

        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        n_blocks = _coded_blocks(cfg)
        # float32 whatever the param dtype: parity blocks are combinations
        # of head rows that a narrower dtype would round, and erasure decode
        # amplifies that rounding by the surviving blocks' condition number
        params["lm_head_coded"] = encode_blocks(
            head.T.astype(jnp.float32), n_blocks - cfg.coded_parity, cfg.coded_parity
        )
    return params


# ==========================================================================
# forward (train / prefill)
# ==========================================================================
def _apply_group_full(
    gp: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    img: jnp.ndarray | None,
    collect_kv: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, Params]:
    """One group, full-sequence mode.  Returns (x, aux_loss, kv_dict)."""
    r = group_period(cfg)
    aux = jnp.zeros((), jnp.float32)
    kv: Params = {}
    if cfg.family in ("ssm", "hybrid"):
        h, _ = mamba_block_apply(gp["mamba"], cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps))
        return x + h, aux, kv
    for j in range(r):
        h = rmsnorm(x, gp[f"ln1_{j}"], cfg.norm_eps)
        if collect_kv:
            dt = h.dtype
            k = jnp.einsum("bsd,dhk->bshk", h, gp[f"attn_{j}"]["w_k"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", h, gp[f"attn_{j}"]["w_v"].astype(dt))
            from repro.models.layers import apply_rope

            kv[f"attn_{j}"] = {"k": apply_rope(k, positions, cfg.rope_theta), "v": v}
        x = x + attention_full(gp[f"attn_{j}"], h, positions, cfg.rope_theta,
                               n_real=cfg.n_heads if cfg.pad_heads else None)
        if cfg.family == "vlm" and j == r - 1 and img is not None:
            hx = rmsnorm(x, gp[f"lnx_{j}"], cfg.norm_eps)
            x = x + cross_attention(gp[f"xattn_{j}"], hx, img)
        h2 = rmsnorm(x, gp[f"ln2_{j}"], cfg.norm_eps)
        if f"moe_{j}" in gp:
            y, a = moe_apply(
                gp[f"moe_{j}"],
                h2,
                top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                kind=cfg.mlp,
                dispatch_groups=cfg.moe_dispatch_groups,
            )
            aux = aux + a
        else:
            y = mlp_apply(gp[f"mlp_{j}"], h2, cfg.mlp)
        x = x + y
    return x, aux, kv


def _shared_attn_apply(sp: Params, cfg: ModelConfig, x, positions):
    h = rmsnorm(x, sp["ln1"], cfg.norm_eps)
    x = x + attention_full(sp["attn"], h, positions, cfg.rope_theta)
    h2 = rmsnorm(x, sp["ln2"], cfg.norm_eps)
    return x + mlp_apply(sp["mlp"], h2, cfg.mlp)


def lm_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,               # [B, S]
    img: jnp.ndarray | None = None,    # [B, n_img, D] (vlm stub frontend)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (final hidden [B,S,D] in activation dtype, moe aux loss)."""
    adt = _adt(cfg)
    x = params["embed"][tokens].astype(adt)
    x = shard_hint(x, "act_bsd")
    positions = jnp.arange(tokens.shape[1])[None, :]
    img = img.astype(adt) if img is not None else None

    if cfg.family == "hybrid":
        aux = jnp.zeros((), jnp.float32)
        for i, gp in enumerate(params["blocks"]):
            body = partial(_hybrid_layer, cfg=cfg, use_attn=(i + 1) % cfg.attn_every == 0)
            if cfg.remat:
                body = jax.checkpoint(body)
            x = body(gp, params["shared_attn"], x, positions)
        return rmsnorm(x, params["final_norm"], cfg.norm_eps), aux

    def body(carry, gp):
        x, aux = carry
        x = shard_hint(x, "act_bsd")
        x, a, _ = _apply_group_full(gp, cfg, x, positions, img, collect_kv=False)
        return (x, aux + a), None

    scan_body = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def _hybrid_layer(gp, sp, x, positions, *, cfg: ModelConfig, use_attn: bool):
    h, _ = mamba_block_apply(gp["mamba"], cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps))
    x = x + h
    if use_attn:
        x = _shared_attn_apply(sp, cfg, x, positions)
    return x


# ==========================================================================
# loss (chunked cross-entropy — never materializes [B,S,V] fp32)
# ==========================================================================
def chunked_ce(
    hidden: jnp.ndarray,    # [B, S, D]
    head: jnp.ndarray,      # [D, V]
    labels: jnp.ndarray,    # [B, S] int32; -1 = padding (ignored)
    chunk: int,
    onehot_pick: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mean token CE + token count, scanned over sequence chunks.

    ``onehot_pick``: gather the label logit as a one-hot contraction —
    with vocab-sharded logits a take_along_axis gather forces GSPMD to
    all-gather the full [B,c,V] logits, while the one-hot dot contracts
    over the sharded vocab axis locally + one tiny all-reduce (§Perf H1).
    """
    b, s, d = hidden.shape
    c = min(chunk, s)
    if s % c != 0:
        c = math.gcd(s, c) or s
    nc = s // c
    hc = hidden.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, c).transpose(1, 0, 2)
    v = head.shape[1]

    def step(carry, inp):
        tot, cnt = carry
        h, lab = inp
        logits = (h.astype(jnp.float32) @ head.astype(jnp.float32))  # [B,c,V]
        logits = shard_hint(logits, "logits_bsv")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        valid = lab >= 0
        if onehot_pick:
            hot = jax.nn.one_hot(jnp.clip(lab, 0), v, dtype=jnp.float32)
            pick = jnp.einsum("bcv,bcv->bc", logits, hot)
        else:
            pick = jnp.take_along_axis(
                logits, jnp.clip(lab, 0)[..., None], axis=-1)[..., 0]
        nll = (lse - pick) * valid
        return (tot + nll.sum(), cnt + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, lc)
    )
    return tot / jnp.maximum(cnt, 1.0), cnt


def lm_loss(
    params: Params,
    cfg: ModelConfig,
    batch: dict[str, jnp.ndarray],
    aux_weight: float = 0.01,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """batch: tokens [B,S], labels [B,S] (+ img_embed for vlm)."""
    hidden, aux = lm_forward(params, cfg, batch["tokens"], batch.get("img_embed"))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    ce, cnt = chunked_ce(hidden, head, batch["labels"], cfg.logit_chunk,
                         onehot_pick=cfg.onehot_ce)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux, "tokens": cnt}


# ==========================================================================
# caches
# ==========================================================================
def lm_init_cache(cfg: ModelConfig, batch: int, s_max: int) -> Params:
    """Decode cache pytree (stacking mirrors params['blocks'])."""
    hd = cfg.resolved_head_dim
    r = group_period(cfg)
    kv_shape = (batch, s_max, cfg.n_kv_heads, hd)

    def kv():
        return {"k": jnp.zeros(kv_shape, jnp.bfloat16), "v": jnp.zeros(kv_shape, jnp.bfloat16)}

    cache: Params = {"pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "hybrid":
        cache["blocks"] = [
            {"mamba": init_mamba_state(cfg, batch)} for _ in range(cfg.n_layers)
        ]
        n_apps = cfg.n_layers // cfg.attn_every
        cache["shared_attn"] = {
            "k": jnp.zeros((n_apps,) + kv_shape, jnp.bfloat16),
            "v": jnp.zeros((n_apps,) + kv_shape, jnp.bfloat16),
        }
        return cache
    if cfg.family == "ssm":
        n_groups = cfg.n_layers
        st = init_mamba_state(cfg, batch)
        cache["blocks"] = {
            "mamba": jax.tree.map(lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), st)
        }
        return cache
    n_groups = cfg.n_layers // r
    g: Params = {}
    for j in range(r):
        g[f"attn_{j}"] = kv()
        if cfg.family == "vlm" and j == r - 1:
            g[f"xattn_{j}"] = {
                "ck": jnp.zeros((batch, cfg.img_tokens, cfg.n_kv_heads, hd), jnp.bfloat16),
                "cv": jnp.zeros((batch, cfg.img_tokens, cfg.n_kv_heads, hd), jnp.bfloat16),
            }
    cache["blocks"] = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), g
    )
    return cache


# ==========================================================================
# prefill
# ==========================================================================
def lm_prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,               # [B, S]
    img: jnp.ndarray | None = None,
    s_max: int | None = None,          # cache capacity (>= S; default S)
    head_mask: jnp.ndarray | None = None,  # coded-head erasure mask [16]
) -> tuple[jnp.ndarray, Params]:
    """Full forward that also emits the KV/recurrent cache and the logits of
    the last position — the serving prefill step.  ``s_max`` reserves cache
    headroom for subsequent decode steps."""
    adt = _adt(cfg)
    b, s = tokens.shape
    s_max = s_max or s
    x = params["embed"][tokens].astype(adt)
    x = shard_hint(x, "act_bsd")
    positions = jnp.arange(s)[None, :]
    img = img.astype(adt) if img is not None else None
    cache: Params = {"pos": jnp.full((b,), s, jnp.int32)}

    if cfg.family == "hybrid":
        blocks_cache = []
        shared_k, shared_v = [], []
        napp = 0
        for i, gp in enumerate(params["blocks"]):
            h, st = mamba_block_apply(gp["mamba"], cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps))
            st["conv"] = _conv_tail(cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps), gp["mamba"])
            x = x + h
            blocks_cache.append({"mamba": st})
            if (i + 1) % cfg.attn_every == 0:
                sp = params["shared_attn"]
                hh = rmsnorm(x, sp["ln1"], cfg.norm_eps)
                from repro.models.layers import apply_rope

                k = jnp.einsum("bsd,dhk->bshk", hh, sp["attn"]["w_k"].astype(adt))
                v = jnp.einsum("bsd,dhk->bshk", hh, sp["attn"]["w_v"].astype(adt))
                shared_k.append(apply_rope(k, positions, cfg.rope_theta))
                shared_v.append(v)
                x = _shared_attn_apply(sp, cfg, x, positions)
                napp += 1
        cache["blocks"] = blocks_cache
        cache["shared_attn"] = _pad_cache_seq(
            {
                "k": jnp.stack(shared_k).astype(jnp.bfloat16),
                "v": jnp.stack(shared_v).astype(jnp.bfloat16),
            },
            s,
            s_max,
        )
        hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _last_logits(params, hidden, cfg, head_mask), cache

    if cfg.family == "ssm":

        def body(x, gp):
            h, st = mamba_block_apply(gp["mamba"], cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps))
            st["conv"] = _conv_tail(cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps), gp["mamba"])
            return x + h, {"mamba": st}

        x, states = jax.lax.scan(body, x, params["blocks"])
        cache["blocks"] = states
        hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _last_logits(params, hidden, cfg, head_mask), cache

    def body(carry, gp):
        x = carry
        x, _, kvd = _apply_group_full(gp, cfg, x, positions, img, collect_kv=True)
        if cfg.family == "vlm":
            r = group_period(cfg)
            ck, cv = precompute_cross_kv(gp[f"xattn_{r-1}"], img)
            kvd[f"xattn_{r-1}"] = {"ck": ck.astype(jnp.bfloat16), "cv": cv.astype(jnp.bfloat16)}
        kvd = jax.tree.map(lambda t: t.astype(jnp.bfloat16), kvd)
        return x, kvd

    x, kvs = jax.lax.scan(body, x, params["blocks"])
    # normalize cache key layout: {"attn_j": {"k","v"}} stacked on groups
    cache["blocks"] = _pad_cache_seq(kvs, s, s_max)
    hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _last_logits(params, hidden, cfg, head_mask), cache


def _pad_cache_seq(tree: Params, s: int, s_max: int) -> Params:
    """Pad self-attention cache K/V (leaf names 'k'/'v') from S to s_max on
    the sequence axis (-3), leaving cross-attention ck/cv untouched."""
    if s_max <= s:
        return tree

    def pad(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v"):
            cfgpad = [(0, 0)] * x.ndim
            cfgpad[-3] = (0, s_max - s)
            return jnp.pad(x, cfgpad)
        return x

    return jax.tree_util.tree_map_with_path(pad, tree)


def _conv_tail(cfg: ModelConfig, u: jnp.ndarray, mp: Params) -> jnp.ndarray:
    """Last (W-1) conv inputs after prefill — the decode conv cache."""
    zxbcdt = u @ mp["in_proj"].astype(u.dtype)
    din, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    xbc = zxbcdt[..., din : 2 * din + 2 * g * n]
    w = cfg.conv_width
    return xbc[:, -(w - 1) :].astype(jnp.bfloat16)


def _last_logits(
    params: Params,
    hidden: jnp.ndarray,
    cfg: ModelConfig | None = None,
    head_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Last-position logits.  With ``cfg.coded`` the head matvec runs through
    the BPCC CodedLinear blocks: any ``coded_parity`` erased model-shards
    (``head_mask`` zeros) still yield exact logits — the paper's
    straggler-tolerant matrix-vector product as the serving hot path.

    Inside a ``sharding.ctx.coded_head_mesh`` context the same matvec runs
    shard_map'd over a real mesh — one code block per device, erasure =
    dropping a device's output — via ``kernels.ops.coded_head_matvec``
    (bit-identical to the single-program path on identical masks).  A
    ``sharding.ctx.head_kernel_mode`` context picks the head's kernel
    implementation — ``'auto'`` for the autotuned per-shape dispatch
    (DESIGN.md §11), resolved here at trace time from the static shapes."""
    last = hidden[:, -1]
    if cfg is not None and cfg.coded and "lm_head_coded" in params:
        from repro.kernels.ops import coded_head_matvec
        from repro.sharding.ctx import (
            current_coded_head_mesh,
            current_head_kernel_mode,
        )

        n_blocks = _coded_blocks(cfg)
        mask = head_mask if head_mask is not None else jnp.ones((n_blocks,), jnp.float32)
        cm = current_coded_head_mesh()
        mesh, axis = cm if cm is not None else (None, "model")
        with jax.named_scope("coded_head"):  # named in the compiled op_names
            y = coded_head_matvec(
                params["lm_head_coded"].astype(jnp.float32),
                last.astype(jnp.float32).T,
                mask,
                n_blocks - cfg.coded_parity,
                cfg.coded_parity,
                mesh=mesh,
                axis=axis,
                kernel_mode=current_head_kernel_mode(),
            )
            return y[: cfg.vocab].T
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return last.astype(jnp.float32) @ head.astype(jnp.float32)


def _coded_blocks(cfg: ModelConfig) -> int:
    """Total coded blocks for the serving head = TP width (one per shard)."""
    from repro.models.config import coded_blocks

    return coded_blocks(cfg)


# ==========================================================================
# decode step
# ==========================================================================
def lm_decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: Params,
    tokens: jnp.ndarray,  # [B] — one new token per sequence
    head_mask: jnp.ndarray | None = None,  # coded-head erasure mask [16]
) -> tuple[jnp.ndarray, Params]:
    """One decoding step: returns (logits [B, vocab] fp32, updated cache)."""
    adt = _adt(cfg)
    pos = cache["pos"]
    x = params["embed"][tokens][:, None].astype(adt)  # [B,1,D]
    x = shard_hint(x, "act_bsd")

    if cfg.family == "hybrid":
        new_blocks = []
        rows_k, rows_v = [], []
        for i, gp in enumerate(params["blocks"]):
            st = cache["blocks"][i]["mamba"]
            h, st2 = mamba_block_apply(
                gp["mamba"], cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps), state=st
            )
            x = x + h
            new_blocks.append({"mamba": st2})
            if (i + 1) % cfg.attn_every == 0:
                x, nk, nv = _shared_attn_decode(params, cfg, cache, x, pos, len(rows_k))
                rows_k.append(nk)
                rows_v.append(nv)
        shared = cache["shared_attn"]
        with jax.named_scope("kv_write"):  # named in the compiled op_names
            new_cache = {
                "pos": pos + 1,
                "blocks": new_blocks,
                "shared_attn": {
                    "k": write_kv_rows(shared["k"], jnp.stack(rows_k), pos),
                    "v": write_kv_rows(shared["v"], jnp.stack(rows_v), pos),
                },
            }
        hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _last_logits(params, hidden, cfg, head_mask), new_cache

    if cfg.family == "ssm":

        def body(x, inp):
            gp, st = inp
            h, st2 = mamba_block_apply(
                gp["mamba"], cfg, rmsnorm(x, gp["ln1"], cfg.norm_eps), state=st["mamba"]
            )
            return x + h, {"mamba": st2}

        x, states = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache = {"pos": pos + 1, "blocks": states}
        hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _last_logits(params, hidden, cfg, head_mask), new_cache

    r = group_period(cfg)

    # the scan only reads the cache; its outputs are each layer's new rows,
    # which one write per leaf stores after it (in place when donated)
    def body(x, inp):
        gp, cg = inp
        rows = {}
        for j in range(r):
            h = rmsnorm(x, gp[f"ln1_{j}"], cfg.norm_eps)
            out, nk, nv = attention_decode(
                gp[f"attn_{j}"], h, cg[f"attn_{j}"]["k"], cg[f"attn_{j}"]["v"], pos,
                cfg.rope_theta,
                n_real=cfg.n_heads if cfg.pad_heads else None,
            )
            rows[f"attn_{j}"] = {"k": nk, "v": nv}
            x = x + out
            if cfg.family == "vlm" and j == r - 1:
                hx = rmsnorm(x, gp[f"lnx_{j}"], cfg.norm_eps)
                x = x + cross_attention_cached(
                    gp[f"xattn_{j}"], hx, cg[f"xattn_{j}"]["ck"], cg[f"xattn_{j}"]["cv"]
                )
            h2 = rmsnorm(x, gp[f"ln2_{j}"], cfg.norm_eps)
            if f"moe_{j}" in gp:
                y, _ = moe_apply(
                    gp[f"moe_{j}"], h2,
                    top_k=cfg.top_k, capacity_factor=cfg.capacity_factor, kind=cfg.mlp,
                    dispatch_groups=cfg.moe_dispatch_groups,
                )
            else:
                y = mlp_apply(gp[f"mlp_{j}"], h2, cfg.mlp)
            x = x + y
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    blocks = dict(cache["blocks"])
    with jax.named_scope("kv_write"):  # named in the compiled op_names
        for name, kv in rows.items():
            blocks[name] = {
                t: write_kv_rows(cache["blocks"][name][t], kv[t], pos, cfg.aligned_decode)
                for t in ("k", "v")
            }
        new_cache = {"pos": pos + 1, "blocks": blocks}
    hidden = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _last_logits(params, hidden, cfg, head_mask), new_cache


def _shared_attn_decode(params, cfg, cache, x, pos, app: int):
    """Apply the zamba2 shared attention block at decode against its own
    cache slice (weights shared; caches per application).  Returns
    ``(x, k, v)``: the application's new key/value rows."""
    sp = params["shared_attn"]
    h = rmsnorm(x, sp["ln1"], cfg.norm_eps)
    ck = cache["shared_attn"]["k"][app]
    cv = cache["shared_attn"]["v"][app]
    out, nk, nv = attention_decode(sp["attn"], h, ck, cv, pos, cfg.rope_theta)
    x = x + out
    h2 = rmsnorm(x, sp["ln2"], cfg.norm_eps)
    return x + mlp_apply(sp["mlp"], h2, cfg.mlp), nk, nv
