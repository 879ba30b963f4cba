"""Sharding policy: logical parameter/activation axes -> mesh PartitionSpecs.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod.  The policy implements:

  * **TP** over ``model``: attention heads, d_ff, vocab, MoE experts (EP),
    Mamba d_inner heads.
  * **DP** over ``("pod", "data")``: batch dims of activations/caches.
  * **FSDP/ZeRO** over ``data``: parameters' non-TP matrix axis (and the
    optimizer state, which inherits param specs) — required to fit the
    340B/400B cells.
  * **SP**: KV-cache sequence sharding (over ``model`` when the KV-head
    count doesn't divide TP — glm4's kv=2, the kv=8 GQA archs — and over
    ``data`` when the decode batch is too small to fill DP: long_500k).

pjit REJECTS shardings whose dimension is not divisible by the assigned
axes, so every spec passes through ``fit()``: non-divisible assignments are
dropped, and named fallbacks kick in —

  * attention q/o with head-count % TP != 0 (llama4's 40H): fall back to
    *contraction sharding* of the d_model dim over (data, model).  Correct
    but compute-replicates attention across TP — measured and attacked in
    the §Perf iterations rather than silently papered over.
  * embed/lm_head with vocab % TP != 0 (mamba2, seamless): vocab stays
    unsharded; the matrix FSDPs over data.

Rules are name+rank based over pytree paths: one table covers all six
model families.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

__all__ = [
    "ShardingPolicy",
    "make_policy",
    "param_specs",
    "coded_head_sharding",
    "validate_coded_head_mesh",
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:  # pragma: no cover
            parts.append(str(k))
    return "/".join(parts)


@dataclass(frozen=True)
class ShardingPolicy:
    mesh: Mesh
    fsdp: bool = True              # shard params over 'data' too (ZeRO-3 style)
    shard_cache_seq: bool = False  # SP on KV-cache sequence dim (tiny batches)
    vocab: int = 0                 # for logits hints divisibility
    qkv_contraction: bool = False  # force contraction-sharded attn projections
    # (decode cells whose KV cache is sequence-sharded: head-sharded q +
    #  S-sharded k makes the 512-dev partitioner explode reconciling the GQA
    #  reshape — replicated q after a tiny AR sidesteps it; weights stay
    #  sharded so HBM is unaffected)

    # ------------------------------------------------------------------
    @property
    def dp_axes(self) -> tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def fsdp_axis(self) -> str | None:
        return "data" if (self.fsdp and "data" in self.mesh.axis_names) else None

    def _axis_size(self, entry) -> int:
        if entry is None:
            return 1
        axes = entry if isinstance(entry, tuple) else (entry,)
        return math.prod(self.mesh.shape[a] for a in axes)

    def fit(self, spec: tuple, shape: tuple) -> P:
        """Left-pad to rank and drop non-divisible axis assignments."""
        entries = (None,) * (len(shape) - len(spec)) + tuple(spec)
        out = []
        for dim, entry in zip(shape, entries):
            out.append(entry if entry and dim % self._axis_size(entry) == 0 else None)
        return P(*out)

    def divisible(self, dim: int, entry) -> bool:
        return dim % self._axis_size(entry) == 0

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def param_spec(self, path: str, shape: tuple) -> P:
        name = path.rsplit("/", 1)[-1]
        ndim = len(shape)
        fs = self.fsdp_axis
        both = ("data", "model") if fs else ("model",)
        if name == "embed":
            if self.divisible(shape[0], "model"):
                return self.fit(("model", fs), shape)
            return self.fit((None, fs), shape)
        if name in ("lm_head", "lm_head_coded"):
            # [D, V] (or coded blocks [nb*br, D]) — vocab over model if it fits
            if name == "lm_head" and self.divisible(shape[1], "model"):
                return self.fit((fs, "model"), shape)
            if name == "lm_head_coded" and self.divisible(shape[0], "model"):
                return self.fit(("model", fs), shape)
            return self.fit((fs, None), shape)
        if ndim <= 1 or name.startswith(
            ("ln", "gate_norm", "dt_bias", "a_log", "d_skip", "final_norm",
             "enc_norm", "gate")
        ):
            return P(*((None,) * ndim))
        is_moe = ("moe_" in path or "/moe/" in path) and "shared" not in path
        if name in ("w_gate", "w_up"):
            if is_moe:
                return self.fit(("model", fs, None), shape)   # [E, D, F]
            return self.fit((fs, "model"), shape)             # [D, F]
        if name == "w_down":
            if is_moe:
                return self.fit(("model", None, fs), shape)   # [E, F, D]
            return self.fit(("model", fs), shape)             # [F, D]
        if name == "router":
            return self.fit((fs, None), shape)                # [D, E]
        if name in ("w_q", "w_k", "w_v"):
            heads = shape[-2]
            if self.divisible(heads, "model") and not self.qkv_contraction:
                return self.fit((fs, "model", None), shape)   # [D, H, Hd]
            # fallback: contraction-shard d_model (correct; see §Perf)
            d = shape[-3]
            entry = both if self.divisible(d, both) else fs
            return self.fit((entry, None, None), shape)
        if name == "w_o":
            heads = shape[-3]
            if self.divisible(heads, "model") and not self.qkv_contraction:
                return self.fit(("model", None, fs), shape)   # [H, Hd, D]
            d = shape[-1]
            entry = both if self.divisible(d, both) else fs
            return self.fit((None, None, entry), shape)
        if name == "in_proj":
            return self.fit((fs, "model"), shape)             # [D, Zproj]
        if name == "out_proj":
            return self.fit(("model", fs), shape)             # [din, D]
        if name == "conv_w":
            return self.fit((None, "model"), shape)           # [W, C]
        return P(*((None,) * ndim))

    def param_specs(self, shapes: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, x: self.param_spec(_path_str(path), tuple(x.shape)), shapes
        )

    def param_shardings(self, shapes: Any) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), self.param_specs(shapes))

    # ------------------------------------------------------------------
    # optimizer state (moments mirror params; QTensor q/scale children)
    # ------------------------------------------------------------------
    def opt_spec(self, path: str, shape: tuple) -> P:
        parts = path.split("/")
        if parts[0] == "step":
            return P()
        if parts[0] in ("m", "v"):
            if parts[-1] in ("0", "1"):  # QTensor children: 0 = q, 1 = scale
                base = self.param_spec("/".join(parts[1:-1]), shape)
                if parts[-1] == "1":  # scale: block axis (last) replicated
                    entries = tuple(base) + (None,) * (len(shape) - len(tuple(base)))
                    return self.fit(tuple(entries[:-1]) + (None,), shape)
                return self.fit(tuple(base), shape)
            return self.param_spec("/".join(parts[1:]), shape)
        return self.param_spec(path, shape)

    def opt_specs(self, shapes: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, x: self.opt_spec(_path_str(path), tuple(x.shape)), shapes
        )

    def state_specs(self, state_shapes: Any) -> Any:
        """Specs for a full TrainState {'params': ..., 'opt': ..[, 'err': ..]}.

        The ``err`` tree (error-feedback residuals for compressed coded
        messages) mirrors params with a leading [n_workers] message axis:
        that axis stays unsharded, the rest inherits the param spec."""

        def fn(path, x):
            ps = _path_str(path)
            root, _, rest = ps.partition("/")
            if root == "params":
                return self.param_spec(rest, tuple(x.shape))
            if root == "err":
                base = tuple(self.param_spec(rest, tuple(x.shape[1:])))
                return self.fit((None,) + base, tuple(x.shape))
            return self.opt_spec(rest, tuple(x.shape))

        return jax.tree_util.tree_map_with_path(fn, state_shapes)

    # ------------------------------------------------------------------
    # inputs / batches
    # ------------------------------------------------------------------
    def batch_spec(self, path: str, shape: tuple) -> P:
        if len(shape) == 0:
            return P()
        return self.fit((self.dp_axes,) + (None,) * (len(shape) - 1), shape)

    def batch_specs(self, specs: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, x: self.batch_spec(_path_str(path), tuple(x.shape)), specs
        )

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def cache_spec(self, path: str, shape: tuple) -> P:
        name = path.rsplit("/", 1)[-1]
        ndim = len(shape)
        dp = self.dp_axes
        if name == "pos":
            return P(*((None,) * ndim))
        if name in ("k", "v", "ck", "cv"):
            # [, B, S, KVH, Hd] — heads on model when divisible; otherwise
            # flash-decode style: SEQUENCE over model (partial softmax)
            kvh = shape[-2]
            heads_fit = self.divisible(kvh, "model")
            if self.shard_cache_seq:  # tiny global batch (long_500k)
                spec: tuple = (None, "data", "model" if heads_fit else None, None)
                if not heads_fit:
                    spec = (None, ("data", "model"), None, None)
            else:
                spec = (dp, "model" if not heads_fit else None, "model" if heads_fit else None, None)
            return self.fit(spec, shape)
        if name == "ssm":   # [, B, H, P, N]
            spec = (None, ("data", "model")) if self.shard_cache_seq else (dp, "model")
            return self.fit(spec + (None, None), shape)
        if name == "conv":  # [, B, W-1, C]
            spec = ((None,) if self.shard_cache_seq else (dp,)) + (None, "model")
            return self.fit(spec, shape)
        return P(*((None,) * ndim))

    def cache_specs(self, shapes: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, x: self.cache_spec(_path_str(path), tuple(x.shape)), shapes
        )

    # ------------------------------------------------------------------
    # activation hints (installed via repro.sharding.ctx)
    # ------------------------------------------------------------------
    def hints(self) -> dict[str, NamedSharding]:
        dp = self.dp_axes
        mk = lambda *spec: NamedSharding(self.mesh, P(*spec))
        h = {
            "act_bsd": mk(dp, None, None),
            "act_bshp": mk(dp, None, "model", None),
            "moe_ecd": mk("model", None, None),
        }
        if self.vocab and self.vocab % self.mesh.shape.get("model", 1) == 0:
            h["logits_bsv"] = mk(dp, None, "model")
        return h


def make_policy(
    mesh: Mesh, cfg: ModelConfig | None = None, *, fsdp: bool = True,
    shard_cache_seq: bool = False, qkv_contraction: bool = False,
) -> ShardingPolicy:
    return ShardingPolicy(
        mesh=mesh, fsdp=fsdp, shard_cache_seq=shard_cache_seq,
        vocab=cfg.vocab if cfg is not None else 0,
        qkv_contraction=qkv_contraction,
    )


def param_specs(shapes: Any, mesh: Mesh, **kw) -> Any:
    return make_policy(mesh, **kw).param_specs(shapes)


# --------------------------------------------------------------------------
# Coded serving head: whole code blocks per device (DESIGN.md §10)
# --------------------------------------------------------------------------
def validate_coded_head_mesh(mesh: Mesh, n_blocks: int, axis: str = "model") -> None:
    """Assert the geometry the shard_map head needs: whole code blocks per
    device, i.e. an axis size that divides the block count.  Erasure stays
    per block; with one block per device it is also per device."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis (axes: {mesh.axis_names})")
    size = mesh.shape[axis]
    if n_blocks % size:
        raise ValueError(
            f"coded head has {n_blocks} blocks but mesh axis {axis!r} has "
            f"{size} devices; the sharded head wants whole blocks on every "
            f"device, so the axis size must divide the block count"
        )


def coded_head_sharding(mesh: Mesh, axis: str = "model") -> NamedSharding:
    """Sharding for ``lm_head_coded`` [n_blocks*br, in]: blocks over ``axis``.

    Placing the coded weight ONCE with this sharding keeps the per-step
    shard_map from resharding it on every decode."""
    return NamedSharding(mesh, P(axis, None))
