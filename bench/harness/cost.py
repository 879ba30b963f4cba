"""Peaks of the chip, and the operations and bytes the algorithm needs.

Counts are taken from the shapes and dtypes of the arrays a step is
handed (anything with ``.shape`` and ``.dtype``: arrays or
``jax.ShapeDtypeStruct``), so a change of dtype is followed.  They are the
algorithm's least, not what the program happens to do:

* every weight a step needs is read once; the coded head needs only
  ``n_data`` of its ``n_data + n_parity`` blocks (any ``n_data`` survivors
  decode), and the plain ``lm_head`` that the coded path never reads is not
  counted;
* the key/value cache is read for the positions attended, not the whole
  capacity, and written for the one new position;
* a matmul of a ``[.., in, out]`` weight with ``t`` tokens is ``2 * size *
  t`` operations; attention at context ``c`` is ``4 * heads * head_dim * c``
  a layer and token.

With these, no share of a roofline can pass 100% by honest work.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = ["PEAKS", "Peaks", "peaks", "DenseShape", "decode_step_cost",
           "prefill_flops", "decode_token_flops", "coded_head_cost",
           "least_seconds", "layer_matmul_params"]


@dataclass(frozen=True)
class Peaks:
    flops: float          # dense bfloat16 matmul, per second
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# keyed by jax's ``device_kind``; a device missing here is an error
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


def least_seconds(flops: float, nbytes: float, pk: Peaks) -> float:
    """The least time of a call: the larger of its two bounds."""
    return max(flops / pk.flops, nbytes / pk.hbm_bytes_per_s)


def _nbytes(a) -> int:
    return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize


def _size(a) -> int:
    return int(np.prod(a.shape))


@dataclass(frozen=True)
class DenseShape:
    """The sizes of the dense family that attention and the head need."""
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_model: int
    vocab: int
    n_data: int = 0       # coded head: data blocks (0 = plain head)
    n_parity: int = 0

    @classmethod
    def of(cls, cfg: dict, n_blocks: int = 0) -> "DenseShape":
        """From a configuration file's keys; ``n_blocks`` is the coded
        head's block count (unused for a plain head)."""
        hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
        n_par = cfg.get("coded_parity", 0) if cfg.get("coded") else 0
        n_data = n_blocks - n_par if cfg.get("coded") else 0
        return cls(cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"], hd,
                   cfg["d_model"], cfg["vocab"], n_data, n_par)


def _layer_matmul(params) -> tuple[int, int, int]:
    """(matmul parameters, their bytes, other layer bytes) of the stacked
    layer weights: a leaf of three or more dims ``[layer, in.., out..]``
    is a matmul weight, the rest (norm scales) are only read."""
    import jax

    mm = mm_bytes = other = 0
    for leaf in jax.tree.leaves(params["blocks"]):
        if len(leaf.shape) >= 3:
            mm += _size(leaf)
            mm_bytes += _nbytes(leaf)
        else:
            other += _nbytes(leaf)
    return mm, mm_bytes, other


def coded_head_cost(w_coded, batch: int, n_data: int, n_parity: int,
                    x_itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes) of ``coded_head_matvec`` at ``batch`` columns: the
    ``n_data`` blocks that any decode needs, their input and output."""
    rows, d = w_coded.shape
    share = n_data / (n_data + n_parity)
    rows_needed = rows * share
    flops = 2.0 * rows_needed * d * batch
    nbytes = _nbytes(w_coded) * share + (d + rows_needed) * batch * x_itemsize
    return flops, nbytes


def _head_cost(params, shp: DenseShape, tokens: int) -> tuple[float, float]:
    if "lm_head_coded" in params:
        return coded_head_cost(params["lm_head_coded"], tokens, shp.n_data,
                               shp.n_parity)
    head = params["lm_head"] if "lm_head" in params else params["embed"]
    return 2.0 * _size(head) * tokens, float(_nbytes(head))


def decode_step_cost(params, cache, shp: DenseShape,
                     positions) -> tuple[float, float]:
    """(flops, bytes) of one decode step for the slots at ``positions``
    (each slot's write position; it attends ``position + 1`` keys)."""
    pos = np.asarray(positions, np.int64)
    b = len(pos)
    mm, mm_bytes, other = _layer_matmul(params)
    flops = 2.0 * mm * b
    nbytes = float(mm_bytes + other)
    ctx = float((pos + 1).sum())
    flops += 4.0 * shp.n_layers * shp.n_heads * shp.head_dim * ctx
    kv_item = np.dtype(cache["blocks"]["attn_0"]["k"].dtype).itemsize
    per_pos = 2 * shp.n_layers * shp.n_kv_heads * shp.head_dim * kv_item
    nbytes += per_pos * (ctx + b)  # read the attended keys, write the new one
    emb = params["embed"]
    nbytes += b * shp.d_model * np.dtype(emb.dtype).itemsize
    nbytes += _nbytes(params["final_norm"])
    hf, hb = _head_cost(params, shp, b)
    return flops + hf, nbytes + hb


def decode_token_flops(shp: DenseShape, matmul_params: int,
                       position: int) -> float:
    """Model operations of one decoded token at ``position`` (the useful
    work: the plain head's vocabulary, not the code's redundancy)."""
    return (2.0 * matmul_params + 2.0 * shp.d_model * shp.vocab
            + 4.0 * shp.n_layers * shp.n_heads * shp.head_dim * (position + 1))


def prefill_flops(shp: DenseShape, matmul_params: int, length: int) -> float:
    """Model operations of a prefill of ``length`` tokens (head on the last
    position only, causal attention)."""
    attn = 4.0 * shp.n_layers * shp.n_heads * shp.head_dim * length * (length + 1) / 2
    return 2.0 * matmul_params * length + attn + 2.0 * shp.d_model * shp.vocab


def layer_matmul_params(params) -> int:
    return _layer_matmul(params)[0]
