"""SPMD coded computation on a JAX mesh — the paper's dataflow, XLA-native.

The paper's asynchronous "first r rows win" cannot live *inside* one XLA
program (SPMD is bulk-synchronous), so this module provides the
deterministic-latency equivalent (DESIGN.md §2): redundant computation plus
**fixed-shape masked recovery**, so that the erasure of any <= e workers'
results never changes program shape — only the 0/1 mask.

Granularities:

  * **Block-MDS CodedLinear** (TPU-native, the serving fast path):
    the output rows of a weight matrix are split into ``n_data`` blocks, and
    ``n_parity`` extra blocks hold Cauchy linear combinations.  One block per
    device along the `model` mesh axis.  Any ``n_data`` surviving blocks
    recover the output with a tiny (n_data x n_data) solve — O(blocks²)
    decode instead of the paper's O(r²), the right trade for a 16-wide TPU
    mesh where failures are per-chip, not per-row.
  * **Row-level Gaussian coding** (paper-faithful granularity): Â = H A with
    dense H, masked least-squares recovery (``repro.core.decoding``).  Used
    by the emulator and validated against the block path in tests.
  * **BPCC batch streaming**: each shard's rows are processed in ``p``
    batches via ``lax.scan`` with a per-batch arrival mask, so partial
    results exist as first-class values — the XLA analogue of the paper's
    partial-result return (and the hook for early-exit approximate serving).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "block_mds_generator",
    "block_mds_generator_np",
    "block_rows",
    "CodedLinear",
    "encode_blocks",
    "decode_blocks",
    "decode_blocks_svd",
    "coded_block_matmul",
    "bpcc_batched_matvec",
    "row_coded_matvec",
]

# Parity blocks and recovery matrices are not bfloat16-exact, and a TPU runs
# a float32 matmul at DEFAULT precision as one bfloat16 pass: the rounding
# would then be amplified by the surviving blocks' condition number.  Every
# contraction that touches coded blocks asks for full float32 precision
# (a no-op on CPU; on TPU the head matvec is bandwidth-bound, so the extra
# MXU passes cost nothing visible).
EXACT = jax.lax.Precision.HIGHEST
# --------------------------------------------------------------------------
# Block-level systematic MDS code (identity + Cauchy parity)
# --------------------------------------------------------------------------
_GEN_CACHE: dict[tuple[int, int], np.ndarray] = {}


def _worst_erasure_cond(b: np.ndarray, n_parity: int, max_patterns: int = 4096) -> float:
    """Worst condition number of the surviving-rows matrix over erasure
    patterns of size n_parity (exhaustive when feasible, else sampled)."""
    import itertools

    n_blocks = b.shape[0]
    pats = itertools.combinations(range(n_blocks), n_parity)
    g = np.random.Generator(np.random.PCG64(0))
    all_pats = list(itertools.islice(pats, max_patterns + 1))
    if len(all_pats) > max_patterns:
        all_pats = [
            tuple(g.choice(n_blocks, size=n_parity, replace=False))
            for _ in range(max_patterns)
        ]
    worst = 1.0
    for pat in all_pats:
        keep = np.ones(n_blocks, bool)
        keep[list(pat)] = False
        s = np.linalg.svd(b[keep], compute_uv=False)
        worst = max(worst, s[0] / max(s[-1], 1e-300))
    return worst


def block_mds_generator_np(
    n_blocks: int, n_data: int, n_seeds: int = 32
) -> np.ndarray:
    """Host-side (numpy, float64) systematic generator — see block_mds_generator.

    Split out so the DecoderCache can build its pseudo-inverse table without
    touching jnp (jnp constants created inside a shard_map trace are lifted
    to tracers, which would poison the host-side float64 precompute).
    """
    if n_blocks < n_data:
        raise ValueError(f"need n_blocks >= n_data, got {n_blocks} < {n_data}")
    n_parity = n_blocks - n_data
    eye = np.eye(n_data, dtype=np.float64)
    if n_parity == 0:
        return eye
    key = (n_blocks, n_data)
    if key not in _GEN_CACHE:
        best, best_cond = None, np.inf
        for seed in range(n_seeds):
            g = np.random.Generator(np.random.PCG64(1234 + seed))
            parity = g.standard_normal((n_parity, n_data))
            parity /= np.linalg.norm(parity, axis=1, keepdims=True)
            b = np.concatenate([eye, parity], axis=0)
            c = _worst_erasure_cond(b, n_parity)
            if c < best_cond:
                best, best_cond = b, c
        _GEN_CACHE[key] = best
    return _GEN_CACHE[key]


def block_mds_generator(
    n_blocks: int, n_data: int, dtype=jnp.float32, n_seeds: int = 32
) -> jnp.ndarray:
    """Systematic generator B [n_blocks, n_data]: I on top, random parity below.

    Parity rows are i.i.d. Gaussian (unit row-norm): any ``n_data`` rows of B
    are linearly independent w.p. 1 — the block-level analogue of the paper's
    "any r rows of H full-rank" property (§2.2.2) — and, unlike structured
    Cauchy/Vandermonde parities whose far-apart real nodes make
    erased-column submatrices numerically rank-deficient, random submatrices
    stay well-conditioned.  Because float32 decode accuracy is governed by
    the *worst* erasure pattern, the seed is chosen once per (n_blocks,
    n_data) by minimizing the worst-case surviving-submatrix condition
    number (exhaustive over patterns when feasible); the search result is
    cached for the process lifetime.
    """
    return jnp.asarray(block_mds_generator_np(n_blocks, n_data, n_seeds), dtype=dtype)


def block_rows(out: int, n_data: int, dtype=jnp.float32) -> int:
    """Rows per code block: ``ceil(out / n_data)`` rounded up to the
    sublane tile of ``dtype`` (8 rows of 4 bytes, 16 of 2, 32 of 1).

    Blocks that start on tile boundaries let XLA view the stored
    ``[n_blocks * br, in]`` weight as ``[n_blocks, br, in]`` in place; an
    unaligned ``br`` makes the per-step block matmul relayout the whole
    weight first.  The extra rows are zero padding, like the ceil's.
    """
    tile = 8 * max(1, 4 // np.dtype(dtype).itemsize)
    rows = -(-out // n_data)  # ceil
    return -(-rows // tile) * tile


def encode_blocks(w: jnp.ndarray, n_data: int, n_parity: int) -> jnp.ndarray:
    """Encode weight rows into (n_data + n_parity) blocks.

    w [out, in]  ->  [n_blocks * br, in], br = ``block_rows(out, n_data,
    w.dtype)`` (zero row padding).  Block j (j >= n_data) = sum_i B[j, i] *
    block_i.  Done once, offline (paper: Â = H A is pre-stored), so plain
    einsum is fine here.
    """
    out, inner = w.shape
    br = block_rows(out, n_data, w.dtype)
    pad = n_data * br - out
    wp = jnp.pad(w, ((0, pad), (0, 0)))
    blocks = wp.reshape(n_data, br, inner)
    b = block_mds_generator(n_data + n_parity, n_data, dtype=w.dtype)
    coded = jnp.einsum("bd,dri->bri", b, blocks, precision=EXACT)
    return coded.reshape((n_data + n_parity) * br, inner)


def decode_blocks_svd(
    y_coded: jnp.ndarray, mask: jnp.ndarray, n_data: int, n_parity: int
) -> jnp.ndarray:
    """Reference decode: in-graph SVD pseudo-inverse of the masked generator.

    Kept as (a) the oracle the DecoderCache fast path is tested against
    exhaustively, (b) the fallback for code geometries too wide for the
    mask lut (> ``decoding.MAX_LUT_BLOCKS`` blocks), and (c) the seed
    baseline the decode benchmark A/Bs.  Two iterative-refinement steps
    against the *unsquared* operator (normal equations would square the
    submatrix condition number — with float32's ~7 digits that visibly
    corrupts unlucky erasure patterns; pinv+refine keeps the worst pattern
    at ~1e-6 relative).
    """
    n_blocks = n_data + n_parity
    b = block_mds_generator(n_blocks, n_data, dtype=jnp.float32)
    m = mask.astype(jnp.float32)
    bm = b * m[:, None]                                    # [n_blocks, n_data]
    pinv = jnp.linalg.pinv(bm, rtol=1e-6)                  # [n_data, n_blocks]
    flat = (
        y_coded.astype(jnp.float32)
        * m.reshape((n_blocks,) + (1,) * (y_coded.ndim - 1))
    ).reshape(n_blocks, -1)
    sol = jnp.matmul(pinv, flat, precision=EXACT)
    for _ in range(2):  # refinement against bm (cond, not cond²)
        resid = flat - jnp.matmul(bm, sol, precision=EXACT)
        sol = sol + jnp.matmul(pinv, resid, precision=EXACT)
    return sol.reshape((n_data,) + y_coded.shape[1:]).astype(y_coded.dtype)


def decode_blocks(
    y_coded: jnp.ndarray, mask: jnp.ndarray, n_data: int, n_parity: int
) -> jnp.ndarray:
    """Recover the data blocks from any ``n_data`` surviving coded blocks.

    y_coded [n_blocks, br, ...] — coded partial results (erased entries may
    hold garbage); mask [n_blocks] — 1.0 where the block's worker survived.

    Hot path (DESIGN.md §2): the refined float64 pseudo-inverse of every
    decodable erasure pattern is precomputed once in a ``DecoderCache``;
    the in-graph decode is a mask-keyed table gather plus ONE small matmul.
    No SVD custom-call in the step HLO (asserted in tests/test_hlo.py) —
    deterministic shape, differentiable, shard_map-transparent.  Geometries
    wider than the lut bound fall back to :func:`decode_blocks_svd`.
    """
    from repro.core.decoding import cacheable, get_decoder_cache

    n_blocks = n_data + n_parity
    if not cacheable(n_data, n_parity):
        return decode_blocks_svd(y_coded, mask, n_data, n_parity)
    rec = get_decoder_cache(n_data, n_parity).recovery(mask)  # [n_data, n_blocks]
    m = mask.astype(jnp.float32)
    flat = (
        y_coded.astype(jnp.float32)
        * m.reshape((n_blocks,) + (1,) * (y_coded.ndim - 1))
    ).reshape(n_blocks, -1)
    sol = jnp.matmul(rec, flat, precision=EXACT)
    return sol.reshape((n_data,) + y_coded.shape[1:]).astype(y_coded.dtype)


# --------------------------------------------------------------------------
# CodedLinear — the first-class framework feature
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class CodedLinear:
    """A straggler-tolerant linear layer: y = W x with n_parity redundancy.

    The coded weight lives sharded one-block-per-device along ``axis`` of the
    mesh; ``apply`` computes all coded blocks (each device its own), then
    recovers the true output from the surviving ones.  With mask == 1 the
    decode degenerates to reading off the systematic prefix (checked in
    tests to machine precision).
    """

    n_data: int
    n_parity: int
    out_features: int

    @property
    def n_blocks(self) -> int:
        return self.n_data + self.n_parity

    @property
    def block_rows(self) -> int:
        """Rows per block of a float32 encode (:func:`block_rows`)."""
        return block_rows(self.out_features, self.n_data)

    def encode(self, w: jnp.ndarray) -> jnp.ndarray:
        return encode_blocks(w, self.n_data, self.n_parity)

    def apply(
        self,
        w_coded: jnp.ndarray,
        x: jnp.ndarray,
        mask: jnp.ndarray,
        *,
        kernel_mode: str | None = None,
    ) -> jnp.ndarray:
        """x [in, batch] -> y [out, batch]; w_coded [n_blocks*br, in].

        Default: XLA block matmul + mask-keyed cached decode (DESIGN.md §2).
        ``kernel_mode`` selects the implementation:

          * ``None`` — the default cached path;
          * ``'interpret'``/``'compile'``/``'off'`` — the fused matmul+decode
            dataflow (``repro.kernels.ops.coded_matvec_decode``), which
            applies the recovery matrix to block outputs while they are
            VMEM-resident — one HBM write total (DESIGN.md §6);
          * ``'svd'`` — force the seed's in-graph SVD fallback (the A/B
            baseline the autotuner and decode bench measure against);
          * ``'auto'`` — per-shape dispatch via the autotune table with
            analytical-model fallback (``repro.kernels.dispatch``,
            DESIGN.md §11), resolved at trace time from static shapes.

        Geometries the DecoderCache refuses cannot run the fused kernel (it
        needs the cached recovery matrix): they take the default path, whose
        ``decode_blocks`` falls back to SVD internally.
        """
        params: dict = {}
        if kernel_mode == "auto":
            from repro.kernels.dispatch import choose_coded_linear
            from repro.sharding.ctx import current_macro_step_k

            d = choose_coded_linear(
                self.out_features, w_coded.shape[1],
                x.shape[1] if x.ndim == 2 else 1,
                self.n_data, self.n_parity,
                macro_k=current_macro_step_k(),
            )
            kernel_mode, params = d.kernel_mode, dict(d.params)
        if kernel_mode is not None and kernel_mode != "svd":
            from repro.core.decoding import cacheable, get_decoder_cache

            if cacheable(self.n_data, self.n_parity):
                from repro.kernels.ops import coded_matvec_decode

                rec = get_decoder_cache(self.n_data, self.n_parity).recovery(mask)
                y = coded_matvec_decode(w_coded, x, rec, mode=kernel_mode,
                                        **params)
                return y[: self.out_features]
        # rows sharded -> each device computes its block
        br = w_coded.shape[0] // self.n_blocks
        y_coded = jnp.matmul(w_coded, x, precision=EXACT)
        y_coded = y_coded.reshape(self.n_blocks, br, -1)
        if kernel_mode == "svd":
            y = decode_blocks_svd(y_coded, mask, self.n_data, self.n_parity)
        else:
            y = decode_blocks(y_coded, mask, self.n_data, self.n_parity)
        y = y.reshape(self.n_data * br, -1)
        return y[: self.out_features]


def coded_block_matmul(
    mesh: Mesh,
    axis: str,
    w_coded: jnp.ndarray,
    x: jnp.ndarray,
    mask: jnp.ndarray,
    n_data: int,
    n_parity: int,
    kernel_mode: str | None = None,
) -> jnp.ndarray:
    """shard_map form of CodedLinear.apply — the collective schedule is
    explicit: local block matmul over this device's ``n_blocks / size``
    contiguous code blocks, one all-reduce that assembles the (small) coded
    outputs on every device, replicated tiny decode.  Each device writes its
    blocks into a zero buffer at its own offset before the ``psum``, so
    every output row has exactly one non-zero addend: the sum is exact, and
    its result is replicated by construction, which is what the replicated
    ``out_specs`` asks shard_map to check.  Bytes on the wire are those of
    an all-reduce of n_blocks*br*batch*4 — the coding overhead is visible in
    the HLO and charged in the roofline.

    ``kernel_mode`` routes each device's LOCAL block matmul through the
    tiled Pallas ``coded_matvec`` kernel (``'interpret'``/``'compile'``);
    None keeps the plain XLA matmul — which is also the bit-identity
    contract with the single-device CodedLinear path (same per-row dot
    products, same decode_blocks arithmetic on the assembled outputs).
    ``'auto'`` resolves per LOCAL shard shape at trace time
    (``repro.kernels.dispatch``); when the dispatcher picks the jnp
    reference it degrades to the plain matmul, preserving the bit-identity
    contract on backends where the Pallas kernel has no edge.
    """
    n_blocks = n_data + n_parity
    br = w_coded.shape[0] // n_blocks

    def local(wc, xc, m):
        mode, params = kernel_mode, {}
        if mode == "auto":
            from repro.kernels.dispatch import choose_matvec
            from repro.sharding.ctx import current_macro_step_k

            d = choose_matvec(wc.shape[0], wc.shape[1],
                              xc.shape[1] if xc.ndim == 2 else 1,
                              macro_k=current_macro_step_k())
            mode, params = (None if d.impl == "ref" else d.mode), dict(d.params)
        if mode is not None:
            from repro.kernels.ops import coded_matvec

            y_local = coded_matvec(wc, xc, mode=mode, **params)
        else:
            y_local = jnp.matmul(wc, xc, precision=EXACT)  # [rows_local, batch]
        rows = y_local.shape[0]
        y_all = jnp.zeros((n_blocks * br,) + y_local.shape[1:], y_local.dtype)
        y_all = jax.lax.dynamic_update_slice_in_dim(
            y_all, y_local, jax.lax.axis_index(axis) * rows, axis=0
        )
        y_all = jax.lax.psum(y_all, axis).reshape(n_blocks, br, -1)
        return decode_blocks(y_all, m, n_data, n_parity).reshape(n_data * br, -1)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(None)),
        out_specs=P(None, None),
    )
    return fn(w_coded, x, mask)


# --------------------------------------------------------------------------
# BPCC batch streaming inside XLA
# --------------------------------------------------------------------------
def bpcc_batched_matvec(
    a_rows: jnp.ndarray, x: jnp.ndarray, p: int, arrived: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One worker's BPCC loop: process ``p`` row-batches, mask by arrival.

    a_rows [l, m] (l divisible by p), x [m] or [m, b], arrived [p] 0/1 —
    which batches reached the master by the deadline.  Returns
    (y [l, ...] with unarrived batches zeroed, rows_delivered scalar).

    Expressed as ``lax.scan`` over batches so partial results are program
    values: the serving engine reads them off batch-by-batch, and XLA sees
    the same loop structure a real streaming worker would run.
    """
    l = a_rows.shape[0]
    if l % p != 0:
        raise ValueError(f"rows {l} not divisible by batches {p}")
    b = l // p
    batches = a_rows.reshape(p, b, *a_rows.shape[1:])

    def step(carry, inp):
        batch, m = inp
        y = (batch @ x) * m
        return carry + m * b, y

    rows, ys = jax.lax.scan(step, jnp.zeros((), x.dtype), (batches, arrived.astype(x.dtype)))
    return ys.reshape(l, *ys.shape[2:]), rows


# --------------------------------------------------------------------------
# Row-level (paper-granularity) coded matvec
# --------------------------------------------------------------------------
def row_coded_matvec(
    a_hat: jnp.ndarray, x: jnp.ndarray, g_full: jnp.ndarray, row_mask: jnp.ndarray
) -> jnp.ndarray:
    """Fine-grained path: ŷ = Â x, recover y from the surviving rows.

    a_hat [q, m], g_full [q, r] dense Gaussian generator, row_mask [q].
    O(r²) decode — kept for fidelity + cross-validation, not the fast path.
    """
    from repro.core.decoding import masked_pinv_decode

    y_hat = a_hat @ x
    if y_hat.ndim == 1:
        y_hat = y_hat[:, None]
        return masked_pinv_decode(g_full, y_hat, row_mask)[:, 0]
    return masked_pinv_decode(g_full, y_hat, row_mask)
