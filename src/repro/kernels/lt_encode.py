"""Pallas TPU encode kernels: LT fountain gather-encode + tiled dense encode.

LT (``lt_encode_pallas``): Â[j] = Σ_d coeffs[j,d]·A[indices[j,d]] — a sparse
row-gather + accumulate.  On TPU, arbitrary dynamic gathers inside a kernel
are expressed with **scalar prefetch**: the degree table (indices, coeffs)
is prefetched to SMEM and the A BlockSpec's index_map reads the *source row
id* from it — the DMA engine then streams exactly the needed [1, BM] row
panel HBM->VMEM per grid step.  A and the output are viewed as [rows, 1, M]
so a one-row block spans a whole (second-minor) dim, as the TPU's (8, 128)
block tiling requires:

    grid = (q, M/BM, d_max)   (d innermost: output panel accumulates in VMEM)
    A block     (1, BM)  at (indices[i, d], 0, j)
    out block   (1, BM)  at (i, 0, j)

Padding entries (coeff 0) gather row 0 and multiply by zero.  Row blocks of
height 1 trade MXU alignment for gather flexibility — acceptable because
the full LT encode is offline in the paper (Â pre-stored) and bandwidth-
bound, not FLOP-bound; the roofline charges it to the memory term.

Dense (``gaussian_encode_pallas``): Â = G A with a dense generator slice
G [q, r] — a plain tiled MXU matmul.  This is the ADAPTIVE path's kernel
(DESIGN.md §9): reserve top-ups and serving parity (re-)encodes are
mid-task, so unlike the offline full encode they sit on the control loop's
critical path and must not round-trip through the host:

    grid = (q/BQ, M/BM, r/BK)   (k innermost: the fp32 [BQ, BM] output tile
                                 stays VMEM-resident across the contraction
                                 — one HBM write per output tile)
    G block   (BQ, BK) at (i, k)
    A block   (BK, BM) at (k, j)
    out block (BQ, BM) at (i, j)

VMEM at the default (BQ, BM, BK) = (128, 512, 512): G tile 256 KB + A tile
1 MB + out 256 KB ≈ 1.5 MB << 16 MB, comfortably double-buffered.  The jnp
oracle is ``repro.kernels.ref.ref_gaussian_encode``; the mode-switchable
wrappers are ``repro.kernels.ops.gaussian_encode`` / ``encode_rows``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lt_encode_pallas", "gaussian_encode_pallas"]


def _kernel(idx_ref, cf_ref, a_ref, o_ref):
    i = pl.program_id(0)
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += cf_ref[i, d] * a_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def lt_encode_pallas(
    a: jnp.ndarray,         # [r, M] source matrix
    indices: jnp.ndarray,   # [q, d_max] int32 source-row ids (padded)
    coeffs: jnp.ndarray,    # [q, d_max] float32 (0 = padding)
    *,
    block_m: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    r, m = a.shape
    q, d_max = indices.shape
    bm = min(block_m, m)
    mp = -(-m // bm) * bm
    # rows as a leading axis of [r, 1, M]: a (1, BM) block then spans the
    # whole second-minor dim, which the TPU's (8, 128) tiling accepts
    a_p = jnp.pad(a, ((0, 0), (0, mp - m))).reshape(r, 1, mp)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q, mp // bm, d_max),
            in_specs=[
                pl.BlockSpec(
                    (pl.Squeezed(), 1, bm),
                    lambda i, j, d, idx_ref, cf_ref: (idx_ref[i, d], 0, j),
                ),
            ],
            out_specs=pl.BlockSpec(
                (pl.Squeezed(), 1, bm),
                lambda i, j, d, idx_ref, cf_ref: (i, 0, j),
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((q, 1, mp), jnp.float32),
        interpret=interpret,
    )(indices.astype(jnp.int32), coeffs.astype(jnp.float32), a_p)
    return out[:, 0, :m]


def _gauss_kernel(g_ref, a_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        g_ref[...].astype(jnp.float32),
        a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # generators are not bf16-exact
    )


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_m", "block_r", "interpret")
)
def gaussian_encode_pallas(
    g: jnp.ndarray,           # [q, r] dense generator rows to encode
    a: jnp.ndarray,           # [r, M] source matrix
    *,
    block_q: int = 128,
    block_m: int = 512,
    block_r: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Â = G A, tiled for the MXU — the on-device dense/reserve encode."""
    q, r = g.shape
    r2, m = a.shape
    if r != r2:
        raise ValueError(f"generator has {r} columns, A has {r2} rows")
    bq, bm, bk = min(block_q, q), min(block_m, m), min(block_r, r)
    qp, mp, rp = -(-q // bq) * bq, -(-m // bm) * bm, -(-r // bk) * bk
    g_p = jnp.pad(g, ((0, qp - q), (0, rp - r)))
    a_p = jnp.pad(a, ((0, rp - r), (0, mp - m)))
    out = pl.pallas_call(
        _gauss_kernel,
        grid=(qp // bq, mp // bm, rp // bk),
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bq, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, mp), jnp.float32),
        interpret=interpret,
    )(g_p, a_p)
    return out[:q, :m]
