"""Pallas TPU kernel: fused coded block matmul + erasure decode.

``CodedLinear.apply`` is two GEMMs: the big coded block matmul
``y_c = W_c x`` ([n_blocks*br, M] x [M, B]) followed by the tiny recovery
contraction ``y = R y_c`` over the block axis.  Done as separate XLA ops the
coded partials round-trip through HBM: n_blocks*br*B fp32 written, read
back, and n_data*br*B written again.  This kernel applies the recovery
matrix while the block outputs are still VMEM-resident (DESIGN.md §6):

  * grid (br/BT, M/BM): row tiles x column panels, column panel innermost so
    the fp32 coded partials [n_blocks*BT, B] accumulate across panels in a
    VMEM scratch — the coded partials never leave VMEM;
  * on the last panel the row tile is decoded in place: each of the n_data
    output blocks is sum_k R[d, k] y_c[k] over the [BT, B] block partials,
    with R ([n_data, n_blocks]) read as scalars from SMEM — n_data*n_blocks
    vector FMAs per row tile, negligible next to the block GEMM, and ONE HBM
    write per row tile;
  * the recovery matrix is the mask-keyed cached pseudo-inverse
    (``repro.core.decoding.DecoderCache``) — erased blocks' columns are
    exactly zero, so their (finite) garbage cannot reach the output;
  * VMEM budget at the default (BT, BM) = (128, 512) with the 16-block
    serving head: W tile 16*128*512*4 = 4 MB + x 16 KB + scratch and out
    (16 blocks, B <= 8 lanes padded to 128) 2 x 1 MB ~= 6 MB < 16 MB; the
    W tile is double-buffered.  Shrink ``block_t`` for wider codes.

The jnp oracle is ``repro.kernels.ref.ref_coded_matvec_decode``; the public
wrapper (mode-switchable) is ``repro.kernels.ops.coded_matvec_decode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["coded_matvec_decode_pallas"]

# parity blocks and recovery weights are not bfloat16-exact: the block GEMM
# runs at full float32 precision (see repro.core.coded_ops.EXACT)
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(r_ref, a_ref, x_ref, o_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                     # [n_blocks, BT, BM]
    nb, bt, bm = a.shape
    # block GEMM on the MXU: [n_blocks*BT, BM] x [BM, B], accumulated over
    # the column panels in VMEM scratch
    acc_ref[...] += jnp.dot(
        a.reshape(nb * bt, bm).astype(jnp.float32),
        x_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=_EXACT,
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _decode():
        # decode while VMEM-resident, once per row tile: each data block is
        # a combination of the [BT, B] block partials with SMEM scalar
        # weights (a [n_blocks, BT*B] view of the partials would fold the
        # batch into the lane dim, which the TPU's layouts cannot do)
        yc = acc_ref[...].reshape(nb, bt, -1)
        for d in range(o_ref.shape[0]):
            y = r_ref[d, 0] * yc[0]
            for k in range(1, nb):
                y = y + r_ref[d, k] * yc[k]
            o_ref[d] = y


@functools.partial(
    jax.jit, static_argnames=("n_blocks", "block_t", "block_m", "interpret")
)
def coded_matvec_decode_pallas(
    w_coded: jnp.ndarray,     # [n_blocks * br, M] coded weight blocks
    x: jnp.ndarray,           # [M] or [M, B] (thin)
    rec: jnp.ndarray,         # [n_data, n_blocks] recovery matrix (mask-keyed)
    *,
    n_blocks: int | None = None,
    block_t: int = 128,
    block_m: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = R·(blocked W_c x), decoded in-kernel — returns [n_data * br(, B)]."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    n_data, nb = rec.shape
    if n_blocks is not None and n_blocks != nb:
        raise ValueError(f"rec says {nb} blocks, got n_blocks={n_blocks}")
    rows, m = w_coded.shape
    if rows % nb:
        raise ValueError(f"{rows} coded rows not divisible by {nb} blocks")
    br = rows // nb
    b = x.shape[1]
    bt, bm = min(block_t, br), min(block_m, m)
    tp, mp = -(-br // bt) * bt, -(-m // bm) * bm
    a_p = jnp.pad(w_coded.reshape(nb, br, m), ((0, 0), (0, tp - br), (0, mp - m)))
    x_p = jnp.pad(x, ((0, mp - m), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid=(tp // bt, mp // bm),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nb, bt, bm), lambda i, j: (0, i, j)),
            pl.BlockSpec((bm, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((n_data, bt, b), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_data, tp, b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nb * bt, b), jnp.float32)],
        interpret=interpret,
    )(rec.astype(jnp.float32), a_p, x_p)
    out = out[:, :br].reshape(n_data * br, b)
    return out[:, 0] if squeeze else out
