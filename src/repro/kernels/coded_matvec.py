"""Pallas TPU kernel: tiled coded matvec / thin matmul  y = Â x.

The paper's per-worker hot loop is a BLAS dgemv on EC2 CPU cores.  The TPU
adaptation restructures it for the MXU + VMEM hierarchy (DESIGN.md §6):

  * grid (R/BR, M/BM): row blocks x column panels; the column panel loop is
    innermost so the fp32 output block stays resident in VMEM and
    accumulates across panels (one HBM write per row block);
  * block shapes are MXU-aligned (multiples of 8 x 128 for fp32, 16 x 128
    for bf16); the decode batch dim (<= 8 for matvec-shaped serving) rides
    along in the x/out blocks so the systolic array sees a [BR, BM]x[BM, B]
    matmul instead of a rank-1 dgemv;
  * VMEM budget at the default (BR, BM) = (256, 512):
    A block 512 KB (fp32) + x 16 KB + out 8 KB  ~=  0.5 MB  <<  16 MB.

BPCC batching: one worker's rows arrive as ``p`` batches; the wrapper in
``ops.py`` simply calls this kernel per batch slice — the row-block grid
already processes rows in batch order, so batch-k partial results are the
first k x (l/p) output rows (no extra kernel work needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["coded_matvec_pallas"]


def _kernel(a_ref, x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        x_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # coded rows are not bf16-exact
    )


@functools.partial(jax.jit, static_argnames=("block_r", "block_m", "interpret"))
def coded_matvec_pallas(
    a: jnp.ndarray,           # [R, M]
    x: jnp.ndarray,           # [M] or [M, B] (thin)
    *,
    block_r: int = 256,
    block_m: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    r, m = a.shape
    b = x.shape[1]
    br, bm = min(block_r, r), min(block_m, m)
    # pad to block multiples (XLA pads/slices are fused and cheap vs the GEMV)
    rp, mp = -(-r // br) * br, -(-m // bm) * bm
    a_p = jnp.pad(a, ((0, rp - r), (0, mp - m)))
    x_p = jnp.pad(x, ((0, mp - m), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid=(rp // br, mp // bm),
        in_specs=[
            pl.BlockSpec((br, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bm, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, b), jnp.float32),
        interpret=interpret,
    )(a_p, x_p)
    out = out[:r]
    return out[:, 0] if squeeze else out
