"""Pallas TPU kernels for the Mamba-2 SSD intra-chunk compute.

The SSD algorithm splits the sequence into chunks of Q; per chunk the work
is matmul-shaped (the whole point of state-space *duality*) and MXU-
friendly — these two kernels own it, while the O(S/Q) inter-chunk state
recurrence stays a jnp ``lax.scan`` (sequential, tiny, not kernel-worthy):

  kernel 1 (``ssd_chunk``):  per (group, chunk) grid cell
      L   = exp(segsum(dA))             [Q, Q]  fp32 in VMEM
      y   = (C Bᵀ ∘ L) · X              [Q, P]
      S_c = Xᵀ · (decay ∘ B)            [P, N]  chunk state contribution
  kernel 2 (``ssd_combine``): y += exp(cumsum dA) ∘ (C · S_inᵀ)

VMEM at Q=256, N=128, P=64 (fp32): L + CBᵀ 2x256 KB, X 64 KB, B/C 2x128 KB
≈ 0.85 MB per cell — comfortable; Q is the tuning knob (see §Perf).
Grid is (B·H, nc); head-expansion of grouped B/C happens in the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ssd_chunk_pallas", "ssd_combine_pallas"]


_EXACT = jax.lax.Precision.HIGHEST  # f32 cumsums/state updates stay f32


def _chunk_kernel(x_ref, da_ref, b_ref, c_ref, y_ref, st_ref, dec_ref, cum_ref):
    q = x_ref.shape[1]
    da = da_ref[0].astype(jnp.float32)                    # [Q, 1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = rows >= cols
    # inclusive cumsum as a lower-triangular matmul on the MXU
    cum = jnp.dot(causal.astype(jnp.float32), da,
                  preferred_element_type=jnp.float32, precision=_EXACT)  # [Q, 1]
    cum_b = jnp.broadcast_to(cum, (q, q))                 # [i, j] -> cum[i]
    ell = jnp.where(causal, jnp.exp(cum_b - cum_b.T), 0.0)  # exp(cum[i]-cum[j])
    c = c_ref[0].astype(jnp.float32)                      # [Q, N]
    b = b_ref[0].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)                      # [Q, P]
    cb = jax.lax.dot_general(                             # C Bᵀ  [Q, Q]
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_EXACT,
    )
    y_ref[0] = jnp.dot(cb * ell, x, preferred_element_type=jnp.float32,
                       precision=_EXACT)
    total = cum[q - 1:q, :]                               # [1, 1]
    decay_states = jnp.exp(total - cum)                   # [Q, 1]
    st_ref[0] = jax.lax.dot_general(                      # Xᵀ (decay ∘ B)  [P, N]
        x, b * decay_states, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_EXACT,
    )
    dec_ref[0] = jnp.exp(total)
    cum_ref[0] = cum


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(
    x: jnp.ndarray,    # [G, Q, P]  (G = B*H, pre-multiplied by dt)
    da: jnp.ndarray,   # [G, Q]
    b: jnp.ndarray,    # [G, Q, N]  head-expanded
    c: jnp.ndarray,    # [G, Q, N]
    *,
    interpret: bool = False,
):
    """Returns (y_diag [G,Q,P], states [G,P,N], total_decay [G], cum [G,Q]).

    Per-cell vectors travel as [Q, 1] columns (and the decay as [1, 1]):
    their blocks then span whole trailing dims, as the TPU's (8, 128) block
    tiling requires."""
    g, q, p = x.shape
    n = b.shape[-1]
    y, st, dec, cum = pl.pallas_call(
        _chunk_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, q, p), jnp.float32),
            jax.ShapeDtypeStruct((g, p, n), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, da[..., None], b, c)
    return y, st, dec[:, 0, 0], cum[..., 0]


def _combine_kernel(c_ref, cum_ref, st_ref, y_ref):
    c = c_ref[0].astype(jnp.float32)          # [Q, N]
    cum = cum_ref[0].astype(jnp.float32)      # [Q, 1]
    st = st_ref[0].astype(jnp.float32)        # [P, N]
    y_ref[0] = jnp.exp(cum) * jax.lax.dot_general(   # C S_inᵀ  [Q, P]
        c, st, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_EXACT,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_combine_pallas(
    c: jnp.ndarray,         # [G, Q, N]
    cum: jnp.ndarray,       # [G, Q]
    states_in: jnp.ndarray, # [G, P, N]  (state entering each chunk)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    g, q, n = c.shape
    p = states_in.shape[1]
    return pl.pallas_call(
        _combine_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p, n), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, q, p), jnp.float32),
        interpret=interpret,
    )(c, cum[..., None], states_in)
