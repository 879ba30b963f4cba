"""jit'd public wrappers over the Pallas kernels (with jnp fallback).

``mode`` picks the implementation.  Left as None it follows the platform
(``platform_mode``): the compiled kernel on a TPU, the pure-jnp reference
(``'off'``, the oracle itself) anywhere else.  ``'interpret'`` runs the
kernel bodies through the Pallas interpreter — the kernel dataflow,
executable on CPU — and is only ever asked for by name, by the tests that
check a kernel against its oracle.

``'auto'`` consults the dispatch table / analytical cost model
(``repro.kernels.dispatch``, DESIGN.md §11): the implementation AND its
tile parameters are resolved per (op, shape, dtype, backend) at trace time
— shapes under jit are static, so the resolved kernel is baked into the
compiled program.  Explicitly-passed modes are never overridden, and
explicit tile kwargs win over table parameters.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.coded_decode import coded_matvec_decode_pallas
from repro.kernels.coded_matvec import coded_matvec_pallas
from repro.kernels.lt_encode import gaussian_encode_pallas, lt_encode_pallas
from repro.kernels.ssd_scan import ssd_chunk_pallas, ssd_combine_pallas

Mode = Literal["interpret", "compile", "off", "auto"]


def platform_mode() -> str:
    """The kernel mode of the default backend: the Pallas TPU kernels
    compile only for a TPU; elsewhere the jnp reference runs."""
    return "compile" if jax.default_backend() == "tpu" else "off"


def _auto(decision, kw: dict) -> tuple[str, dict]:
    """(mode, kwargs) from a dispatch Decision; caller kwargs win."""
    return decision.mode or "off", {**decision.params, **kw}

__all__ = [
    "platform_mode",
    "coded_matvec",
    "coded_matvec_decode",
    "coded_head_matvec",
    "lt_encode",
    "gaussian_encode",
    "encode_rows",
    "encode_blocks_device",
    "ssd_forward",
]


def coded_matvec(a, x, mode: Mode | None = None, **kw):
    mode = mode or platform_mode()
    if mode == "auto":
        from repro.kernels.dispatch import choose_matvec
        from repro.sharding.ctx import current_macro_step_k

        b = x.shape[1] if x.ndim == 2 else 1
        mode, kw = _auto(
            choose_matvec(a.shape[0], a.shape[1], b,
                          macro_k=current_macro_step_k()),
            kw,
        )
    if mode == "off":
        return _ref.ref_coded_matvec(a, x)
    return coded_matvec_pallas(a, x, interpret=(mode == "interpret"), **kw)


def coded_matvec_decode(a, x, rec, mode: Mode | None = None, **kw):
    """Fused coded block matmul + erasure decode (DESIGN.md §6).

    ``rec`` is the mask-keyed [n_data, n_blocks] recovery matrix from
    ``repro.core.decoding.DecoderCache.recovery(mask)``.
    """
    mode = mode or platform_mode()
    if mode == "auto":
        from repro.kernels.dispatch import choose_matvec_decode
        from repro.sharding.ctx import current_macro_step_k

        b = x.shape[1] if x.ndim == 2 else 1
        mode, kw = _auto(
            choose_matvec_decode(a.shape[0], a.shape[1], b,
                                 rec.shape[0], rec.shape[1],
                                 macro_k=current_macro_step_k()),
            kw,
        )
    if mode == "off":
        return _ref.ref_coded_matvec_decode(a, x, rec)
    return coded_matvec_decode_pallas(a, x, rec, interpret=(mode == "interpret"), **kw)


def coded_head_matvec(
    w_coded,
    x,
    mask,
    n_data: int,
    n_parity: int,
    *,
    mesh=None,
    axis: str = "model",
    kernel_mode: str | None = None,
):
    """The serving coded-head matvec, dispatched by execution geometry
    (DESIGN.md §10).  w_coded [(n_data+n_parity)*br, in], x [in, batch],
    mask [n_blocks] -> y [n_data*br, batch] fp32.

      * ``mesh`` given — shard_map over ``axis``: whole code blocks per
        device (one each when the axis is as long as the code), local
        block matmul (optionally the Pallas ``coded_matvec`` kernel via
        ``kernel_mode``), a psum that assembles the small coded outputs,
        replicated mask-keyed DecoderCache decode.  Erasing a block is
        exactly zeroing it in the mask.
      * no mesh — the single-program CodedLinear path: one fused block
        matmul + cached decode (or the fused Pallas matmul+decode kernel
        when ``kernel_mode`` is set).

    Both paths share ``decode_blocks`` and the same generator, so the
    sharded head is bit-identical to the single-device head on identical
    masks (asserted in tests/test_serve_mesh.py).  ``kernel_mode='auto'``
    resolves the implementation per shape from the autotune dispatch table
    (``repro.kernels.dispatch``, DESIGN.md §11).
    """
    from repro.core.coded_ops import CodedLinear, coded_block_matmul

    if mesh is not None:
        return coded_block_matmul(
            mesh, axis, w_coded, x, mask, n_data, n_parity,
            kernel_mode=kernel_mode,
        )
    br = w_coded.shape[0] // (n_data + n_parity)
    cl = CodedLinear(n_data=n_data, n_parity=n_parity, out_features=n_data * br)
    return cl.apply(w_coded, x, mask, kernel_mode=kernel_mode)


def lt_encode(a, indices, coeffs, mode: Mode | None = None, **kw):
    mode = mode or platform_mode()
    if mode == "auto":
        from repro.kernels.dispatch import choose_encode

        mode, kw = _auto(
            choose_encode("lt", indices.shape[0], a.shape[0], a.shape[1],
                          d_max=indices.shape[1]),
            kw,
        )
    if mode == "off":
        return _ref.ref_lt_encode(a, indices, coeffs)
    return lt_encode_pallas(a, indices, coeffs, interpret=(mode == "interpret"), **kw)


def gaussian_encode(g, a, mode: Mode | None = None, **kw):
    """Â = G A for a dense generator slice (tiled MXU matmul, DESIGN.md §9)."""
    mode = mode or platform_mode()
    if mode == "auto":
        from repro.kernels.dispatch import choose_encode

        mode, kw = _auto(
            choose_encode("gaussian", g.shape[0], g.shape[1], a.shape[1]), kw
        )
    if mode == "off":
        return _ref.ref_gaussian_encode(g, a)
    return gaussian_encode_pallas(g, a, interpret=(mode == "interpret"), **kw)


def encode_rows(a, plan, start: int, stop: int, mode: Mode | None = None,
                **kw):
    """On-device encode of plan rows [start, stop) — the reserve top-up path.

    Dispatches by code family: dense (gaussian) plans go through the tiled
    matmul kernel on the generator slice; sparse LT plans through the
    scalar-prefetch gather kernel on the degree-table slice.  Returns the
    [stop-start, M] fp32 coded rows.  ``a`` may be any array convertible to
    a device array; the encode itself never leaves the device.
    """
    if not 0 <= start <= stop <= plan.q:
        raise ValueError(f"bad plan row range [{start}, {stop}) for q={plan.q}")
    a = jnp.asarray(a)
    if plan.kind == "gaussian":
        # a dense plan's coeffs ARE the generator (indices = arange(r))
        return gaussian_encode(jnp.asarray(plan.coeffs[start:stop]), a, mode, **kw)
    return lt_encode(
        a,
        jnp.asarray(plan.indices[start:stop]),
        jnp.asarray(plan.coeffs[start:stop]),
        mode,
        **kw,
    )


def encode_blocks_device(
    w, n_data: int, n_parity: int, mode: Mode | None = None, **kw
):
    """Block-MDS weight encode through the tiled kernel (DESIGN.md §9).

    The serving analogue of ``encode_rows``: ``coded_ops.encode_blocks``'s
    einsum, restructured as  B [n_blocks, n_data] @ blocks [n_data, br*in]
    so a ParityController-driven parity re-encode runs on device without a
    host round-trip.  w [out, in] -> [(n_data+n_parity)*br, in] fp32, with
    ``encode_blocks``'s tile-aligned ``br``.
    """
    from repro.core.coded_ops import block_mds_generator_np, block_rows

    w = jnp.asarray(w)
    out, inner = w.shape
    br = block_rows(out, n_data, w.dtype)
    wp = jnp.pad(w, ((0, n_data * br - out), (0, 0)))
    blocks = wp.reshape(n_data, br * inner)
    b = jnp.asarray(block_mds_generator_np(n_data + n_parity, n_data), jnp.float32)
    coded = gaussian_encode(b, blocks, mode, **kw)
    return coded.reshape((n_data + n_parity) * br, inner)


def ssd_forward(
    x: jnp.ndarray,    # [B, S, H, P] (pre-multiplied by dt)
    da: jnp.ndarray,   # [B, S, H]
    b: jnp.ndarray,    # [B, S, G, N]
    c: jnp.ndarray,    # [B, S, G, N]
    chunk: int,
    mode: Mode | None = None,
    h0: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full SSD using the Pallas chunk kernels + jnp inter-chunk scan.

    Drop-in equivalent of ``repro.models.ssm.ssd_chunked`` (the oracle).
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    mode = mode or platform_mode()
    bsz, s, h, p = x.shape
    g_, n = b.shape[2], b.shape[3]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} must divide chunk {q} on the kernel path")
    nc = s // q
    rep = h // g_
    # head-expand + flatten to per-(b,h,chunk) cells
    bh = jnp.repeat(b, rep, axis=2)
    ch = jnp.repeat(c, rep, axis=2)

    def cells(t, feat):  # [B,S,H,F] -> [B*H*nc, Q, F]
        t = t.reshape(bsz, nc, q, h, feat).transpose(0, 3, 1, 2, 4)
        return t.reshape(bsz * h * nc, q, feat)

    xc = cells(x, p)
    bc = cells(bh, n)
    cc = cells(ch, n)
    dac = da.reshape(bsz, nc, q, h).transpose(0, 3, 1, 2).reshape(bsz * h * nc, q)

    if mode == "off":
        y, st, dec, cum = _ref.ref_ssd_chunk(xc, dac, bc, cc)
    else:
        y, st, dec, cum = ssd_chunk_pallas(
            xc, dac, bc, cc, interpret=(mode == "interpret")
        )

    # inter-chunk recurrence (sequential over nc — stays in jnp)
    st_r = st.reshape(bsz * h, nc, p, n)
    dec_r = dec.reshape(bsz * h, nc)
    init = (
        jnp.zeros((bsz * h, p, n), jnp.float32)
        if h0 is None
        else h0.reshape(bsz * h, p, n).astype(jnp.float32)
    )

    def step(carry, inp):
        s_c, d_c = inp
        return carry * d_c[:, None, None] + s_c, carry

    final, states_in = jax.lax.scan(
        step, init, (st_r.transpose(1, 0, 2, 3), dec_r.T)
    )
    states_in = states_in.transpose(1, 0, 2, 3).reshape(bsz * h * nc, p, n)

    if mode == "off":
        y_off = _ref.ref_ssd_combine(cc, cum, states_in)
    else:
        y_off = ssd_combine_pallas(cc, cum, states_in, interpret=(mode == "interpret"))

    y_tot = (y + y_off).reshape(bsz, h, nc, q, p).transpose(0, 2, 3, 1, 4)
    y_tot = y_tot.reshape(bsz, s, h, p).astype(x.dtype)
    return y_tot, final.reshape(bsz, h, p, n)
