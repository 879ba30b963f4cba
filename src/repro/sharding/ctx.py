"""Activation-sharding hints as a context, keeping model code mesh-agnostic.

Model code calls ``shard_hint(x, "act_btd")``; the launcher installs a dict
of logical-name -> PartitionSpec via ``sharding_hints(...)``.  Outside the
context (unit tests, single-device smoke runs) every hint is a no-op, so
the same model code runs anywhere.
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar

import jax

_HINTS: ContextVar[dict | None] = ContextVar("sharding_hints", default=None)

# (mesh, axis) the coded LM head should shard_map over — installed by the
# serving engine around its jitted step traces (DESIGN.md §10).  Unset, the
# head runs the single-program CodedLinear path; model code stays
# mesh-agnostic either way.
_CODED_HEAD: ContextVar[tuple | None] = ContextVar("coded_head_mesh", default=None)

# kernel mode for the coded LM-head matvec — same threading pattern as the
# mesh: the engine installs it around its jitted step traces, the model
# reads it at trace time (DESIGN.md §11).  'auto' turns on table-driven
# dispatch; None keeps the default cached path.
_HEAD_KMODE: ContextVar[str | None] = ContextVar("head_kernel_mode", default=None)

# fused macro-step length K — installed by the serving engine around its
# K-step block traces (DESIGN.md §14).  'auto' kernel dispatch reads it to
# amortize the per-call dispatch floor over the K fused iterations when
# ranking candidate implementations; 1 (the default) is the scalar step.
_MACRO_K: ContextVar[int] = ContextVar("macro_step_k", default=1)


def current_hints() -> dict | None:
    return _HINTS.get()


@contextlib.contextmanager
def sharding_hints(hints: dict):
    token = _HINTS.set(hints)
    try:
        yield
    finally:
        _HINTS.reset(token)


def current_coded_head_mesh() -> tuple | None:
    """(mesh, axis_name) for the mesh-sharded coded head, or None."""
    return _CODED_HEAD.get()


@contextlib.contextmanager
def coded_head_mesh(mesh, axis: str = "model"):
    """Route the coded LM-head matvec through ``shard_map`` over ``mesh``:
    one code block per device along ``axis``, erasure = dropping a device's
    output, decode via the mask-keyed DecoderCache (replicated).  A None
    mesh is a no-op, so callers can thread an optional mesh straight in."""
    if mesh is None:
        yield
        return
    token = _CODED_HEAD.set((mesh, axis))
    try:
        yield
    finally:
        _CODED_HEAD.reset(token)


def current_head_kernel_mode() -> str | None:
    """Kernel mode for the coded LM-head matvec, or None (default path)."""
    return _HEAD_KMODE.get()


@contextlib.contextmanager
def head_kernel_mode(mode: str | None):
    """Route the coded LM-head matvec through ``kernel_mode=mode`` —
    ``'auto'`` for autotuned per-shape dispatch (DESIGN.md §11), an explicit
    kernel mode to pin an implementation.  None is a no-op, so callers can
    thread an optional mode straight in."""
    if mode is None:
        yield
        return
    token = _HEAD_KMODE.set(mode)
    try:
        yield
    finally:
        _HEAD_KMODE.reset(token)


def current_macro_step_k() -> int:
    """Fused macro-step length for the trace being built (1 = scalar)."""
    return _MACRO_K.get()


@contextlib.contextmanager
def macro_step_k(k: int | None):
    """Declare that the enclosed trace decodes ``k`` fused iterations per
    launch, so 'auto' kernel dispatch amortizes its per-call overhead term
    accordingly (DESIGN.md §14).  ``None`` / ``k <= 1`` is a no-op."""
    if k is None or k <= 1:
        yield
        return
    token = _MACRO_K.set(int(k))
    try:
        yield
    finally:
        _MACRO_K.reset(token)


def kv_seq_sharded() -> bool:
    """Is the decode KV cache's sequence axis sharded?  A launcher that
    shards the cache states its sharding as the ``kv_cache`` hint (one
    full-rank ``[..., B, S, KVH, Hd]`` spec); without it (the serving
    engine, unit tests) the cache is whole on each device."""
    hints = _HINTS.get()
    sharding = hints.get("kv_cache") if hints else None
    return sharding is not None and sharding.spec[-3] is not None


def shard_hint(x: jax.Array, name: str) -> jax.Array:
    """Constrain ``x`` to the installed spec for ``name`` (no-op if unset)."""
    hints = _HINTS.get()
    if not hints:
        return x
    spec = hints.get(name)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
