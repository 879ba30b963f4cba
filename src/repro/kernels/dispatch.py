"""Table-driven kernel dispatch — the ``kernel_mode="auto"`` seam.

Resolution precedence (DESIGN.md §11):

  1. **explicit mode** — a caller passing ``'interpret'/'compile'/'off'``
     (or ``'svd'``/``None`` at the CodedLinear level) is never overridden;
     ``'auto'`` is the only mode that consults this module;
  2. **dispatch table** — ``reports/bench/autotune.json``, written by
     ``tools/autotune.py``: per (op, shape, dtype, backend) winners, CPU
     rows measured, TPU rows model-derived (``source`` says which);
  3. **analytical fallback** — shapes the table has never seen are priced
     by the calibrated cost model (``repro.kernels.cost``) using the fitted
     hardware constants persisted in the table's meta (or the backend
     preset when no table exists at all).

Resolution happens at TRACE time from static shapes (``a.shape`` under jit
is concrete), so ``'auto'`` works inside jitted serving steps with zero
runtime overhead — the chosen implementation is baked into the compiled
program.  A missing/corrupt table is never an error: ``auto`` degrades to
the analytical model, and the model's candidate set always contains the
pre-autotune default, so behaviour without a table is no worse than before
the autotuner existed.

Test hooks: ``set_table_path(path)`` re-points the singleton (None
restores the default), ``invalidate()`` drops the memoized table.
"""
from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from pathlib import Path

from repro.kernels import cost as _cost

__all__ = [
    "Decision",
    "DispatchTable",
    "default_table_path",
    "get_table",
    "set_table_path",
    "invalidate",
    "choose_coded_linear",
    "choose_matvec",
    "choose_matvec_decode",
    "choose_encode",
]

TABLE_VERSION = 1


def default_table_path() -> str:
    """Committed table location (env ``REPRO_AUTOTUNE_TABLE`` overrides —
    how tests and the CI consistency job point at scratch tables)."""
    env = os.environ.get("REPRO_AUTOTUNE_TABLE")
    if env:
        return env
    return str(Path(__file__).resolve().parents[3]
               / "reports" / "bench" / "autotune.json")


@dataclass(frozen=True)
class Decision:
    """One resolved dispatch choice."""

    op: str
    impl: str                 # 'default' | 'svd' | 'fused' | 'ref' | 'pallas'
    mode: str | None          # kernels.ops mode ('off'/'compile') or None
    params: dict = field(default_factory=dict)   # Pallas tile kwargs
    source: str = "model"     # 'table' | 'model'
    predicted_us: float | None = None

    @property
    def kernel_mode(self) -> str | None:
        """The CodedLinear.apply kernel_mode equivalent of this decision."""
        if self.impl == "default":
            return None
        if self.impl == "svd":
            return "svd"
        return self.mode


def _impl_mode(impl: str, backend: str) -> str | None:
    """kernels.ops mode for an impl choice on a backend: the fused/pallas
    dataflow is the jnp reference ('off') on CPU — interpret mode is an
    interpreter artifact, never a dispatch target — and the compiled kernel
    elsewhere."""
    if impl in ("default", "svd"):
        return None
    if impl == "ref":
        return "off"
    return "off" if backend == "cpu" else "compile"


class DispatchTable:
    """Parsed ``autotune.json``: entry lookup + calibrated hardware."""

    def __init__(self, doc: dict):
        self.doc = doc
        self.entries: dict[tuple, dict] = {}
        for e in doc.get("entries", []):
            key = (e["op"], e["backend"], e["shape"], e.get("dtype", "float32"))
            self.entries[key] = e
        self._hw: dict[str, _cost.HostHardware] = {}
        for backend, hw in doc.get("hardware", {}).items():
            try:
                self._hw[backend] = _cost.HostHardware.from_dict(hw)
            except (KeyError, TypeError):
                pass

    @classmethod
    def load(cls, path: str) -> "DispatchTable | None":
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            return None
        if doc.get("version") != TABLE_VERSION:
            return None
        return cls(doc)

    def hardware(self, backend: str) -> _cost.HostHardware:
        return self._hw.get(backend) or _cost.preset(backend)

    def lookup(self, op: str, backend: str, shape: str,
               dtype: str = "float32", geometry: dict | None = None
               ) -> dict | None:
        e = self.entries.get((op, backend, shape, dtype))
        if e is None:
            return None
        if geometry:
            eg = e.get("geometry", {})
            if any(eg.get(k) != v for k, v in geometry.items()):
                return None
        if e.get("mode") == "interpret":  # never dispatch to the interpreter
            return None
        return e


_lock = threading.Lock()
_table_path: str | None = None
_table: DispatchTable | None = None
_loaded = False


def set_table_path(path: str | None) -> None:
    """Point the singleton at ``path`` (None = back to the default)."""
    global _table_path
    with _lock:
        _table_path = path
    invalidate()


def invalidate() -> None:
    """Drop the memoized table (reloaded lazily on next lookup)."""
    global _table, _loaded
    with _lock:
        _table, _loaded = None, False


def get_table() -> DispatchTable | None:
    global _table, _loaded
    with _lock:
        if not _loaded:
            _table = DispatchTable.load(_table_path or default_table_path())
            _loaded = True
        return _table


def _backend() -> str:
    """The default backend, checked against the hardware presets: on a
    device kind the cost model has no preset for, dispatch is an error."""
    import jax

    backend = jax.default_backend()
    _cost.preset(backend, jax.devices()[0].device_kind)
    return backend


def _resolve(op: str, shape: str, geometry: dict | None,
             dtype: str, backend: str | None, macro_k: int = 1,
             **geom) -> Decision:
    backend = backend or _backend()
    table = get_table()
    if table is not None:
        e = table.lookup(op, backend, shape, dtype, geometry)
        if e is not None:
            # table rows are MEASURED per-call winners; the macro-step
            # amortization only adjusts the analytical fallback below
            # (re-measuring fused-block cells is tools/autotune.py work)
            return Decision(
                op=op, impl=e["impl"],
                mode=e.get("mode") or _impl_mode(e["impl"], backend),
                params=dict(e.get("params", {})), source="table",
                predicted_us=e.get("predicted_us"),
            )
        hw = table.hardware(backend)
    else:
        hw = _cost.preset(backend)
    impl, predicted, params = _cost.predict_best(
        op, backend, hw, macro_k=macro_k, **geom
    )
    return Decision(op=op, impl=impl, mode=_impl_mode(impl, backend),
                    params=params, source="model", predicted_us=predicted)


# --------------------------------------------------------------------------
# per-op choosers (shape-string conventions documented in DESIGN.md §11)
# --------------------------------------------------------------------------
def choose_coded_linear(
    out: int, inner: int, batch: int, n_data: int, n_parity: int,
    dtype: str = "float32", backend: str | None = None, macro_k: int = 1,
) -> Decision:
    """``CodedLinear.apply`` dispatch; shape key ``outxinnerxbatch``.

    Geometries the DecoderCache refuses cannot run the fused kernel (it
    needs the cached recovery matrix) — they stay on the default path,
    whose decode_blocks falls back to SVD internally.  ``macro_k`` is the
    fused macro-step length of the enclosing trace (DESIGN.md §14).
    """
    from repro.core.decoding import cacheable

    if not cacheable(n_data, n_parity):
        return Decision(op="coded_linear", impl="default", mode=None,
                        source="model")
    return _resolve(
        "coded_linear", f"{out}x{inner}x{batch}",
        {"n_data": n_data, "n_parity": n_parity}, dtype, backend,
        macro_k=macro_k,
        out=out, inner=inner, batch=batch, n_data=n_data, n_parity=n_parity,
    )


def choose_matvec(r: int, m: int, b: int, dtype: str = "float32",
                  backend: str | None = None, macro_k: int = 1) -> Decision:
    """``coded_matvec`` dispatch; shape key ``rxmxb``."""
    return _resolve("coded_matvec", f"{r}x{m}x{b}", None, dtype, backend,
                    macro_k=macro_k, r=r, m=m, b=b)


def choose_matvec_decode(
    rows: int, m: int, b: int, n_data: int, n_blocks: int,
    dtype: str = "float32", backend: str | None = None, macro_k: int = 1,
) -> Decision:
    """``coded_matvec_decode`` dispatch; shape key ``rowsxmxb``."""
    return _resolve(
        "coded_matvec_decode", f"{rows}x{m}x{b}",
        {"n_data": n_data, "n_blocks": n_blocks}, dtype, backend,
        macro_k=macro_k,
        rows=rows, m=m, b=b, n_data=n_data, n_blocks=n_blocks,
    )


def choose_encode(kind: str, q: int, r: int, m: int, d_max: int = 0,
                  dtype: str = "float32", backend: str | None = None,
                  ) -> Decision:
    """Encode-kernel dispatch (``gaussian_encode``/``lt_encode``);
    shape key ``qxrxm``."""
    op = f"{kind}_encode"
    return _resolve(op, f"{q}x{r}x{m}", None, dtype, backend,
                    q=q, r=r, m=m, d_max=d_max)
