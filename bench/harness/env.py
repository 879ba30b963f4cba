"""Process set-up shared by the entry points: the compile cache, the
import paths, and the look for the chip."""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def init() -> None:
    """Before anything touches a device: the persistent compile cache at a
    fixed path in the checkout (unless ``JAX_COMPILATION_CACHE_DIR`` names
    one) holding every program however fast it compiled, the system under
    test on the path, and partitionable threefry keys, which the
    reference's draws assume."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    for p in (ROOT / "bench", ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_threefry_partitionable", True)
    import repro  # noqa: F401  (the system under test; absent, the run fails here)


def chip_error(chips: int) -> str | None:
    """Why this process cannot run a cell of ``chips`` chips, or None."""
    import jax

    from . import cost

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return (f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                f"{devs[0].platform} device(s)")
    try:
        cost.peaks(devs[0].device_kind)
    except ValueError as e:
        return str(e)
    return None
