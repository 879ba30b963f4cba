"""repro — BPCC coded-computing reproduction (see ROADMAP.md, DESIGN.md).

Importing the package pins ``jax_threefry_partitionable`` on so that every
``jax.random`` draw is *sharding-invariant*: a parameter initialized under a
2x2 mesh is bit-identical to the single-device init (required by the elastic
resharding path and asserted in tests/test_multidevice.py).  It is the
default in the installed JAX; the pin keeps the guarantee explicit.

It also places JAX's persistent compilation cache, in this one place: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing here overrides
it; otherwise the cache lives at ``.jax_cache/`` in the checkout.  The path
is part of the cache key, so it is fixed: a warm cache from one run serves
the next (a 32-layer decode step otherwise compiles from scratch each run).
The cache key includes the programs' metadata: named scopes
(``coded_head``, ``kv_write``) live only there, and without it a program
compiled before a scope was added or renamed is loaded with the old
``op_name``s, which ``ServeEngine.op_scopes()`` and a profile then show.
"""
import os as _os
from pathlib import Path as _Path

import jax as _jax

_jax.config.update("jax_threefry_partitionable", True)
_jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        str(_Path(__file__).resolve().parents[2] / ".jax_cache"),
    )
