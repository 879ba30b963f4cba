"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached.  Interpret-mode
tests cannot see what the chip's compiler refuses — unaligned blocks,
in-kernel shape casts, VMEM overruns — and these compiles can, at the
widths the serving and SSM paths really run: the phi3-mini coded head
(16 blocks x 2291 rows x 3072) and mamba2-130m's SSD chunk (Q=256, P=64,
N=128).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test runner's
workers import every test module.
"""
import os
from functools import partial

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# phi3-mini-3.8b coded LM head: vocab 32064 over 14 data + 2 parity blocks
N_DATA, N_PARITY, D_MODEL, VOCAB = 14, 2, 3072, 32_064
BLOCK_ROWS = -(-VOCAB // N_DATA)          # 2291
N_BLOCKS = N_DATA + N_PARITY


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the HLO"
    return compiled


@pytest.mark.parametrize("batch", [1, 8])
def test_coded_matvec_compiles_at_head_width(one_chip, batch):
    from repro.kernels.ops import coded_matvec

    _compile_kernel(
        partial(coded_matvec, mode="compile"),
        _sds((N_BLOCKS * BLOCK_ROWS, D_MODEL), jnp.float32, one_chip),
        _sds((D_MODEL, batch), jnp.float32, one_chip),
    )


@pytest.mark.parametrize("batch", [1, 8])
def test_coded_matvec_decode_compiles_at_head_width(one_chip, batch):
    from repro.kernels.ops import coded_matvec_decode

    _compile_kernel(
        partial(coded_matvec_decode, mode="compile"),
        _sds((N_BLOCKS * BLOCK_ROWS, D_MODEL), jnp.float32, one_chip),
        _sds((D_MODEL, batch), jnp.float32, one_chip),
        _sds((N_DATA, N_BLOCKS), jnp.float32, one_chip),
    )


def test_gaussian_encode_compiles_at_head_reencode_width(one_chip):
    """The parity top-up re-encode: a 17x14 generator over the 14 data
    blocks of the head, each flattened to 2291*3072."""
    from repro.kernels.ops import gaussian_encode

    _compile_kernel(
        partial(gaussian_encode, mode="compile"),
        _sds((N_BLOCKS + 1, N_DATA), jnp.float32, one_chip),
        _sds((N_DATA, BLOCK_ROWS * D_MODEL), jnp.float32, one_chip),
    )


def test_lt_encode_compiles(one_chip):
    """An LT reserve slice: 640 coded rows of degree <= 16 over a 512-row,
    3072-wide source."""
    from repro.kernels.ops import lt_encode

    _compile_kernel(
        partial(lt_encode, mode="compile"),
        _sds((512, D_MODEL), jnp.float32, one_chip),
        _sds((640, 16), jnp.int32, one_chip),
        _sds((640, 16), jnp.float32, one_chip),
    )


# mamba2-130m: d_inner 1536 / head_dim 64 = 24 heads; 1024 tokens = 4 chunks
SSD_CELLS, SSD_Q, SSD_P, SSD_N = 24 * 4, 256, 64, 128


def test_ssd_chunk_compiles_at_mamba2_width(one_chip):
    from repro.kernels.ssd_scan import ssd_chunk_pallas

    _compile_kernel(
        partial(ssd_chunk_pallas, interpret=False),
        _sds((SSD_CELLS, SSD_Q, SSD_P), jnp.float32, one_chip),
        _sds((SSD_CELLS, SSD_Q), jnp.float32, one_chip),
        _sds((SSD_CELLS, SSD_Q, SSD_N), jnp.float32, one_chip),
        _sds((SSD_CELLS, SSD_Q, SSD_N), jnp.float32, one_chip),
    )


def test_ssd_combine_compiles_at_mamba2_width(one_chip):
    from repro.kernels.ssd_scan import ssd_combine_pallas

    _compile_kernel(
        partial(ssd_combine_pallas, interpret=False),
        _sds((SSD_CELLS, SSD_Q, SSD_N), jnp.float32, one_chip),
        _sds((SSD_CELLS, SSD_Q), jnp.float32, one_chip),
        _sds((SSD_CELLS, SSD_P, SSD_N), jnp.float32, one_chip),
    )
