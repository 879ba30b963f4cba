"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached.  Interpret-mode
tests cannot see what the chip's compiler refuses — unaligned blocks,
in-kernel shape casts, VMEM overruns — and these compiles can, at the
widths the serving and SSM paths really run: the phi3-mini coded head
(16 blocks x 2296 rows x 3072, and the ragged 2291 rows of an unaligned
head) and mamba2-130m's SSD chunk (Q=256, P=64, N=128).  The engine's
whole decode step is compiled at both benchmark configurations' widths,
to check that it reads the stored coded head in place.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test runner's
workers import every test module.
"""
import math
import os
import re
from functools import partial

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.coded_ops import block_rows

# phi3-mini-3.8b coded LM head: vocab 32064 over 14 data + 2 parity blocks
N_DATA, N_PARITY, D_MODEL, VOCAB = 14, 2, 3072, 32_064
RAGGED_ROWS = -(-VOCAB // N_DATA)         # 2291: no tile alignment
BLOCK_ROWS = block_rows(VOCAB, N_DATA)    # 2296: the stored float32 head
N_BLOCKS = N_DATA + N_PARITY
HEAD_ROWS = pytest.mark.parametrize("br", [RAGGED_ROWS, BLOCK_ROWS])


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


@HEAD_ROWS
@pytest.mark.parametrize("batch", [1, 8])
def test_coded_matvec_compiles_at_head_width(one_chip, batch, br):
    from repro.kernels.ops import coded_matvec

    _compile_kernel(
        partial(coded_matvec, mode="compile"),
        _sds((N_BLOCKS * br, D_MODEL), jnp.float32, one_chip),
        _sds((D_MODEL, batch), jnp.float32, one_chip),
    )


@HEAD_ROWS
@pytest.mark.parametrize("batch", [1, 8])
def test_coded_matvec_decode_compiles_at_head_width(one_chip, batch, br):
    from repro.kernels.ops import coded_matvec_decode

    _compile_kernel(
        partial(coded_matvec_decode, mode="compile"),
        _sds((N_BLOCKS * br, D_MODEL), jnp.float32, one_chip),
        _sds((D_MODEL, batch), jnp.float32, one_chip),
        _sds((N_DATA, N_BLOCKS), jnp.float32, one_chip),
    )


@HEAD_ROWS
def test_gaussian_encode_compiles_at_head_reencode_width(one_chip, br):
    """The parity top-up re-encode: a 17x14 generator over the 14 data
    blocks of the head, each flattened to br*3072."""
    from repro.kernels.ops import gaussian_encode

    _compile_kernel(
        partial(gaussian_encode, mode="compile"),
        _sds((N_BLOCKS + 1, N_DATA), jnp.float32, one_chip),
        _sds((N_DATA, br * D_MODEL), jnp.float32, one_chip),
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


# The benchmark's two configurations at the engine's sizes: the last stage
# of a 4-stage glm4-9b (10 layers, 32 slots x 2048) and phi3-mini (8 x 512)
_BODY = dict(family="dense", mlp="swiglu", param_dtype="bfloat16",
             dtype="bfloat16", coded=True, coded_parity=N_PARITY)
DECODE_CELLS = {
    "glm4-9b-pp4-last": (dict(n_layers=10, d_model=4096, n_heads=32,
                              n_kv_heads=2, head_dim=128, d_ff=13696,
                              vocab=151_552, rope_theta=5e5), 32, 2048),
    "phi3-mini-3.8b": (dict(n_layers=32, d_model=3072, n_heads=32,
                            n_kv_heads=32, head_dim=96, d_ff=8192,
                            vocab=VOCAB, rope_theta=1e4), 8, 512),
}
# an instruction's result dims and opcode: `%name = f32[a,b]{layout} op(`
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\w+\[([0-9,]*)\]\S*\s+"
                    r"([\w\-]+)\(")


@pytest.mark.parametrize("name", list(DECODE_CELLS))
def test_decode_step_reads_coded_head_in_place(one_chip, name):
    """The engine's decode step (``decode_step`` then argmax) views the
    stored ``[16 * br, d]`` head as ``[16, br, d]`` blocks.  With ``br``
    off the float32 (8, 128) tile that view is a relayout of the whole
    head on every step (2.84 GB at glm4-9b's 151552-row vocabulary); with
    tile-aligned blocks it is a bitcast, and the step's temporaries stay
    far below the head's size.  A relayout is a reshape, copy or transpose
    of the head's size, in the step or in any fusion's body."""
    from repro.models.config import ModelConfig
    from repro.models.registry import build_model

    widths, n_slots, s_max = DECODE_CELLS[name]
    model = build_model(ModelConfig(name=name, **widths, **_BODY))

    def place(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = place(model.param_shapes())
    head = params["lm_head_coded"]
    assert head.dtype == jnp.float32
    assert head.shape[0] == N_BLOCKS * block_rows(widths["vocab"], N_DATA)

    def decode_argmax(params, cache, last_tok, mask):
        logits, cache = model.decode_step(params, cache, last_tok, mask)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    compiled = jax.jit(decode_argmax).lower(
        params, place(model.cache_shapes(n_slots, s_max)),
        _sds((n_slots,), jnp.int32, one_chip),
        _sds((N_BLOCKS,), jnp.float32, one_chip),
    ).compile()
    head_elems = head.shape[0] * head.shape[1]
    copies = []
    for line in compiled.as_text().splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(2) not in ("reshape", "copy", "transpose"):
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if dims and math.prod(dims) == head_elems:
            copies.append(line.strip()[:160])
    assert not copies, f"the step rewrites the coded head: {copies}"
    temp, head_bytes = compiled.memory_analysis().temp_size_in_bytes, head_elems * 4
    assert temp < head_bytes / 4, (temp, head_bytes)


# an instruction's name, result dims, opcode and operands:
# `%name = bf16[a,b]{layout} op(%x, %y), ...`
_INSTR_ARGS = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([0-9,]*)\]\S*\s+"
                         r"([\w\-]+)\(([^)]*)\)")
# the KV writes: the model's kv_write scope, or a layer scan's stacking of
# its per-layer outputs
_WRITE_SIDE = re.compile(r'op_name="[^"]*(?:/kv_write/|while/body/dynamic_update_slice)')
_ALIASED = re.compile(r"\{[0-9, ]*\}:\s*\((\d+),")


@pytest.mark.parametrize("masked", [False, True], ids=["no-mask", "mask"])
@pytest.mark.parametrize("name", list(DECODE_CELLS))
def test_decode_step_writes_kv_cache_in_place(one_chip, name, masked):
    """The engine's decode step with its cache donated stores each slot's
    new key/value row and moves no whole cache to do it: the module
    aliases every cache leaf from input to output; no select, copy or
    dynamic-update-slice on the write side (the ``kv_write`` scope or the
    layer scan's stacking of its outputs), in the step or in a fusion
    body, writes as many elements as one layer's or the whole stack's K/V
    cache (a dynamic-update-slice counts its update, since in place it
    moves only that); and the step's temporaries stay far below one
    stacked cache, so no hidden relayout of it is left."""
    from repro.models.config import ModelConfig
    from repro.models.registry import build_model

    widths, n_slots, s_max = DECODE_CELLS[name]
    model = build_model(ModelConfig(name=name, **widths, **_BODY))

    def place(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = place(model.param_shapes())
    cache = place(model.cache_shapes(n_slots, s_max))
    mask = _sds((N_BLOCKS,), jnp.float32, one_chip) if masked else None

    def decode_argmax(params, cache, last_tok, mask):
        logits, cache = model.decode_step(params, cache, last_tok, mask)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    compiled = jax.jit(decode_argmax, donate_argnums=(1,), keep_unused=True).lower(
        params, cache, _sds((n_slots,), jnp.int32, one_chip), mask).compile()
    text = compiled.as_text()

    first = len(jax.tree.leaves(params))
    cache_params = set(range(first, first + len(jax.tree.leaves(cache))))
    header = next(line for line in text.splitlines() if line.startswith("HloModule"))
    aliased = {int(p) for p in _ALIASED.findall(header)}
    assert cache_params <= aliased, (sorted(cache_params - aliased), header[:300])

    stacked = cache["blocks"]["attn_0"]["k"]
    layer = math.prod(stacked.shape[1:])
    whole = {layer, layer * stacked.shape[0]}
    dims: dict[str, int] = {}
    writes = []
    for line in text.splitlines():
        m = _INSTR_ARGS.match(line)
        if m is None:
            continue
        iname, shape, op, args = m.groups()
        dims[iname] = math.prod(int(d) for d in shape.split(",") if d)
        if op not in ("select", "copy", "dynamic-update-slice") or not _WRITE_SIDE.search(line):
            continue
        moved = dims[iname]
        if op == "dynamic-update-slice":  # in place it moves its update only
            update = args.split(",")[1].strip().lstrip("%")
            moved = dims.get(update, moved)
        if moved in whole:
            writes.append(line.strip()[:200])
    assert not writes, f"the step rewrites a whole K/V cache: {writes}"
    cache_bytes = stacked.dtype.itemsize * math.prod(stacked.shape)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 8, (temp, cache_bytes)
