"""The main-path kernels compile for a TPU v5e chip.

Each test lowers one kernel wrapper with ``interpret=False`` (Mosaic) at
real widths -- a ``1 << 17``-character document, the bulk benchmark
cell's 4 MiB call, the 64-document packed batch, or the ingest cell's
1,024-tile batch -- and compiles it
for a described, not attached, ``v5e`` chip.  What the chip's compiler
refuses (unlowerable primitives, block shapes, more SMEM than the chip
has) fails here without a chip.  The compiled HLO must hold the Mosaic
kernel (``tpu_custom_call``).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU compiler library,
and every test worker imports this file.
"""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import onepass_transcode as op
from repro.kernels import ragged_transcode as rt
from repro.kernels import runtime
from repro.kernels import window

N_CHARS = 1 << 17      # chip_smoke.py N_CHARS
N_DOCS = 64            # chip_smoke.py packed batch
DTYPE = {"utf8": jnp.uint8, "utf16": jnp.uint16}
# Worst-case stored units for N_CHARS characters (4-byte UTF-8, 2-unit
# UTF-16): the shapes the entry points see at this document size.
UNITS = {"utf8": 4 * N_CHARS, "utf16": 2 * N_CHARS}
PAIRS = [("utf8", "utf16"), ("utf16", "utf8")]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _doc(fmt, sharding):
    return jax.ShapeDtypeStruct((UNITS[fmt],), DTYPE[fmt], sharding=sharding)


def _batch(fmt, sharding):
    return (_doc(fmt, sharding),
            jax.ShapeDtypeStruct((N_DOCS + 1,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((N_DOCS,), jnp.int32, sharding=sharding))


@pytest.mark.parametrize("errors", ["strict", "replace"])
@pytest.mark.parametrize("src,dst", PAIRS)
def test_onepass_compiles_for_v5e(one_chip, src, dst, errors):
    text = _compile_text(
        lambda x: op.transcode_onepass(x, src=src, dst=dst, errors=errors,
                                       interpret=False),
        _doc(src, one_chip))
    assert "tpu_custom_call" in text


def test_onepass_bulk_cell_shape_compiles_for_v5e(one_chip):
    """The bulk benchmark cell's call: 4 MiB of UTF-8 to UTF-16, strict,
    validated, which runs blocks of ``G_MAX`` tiles per grid step: the
    block's vregs, VMEM buffers and SMEM carry fit the chip."""
    n = 4 << 20
    assert window.tiles_per_step(n, op.G_MAX) == op.G_MAX
    text = _compile_text(
        lambda x: op.transcode_onepass(x, src="utf8", dst="utf16",
                                       validate=True, errors="strict",
                                       interpret=False),
        jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_onepass_utf16_bulk_cell_shape_compiles_for_v5e(one_chip):
    """The UTF-16 bulk benchmark cell's call: 2^21 units of UTF-16 to
    UTF-8, strict, validated, in blocks of ``G_MAX`` tiles: the uint8
    windows of three bytes per unit, with their block buffer, fit the
    chip."""
    n = 2 << 20
    assert window.tiles_per_step(n, op.G_MAX) == op.G_MAX
    text = _compile_text(
        lambda x: op.transcode_onepass(x, src="utf16", dst="utf8",
                                       validate=True, errors="strict",
                                       interpret=False),
        jax.ShapeDtypeStruct((n,), jnp.uint16, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("src,dst", PAIRS)
def test_scan_compiles_for_v5e(one_chip, src, dst):
    text = _compile_text(
        lambda x: op.scan_onepass(x, src=src, dst=dst, interpret=False),
        _doc(src, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("errors", ["strict", "replace"])
@pytest.mark.parametrize("src,dst", PAIRS)
def test_ragged_onepass_compiles_for_v5e(one_chip, src, dst, errors):
    text = _compile_text(
        lambda d, o, n: rt.transcode_ragged(d, o, n, src=src, dst=dst,
                                            errors=errors, interpret=False),
        *_batch(src, one_chip))
    assert "tpu_custom_call" in text


def test_ragged_ingest_cell_shape_compiles_for_v5e(one_chip):
    """The ingest benchmark cell's batch: 1,024 tiles of UTF-8 padded to
    one document per tile, to UTF-16 with ``errors="replace"``, which
    runs blocks of ``RAGGED_G_MAX`` tiles per grid step, each tile with
    its own document: the block's vregs, VMEM buffers and six per-tile
    SMEM vectors fit the chip."""
    n, docs = 1 << 20, 1024
    assert window.tiles_per_step(n, rt.RAGGED_G_MAX) == rt.RAGGED_G_MAX
    text = _compile_text(
        lambda d, o, l: rt.transcode_ragged(d, o, l, src="utf8",
                                            dst="utf16", validate=True,
                                            errors="replace",
                                            interpret=False),
        jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((docs + 1,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((docs,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("src,dst", PAIRS)
def test_ragged_scan_compiles_for_v5e(one_chip, src, dst):
    text = _compile_text(
        lambda d, o, n: rt.scan_ragged(d, o, n, src=src, dst=dst,
                                       interpret=False),
        *_batch(src, one_chip))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# Launch-size limits: the largest input one launch takes compiles, and one
# tile more is refused before compiling, with the limit in the message.

BLOCK = window.BLOCK
# Ragged launches keep six int32 vectors per tile in SMEM.
RAGGED_MAX_TILES = window.SMEM_BUDGET // (4 * 6)


def _packed(n, sharding=None):
    return (jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=sharding),
            jax.ShapeDtypeStruct((N_DOCS + 1,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((N_DOCS,), jnp.int32, sharding=sharding))


def _ragged(d, o, n):
    return rt.transcode_ragged(d, o, n, src="utf8", dst="utf16",
                               interpret=False)


def test_largest_packed_batch_compiles_for_v5e(one_chip):
    text = _compile_text(_ragged, *_packed(RAGGED_MAX_TILES * BLOCK,
                                           one_chip))
    assert "tpu_custom_call" in text


def test_packed_batch_past_the_smem_limit_is_refused():
    with pytest.raises(runtime.LaunchTooLarge,
                       match=f"at most {RAGGED_MAX_TILES} tiles"):
        jax.eval_shape(_ragged, *_packed((RAGGED_MAX_TILES + 1) * BLOCK))


def test_document_past_the_int32_offset_limit_is_refused():
    # UTF-16 -> UTF-8 emits up to 3 bytes per unit: int32 offsets bound
    # a document to (2**31 - 1) // (3 * BLOCK) tiles.
    limit = ((1 << 31) - 1) // (3 * BLOCK)
    x = jax.ShapeDtypeStruct(((limit + 1) * BLOCK,), jnp.uint16)
    with pytest.raises(runtime.LaunchTooLarge, match=f"at most {limit} "):
        jax.eval_shape(lambda x: op.transcode_onepass(
            x, src="utf16", dst="utf8", interpret=False), x)
