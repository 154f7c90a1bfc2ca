"""The window module's contract (``repro.kernels.window``): the tile and
block geometry both transcode kernels read, the launch limits, the
chunked output and the windowed store, and the single-document wrapper
helpers."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import result as R
from repro.kernels import runtime, stages, window

BLOCK, ROWS, LANES = window.BLOCK, window.ROWS, window.LANES
INT32_MAX = (1 << 31) - 1


# ---------------------------------------------------------------------------
# Chunked output: shape and scratch of a windowed writer.


@pytest.mark.parametrize("g", [1, 8, 32])
@pytest.mark.parametrize("src,dst", [("utf16", "utf8"), ("utf8", "utf16"),
                                     ("utf8", "utf32")],
                         ids=["uint8", "uint16", "uint32"])
def test_window_out_shape_and_scratch(src, dst, g):
    codec_s, codec_d, _f = stages.get_pair(src, dst)
    width = stages.stage_width(codec_s, codec_d)
    nblk = 4 * g
    spec, shape, (buf, pend, sem) = window.window_out(nblk, width,
                                                      codec_d.dtype, g)
    assert spec.memory_space == pl.ANY
    assert shape.dtype == codec_d.dtype
    nchunk = shape.shape[0]
    assert shape.shape == (nchunk, ROWS, LANES)
    # Every unit the launch can write, from offset 0.
    assert nchunk * BLOCK >= nblk * width
    # One step's g windows, starting anywhere inside a chunk, fit the
    # buffer, and the buffer is no larger than that.
    k = buf.shape[0]
    assert buf.shape == (k, ROWS, LANES) and buf.dtype == codec_d.dtype
    assert k * BLOCK >= g * width + BLOCK - 1 > (k - 1) * BLOCK
    # The last step's copy stays inside the output.
    assert (nblk - g) * width // BLOCK + k <= nchunk
    assert pend.shape == (ROWS, LANES) and pend.dtype == codec_d.dtype
    assert buf.memory_space == pend.memory_space == pltpu.VMEM
    assert sem.memory_space == pltpu.SEMAPHORE


# ---------------------------------------------------------------------------
# Launch limits.


@pytest.mark.parametrize("per_tile_arrays", [0, 3, 6])
def test_check_launch_smem_budget_edge(per_tile_arrays):
    limit = INT32_MAX // BLOCK
    if per_tile_arrays:
        limit = window.SMEM_BUDGET // (4 * per_tile_arrays)
        assert 4 * per_tile_arrays * limit <= window.SMEM_BUDGET \
            < 4 * per_tile_arrays * (limit + 1)
    window.check_launch(limit, 1, per_tile_arrays, False, "probe")
    with pytest.raises(runtime.LaunchTooLarge,
                       match=f"probe: {limit + 1} tiles .* at most "
                             f"{limit} tiles"):
        window.check_launch(limit + 1, 1, per_tile_arrays, False, "probe")


@pytest.mark.parametrize("factor", [1, 2, 3, 4])
def test_check_launch_int32_index_edge(factor):
    # The interpreter has no SMEM budget: only int32 offsets bound it.
    limit = INT32_MAX // (factor * BLOCK)
    assert limit * factor * BLOCK <= INT32_MAX \
        < (limit + 1) * factor * BLOCK
    window.check_launch(limit, factor, 6, True, "probe")
    with pytest.raises(runtime.LaunchTooLarge, match=f"at most {limit} "):
        window.check_launch(limit + 1, factor, 6, True, "probe")


# ---------------------------------------------------------------------------
# The windowed store, in the interpreter: two grid steps of g windows
# each, the second at the unaligned offset where the first ended.

STEPS = 2
WIDTH = 2 * BLOCK


def _store_kernel(tot_ref, stage_ref, out_ref, buf, pend, sem, carry):
    i = pl.program_id(0)
    g = stage_ref.shape[0]

    @pl.when(i == 0)
    def _():
        carry[0] = 0

    base = carry[0]
    tot = tuple(tot_ref[i * g + j] for j in range(g))
    if g == 1:
        window.store_window(stage_ref[0], base, tot[0], out_ref, buf, pend,
                            sem)
    else:
        window.store_window(stage_ref[...], base, tot, out_ref, buf, pend,
                            sem)
    carry[0] = base + sum(tot)


@pytest.mark.parametrize("g", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint16])
def test_store_window_places_windows_and_zero_rest(dtype, g):
    rng = np.random.default_rng(g)
    nblk = STEPS * g
    tots = rng.integers(0, WIDTH + 1, nblk)
    tots[g - 1] = WIDTH - 300              # the first step ends unaligned
    assert sum(tots[:g]) % BLOCK
    stage = np.zeros((nblk, WIDTH), np.int32)
    want = []
    for t, n in enumerate(tots):
        units = 1 + (np.arange(n) * 31 + t * 17) % 250
        stage[t, :n] = units
        want.append(units)
    want = np.concatenate(want)
    spec, shape, scratch = window.window_out(nblk, WIDTH, dtype, g)
    out = pl.pallas_call(
        _store_kernel,
        grid=(STEPS,),
        in_specs=[window.SMEM,
                  pl.BlockSpec((g, WIDTH // LANES, LANES),
                               lambda i: (i, 0, 0))],
        out_specs=spec,
        out_shape=shape,
        scratch_shapes=scratch + [pltpu.SMEM((1,), jnp.int32)],
        compiler_params=window.SEQUENTIAL,
        interpret=True,
    )(jnp.asarray(tots, jnp.int32),
      jnp.asarray(stage.reshape(nblk, WIDTH // LANES, LANES)))
    out = np.asarray(out).reshape(-1)
    assert out.dtype == np.dtype(dtype)
    assert np.array_equal(out[: len(want)], want)
    # The last copy wrote whole chunks from the last step's base; past
    # the last unit they hold zeros.
    last_base = int(sum(tots[:g]))
    end = (last_base // BLOCK + scratch[0].shape[0]) * BLOCK
    assert not out[len(want): end].any()


# ---------------------------------------------------------------------------
# Block geometry: each step's tiles, their neighbours and lane indices.


def _geometry_kernel(xp_ref, x_ref, xn_ref, x_out, xp_out, xn_out, gi_out):
    x, xp, xn = window.load_block(xp_ref, x_ref, xn_ref)
    gidx = window.gidx(x.shape)
    if x.ndim == 2:
        x, xp, xn, gidx = x[None], xp[None], xn[None], gidx[None]
    x_out[...], xp_out[...], xn_out[...], gi_out[...] = x, xp, xn, gidx


@pytest.mark.parametrize("g", [1, 2, 4])
def test_block_specs_give_each_tile_its_neighbours(g):
    ntiles = 3 * g
    n = ntiles * BLOCK - 5
    src = (1 + np.arange(n) % 251).astype(np.uint8)
    assert window.tiles_per_step(n, g) == g
    x3, nblk = runtime.tile_with_boundaries(jnp.asarray(src), ROWS, LANES,
                                            block=g)
    assert nblk == ntiles
    blk = pl.BlockSpec((g, ROWS, LANES), lambda i: (i, 0, 0))
    out = jax.ShapeDtypeStruct((nblk, ROWS, LANES), jnp.int32)
    x, xp, xn, gi = (np.asarray(a).reshape(nblk, BLOCK) for a in
                     pl.pallas_call(
                         _geometry_kernel, grid=(nblk // g,),
                         in_specs=window.block_specs(g),
                         out_specs=[blk] * 4, out_shape=[out] * 4,
                         interpret=True)(x3, x3, x3))
    tiles = np.zeros((ntiles + 2, BLOCK), np.int32)   # zero tile each side
    tiles[1:-1].reshape(-1)[:n] = src
    assert np.array_equal(x, tiles[1:-1])
    assert np.array_equal(xp, tiles[:-2])
    assert np.array_equal(xn, tiles[2:])
    assert np.array_equal(gi, np.arange(nblk * BLOCK).reshape(nblk, BLOCK))


# ---------------------------------------------------------------------------
# The single-document wrapper contract.


def test_mask_padding_zeroes_past_n_only_when_masked():
    x = jnp.arange(1, 11, dtype=jnp.int32)
    assert window.mask_padding(x, 4, jnp.uint8, False) is x
    got = window.mask_padding(x, 4, jnp.uint8, True)
    assert got.dtype == jnp.uint8
    assert np.array_equal(np.asarray(got), [1, 2, 3, 4] + [0] * 6)


def test_ascii_copy_result_widens_and_pads_to_capacity():
    x = jnp.asarray(np.frombuffer(b"abcdef", np.uint8))
    res = window.ascii_copy_result(x, 4, 9, jnp.uint16)
    assert res.buffer.dtype == jnp.uint16 and res.buffer.shape == (9,)
    assert np.array_equal(np.asarray(res.buffer),
                          list(b"abcdef") + [0, 0, 0])
    assert int(res.count) == 4 and int(res.status) == R.STATUS_OK


def test_clip_to_cap_keeps_capacity_and_clears_slack():
    out = jnp.arange(1, 13, dtype=jnp.uint16)
    got = window.clip_to_cap(out, 8, 5, jnp.uint16)
    assert got.dtype == jnp.uint16
    assert np.array_equal(np.asarray(got), [1, 2, 3, 4, 5, 0, 0, 0])


def test_status_folds_the_first_error():
    imax = R.NO_ERR_SENTINEL
    clean = jnp.asarray([imax, imax, imax], jnp.int32)
    none = jnp.zeros(3, jnp.int32)
    assert int(window.status(none, clean)) == R.STATUS_OK
    errs = jnp.asarray([0, 1, 1], jnp.int32)
    ferrs = jnp.asarray([imax, 2100, 1030], jnp.int32)
    assert int(window.status(errs, ferrs)) == 1030
