"""Tile geometry and the compact-window store that the transcode kernels
share: the single-stream kernel (``repro.kernels.onepass_transcode``) and
the ragged packed-batch kernel (``repro.kernels.ragged_transcode``).

What lives here is everything both launch geometries must agree on, so
that neither can drift from the other:

  tiles       the ``(ROWS, LANES)`` VMEM tile, its global lane index,
              the prev/cur/next BlockSpecs over the boundary-tiled input
              and, for a grid step that handles a block of G tiles, the
              block's BlockSpecs and its neighbour tiles;
  launches    SMEM operands, the sequential grid, and the limits one
              launch can take exactly (``check_launch``);
  windows     the chunked HBM output and the windowed writer that stores
              a step's compact units at a data-dependent offset
              (``window_out`` / ``store_window``);
  contract    the single-document wrapper's padding mask, whole-buffer
              ASCII copy, drop-at-capacity clip and status fold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import result as R
from repro.kernels import runtime
from repro.kernels.stages import common
from repro.kernels.stages import driver as sdrv

ROWS = sdrv.ROWS
LANES = sdrv.LANES
BLOCK = sdrv.BLOCK

_IMAX = R.NO_ERR_SENTINEL


def tile(x):
    """Pad flat narrow array to whole tiles + one zero boundary tile/side."""
    return runtime.tile_with_boundaries(x, ROWS, LANES, boundary_tiles=2)


def gidx(shape):
    """Global stream index of every lane of the current ``(ROWS, LANES)``
    tile, or of the current ``(G, ROWS, LANES)`` block of tiles."""
    i = pl.program_id(0)
    row = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 1)
    pos = row * LANES + lane
    if len(shape) == 2:
        return i * BLOCK + pos
    step = i * (shape[0] * BLOCK)
    return jnp.stack([step + g * BLOCK + pos for g in range(shape[0])])


# Shared BlockSpecs: one definition of the tile geometry / neighbour-tile
# offset convention for every counting launch, single-stream and ragged.
def tile_spec(off):
    """Current/prev/next tile of the (nblk+2, ROWS, LANES) padded array."""
    return pl.BlockSpec((1, ROWS, LANES), lambda i, off=off: (i + off, 0, 0))


def load(ref):
    """A tile block as the ``(ROWS, LANES)`` int32 lanes the stages read."""
    return ref[0].astype(jnp.int32)


def tiles_per_step(n: int, cap: int) -> int:
    """Tiles per grid step for an ``n``-element input: the largest power
    of two up to ``cap`` that does not exceed its tile count, so a
    short input is never padded to a whole block."""
    nblk = max(1, -(-n // BLOCK))
    return min(cap, 1 << (nblk.bit_length() - 1))


def block_specs(g: int):
    """``(prev, cur, next)`` BlockSpecs over the block-aligned tiling of
    ``runtime.tile_with_boundaries(..., block=g)``: the block of ``g``
    tiles, and the single tiles just before and just after it."""
    one = (1, ROWS, LANES)
    return [pl.BlockSpec(one, lambda i: ((i + 1) * g - 1, 0, 0)),
            pl.BlockSpec((g, ROWS, LANES), lambda i: (i + 1, 0, 0)),
            pl.BlockSpec(one, lambda i: ((i + 2) * g, 0, 0))]


def load_block(xp_ref, x_ref, xn_ref):
    """``(x, xp, xn)`` int32 lanes of the step: one tile as
    :func:`load` reads it, or a ``(G, ROWS, LANES)`` block with each
    tile's neighbours built from the block and the tiles around it."""
    if x_ref.shape[0] == 1:
        return load(x_ref), load(xp_ref), load(xn_ref)
    x = x_ref[...].astype(jnp.int32)
    xp = jnp.concatenate([xp_ref[...].astype(jnp.int32), x[:-1]])
    xn = jnp.concatenate([x[1:], xn_ref[...].astype(jnp.int32)])
    return x, xp, xn


# Scalars and per-tile vectors live whole in SMEM and are indexed by grid
# step: a (1,)-element VMEM block per tile does not lower, and SMEM is
# where the scalar core reads offsets and writes counts anyway.
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

# Every grid here runs in order on one core: the one-pass kernels carry
# the running output offset across steps, and every writer carries the
# partially filled output chunk (``store_window``).  DESIGN.md §9.
SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

# SMEM holds 1 MiB on the chips this targets; the per-tile int32 vectors
# of one launch must fit beside the compiler's own scalars.
SMEM_BUDGET = (1 << 20) - (64 << 10)

# Counts, statuses and output offsets are int32.
_INDEX_LIMIT = (1 << 31) - 1


def check_launch(nblk: int, factor: int, per_tile_arrays: int,
                 interpret: bool, what: str):
    """Refuse, with the limit in the message, a launch that cannot run
    exactly: one whose output capacity (``factor`` units per input
    element) overflows the int32 offsets, or whose per-tile SMEM vectors
    do not fit the chip (Mosaic only).  Larger inputs would otherwise
    fail inside the compiler or wrap silently."""
    limit = _INDEX_LIMIT // (factor * BLOCK)
    if not interpret and per_tile_arrays:
        limit = min(limit, SMEM_BUDGET // (4 * per_tile_arrays))
    if nblk > limit:
        raise runtime.LaunchTooLarge(
            f"{what}: {nblk} tiles of {BLOCK} elements; one launch takes "
            f"at most {limit} tiles ({limit * BLOCK} elements).  Split "
            f"the input.")


# ---------------------------------------------------------------------------
# Window store.  A tile's compact units start at a data-dependent offset
# of the output, which no vector store or DMA can address directly.  The
# output therefore lives in HBM as whole ``(ROWS, LANES)`` chunks, and each
# writer step
#   1. rotates its compact window by ``base % BLOCK`` (a dynamic flat
#      rotation) so it lines up with chunk ``base // BLOCK``,
#   2. merges the chunk that earlier tiles left partially filled (kept in
#      VMEM: ``pend``) under the window's first chunk,
#   3. DMAs the window's chunks to HBM and waits, and
#   4. keeps the chunk holding its last unit as the next ``pend``.
# Only whole chunks move, at chunk-aligned HBM offsets.  A step that
# handles a block of G tiles merges their G windows into one VMEM buffer
# first — tile g's window at ``base + prefix_g``, ``prefix_g`` the sum
# of the totals of the tiles before it, each merged (1-2) under the
# chunk the tile before it left partially filled — and then makes ONE
# copy of the whole buffer (3) and keeps one ``pend`` (4).


def window_out(nblk: int, width: int, dtype, tiles_per_step: int = 1):
    """``(out_spec, out_shape, scratch_shapes)`` of a windowed writer.  A
    step's ``tiles_per_step * width`` units, starting anywhere inside a
    chunk, touch ``k`` chunks."""
    k = tiles_per_step * width // BLOCK + 1
    nchunk = nblk * width // BLOCK + 1
    return (pl.BlockSpec(memory_space=pl.ANY),
            jax.ShapeDtypeStruct((nchunk, ROWS, LANES), dtype),
            [pltpu.VMEM((k, ROWS, LANES), dtype),
             pltpu.VMEM((ROWS, LANES), dtype),
             pltpu.SemaphoreType.DMA(())])


def store_window(stage, base, tot, out_ref, buf, pend, sem):
    """Store one tile's compact window (``stage``: ``(width // LANES,
    LANES)`` int32, ``tot`` units at its front) at output offset
    ``base``; or, for a block, the G windows of ``stage``: ``(G, width //
    LANES, LANES)`` with ``tot`` the tuple of their totals, one after
    the other from ``base``.  See the section comment above."""
    if stage.ndim == 2:
        windows, tot = [stage], (tot,)
    else:
        windows = [stage[g] for g in range(stage.shape[0])]

    @pl.when(pl.program_id(0) == 0)
    def _():
        pend[...] = jnp.zeros(pend.shape, pend.dtype)

    # Each window spans its rows plus one chunk, for its start offset.
    span = stage.shape[-2] // ROWS + 1
    pos = off = base % BLOCK          # the next unit's place in ``buf``
    below = pend[...]
    for g, (w, t) in enumerate(zip(windows, tot)):
        # The first window starts in chunk 0; the others in a chunk the
        # data decides.
        c = 0 if g == 0 else pos // BLOCK
        at = pos if g == 0 else pos % BLOCK
        win = jnp.concatenate(
            [w, jnp.zeros((ROWS, LANES), jnp.int32)])
        win = common.flat_roll(win, at)
        head = win[:ROWS]
        head = jnp.where(common.flat_index(head) < at,
                         below.astype(jnp.int32), head)
        buf[c] = head.astype(buf.dtype)
        for j in range(1, span):
            buf[c + j] = win[j * ROWS:(j + 1) * ROWS].astype(buf.dtype)
        pos = pos + t
        if g + 1 < len(tot):
            below = buf[pos // BLOCK]
    k = buf.shape[0]
    copy = pltpu.make_async_copy(buf, out_ref.at[pl.ds(base // BLOCK, k)],
                                 sem)
    copy.start()
    copy.wait()
    pend[...] = buf[pos // BLOCK]


# ---------------------------------------------------------------------------
# The wrapper contract around a launch, defined once: padding mask,
# whole-buffer ASCII copy, drop-at-capacity clip and the status fold of
# per-tile error scalars.


def mask_padding(x, n, dtype, masked):
    """Zero the lanes at/past ``n`` when an explicit n_valid was given."""
    if not masked:
        return x
    idx = jnp.arange(x.shape[0])
    return jnp.where(idx < n, x, 0).astype(dtype)


def ascii_copy_result(xm, n, cap, dst_dtype):
    """Paper Algorithm 3 whole-buffer fast path: ASCII values are
    numerically identical in every matrix format — a widening copy."""
    out = xm.astype(dst_dtype)
    if cap > xm.shape[0]:
        out = jnp.concatenate(
            [out, jnp.zeros((cap - xm.shape[0],), dst_dtype)])
    return R.TranscodeResult(out, jnp.asarray(n, jnp.int32),
                             jnp.int32(R.STATUS_OK))


def clip_to_cap(outp, cap, total, dst_dtype):
    """Keep the first ``cap`` lanes (the cross-strategy drop-at-capacity
    rule) and clear the write-window slack past ``total``."""
    outp = outp[:cap]
    return jnp.where(jnp.arange(cap) < total, outp,
                     jnp.zeros((), dst_dtype))


def status(errs, ferrs):
    """Per-tile error flags and first-error offsets -> one status."""
    first = jnp.min(ferrs, initial=_IMAX)
    return R.status_from_first(first, jnp.max(errs, initial=0) > 0)
