"""Shared tile-layout helpers for the codec stages.

Every stage body sees its VMEM tile as a 2-D ``(ROWS, LANES)`` int32
array plus the two neighbour tiles, and reads it as the row-major flat
element stream.  A leading axis is carried along: a ``(G, ROWS, LANES)``
block is G independent tiles, each with its own neighbours, and every
helper below treats each tile as it treats a lone one.  The helpers are
the single definition of that convention, written only with operations
the Mosaic TPU compiler lowers: lane/sublane rotations
(``pltpu.roll``), iotas, compares, selects and full reductions.  There is
no flattening to 1-D, no concatenation at unaligned offsets and no
gather; under the Pallas interpreter ``pltpu.roll`` is ``jnp.roll``, so
both execution modes run the same program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def _axes(x):
    return x.ndim - 2, x.ndim - 1


def row_lane(x):
    """Row and lane index of every element of a ``(..., R, L)`` array."""
    ra, la = _axes(x)
    return (jax.lax.broadcasted_iota(jnp.int32, x.shape, ra),
            jax.lax.broadcasted_iota(jnp.int32, x.shape, la))


def flat_index(x):
    """Row-major flat position of every element within its ``(R, L)``
    tile."""
    row, lane = row_lane(x)
    return row * x.shape[-1] + lane


def flat_roll(x, s):
    """Circular shift of the row-major flat stream: ``out[p] = x[p - s]``.

    ``s`` is a non-negative Python int or an int32 scalar (a dynamic
    rotate on the chip).  One lane rotation plus two row rotations and a
    select: the lanes that wrap past a row edge take the previous row.
    """
    ra, la = _axes(x)
    nrow, nlane = x.shape[ra], x.shape[la]
    if isinstance(s, int):
        a, c = divmod(s % (nrow * nlane), nlane)
        if c == 0:
            return pltpu.roll(x, a, ra) if a else x
        y = pltpu.roll(x, c, la)
        _, lane = row_lane(x)
        return jnp.where(lane >= c, pltpu.roll(y, a, ra) if a else y,
                         pltpu.roll(y, (a + 1) % nrow, ra))
    s = jnp.asarray(s, jnp.int32)
    c, a = s % nlane, s // nlane
    y = pltpu.roll(x, c, la)
    _, lane = row_lane(x)
    return jnp.where(lane >= c, pltpu.roll(y, a % nrow, ra),
                     pltpu.roll(y, (a + 1) % nrow, ra))


def shift_left_flat(cur, nxt, n):
    """``cur[i + n]`` (flat order) with elements flowing in from the next
    tile; ``0 < n < LANES``."""
    ra, la = _axes(cur)
    nrow, nlane = cur.shape[ra], cur.shape[la]
    row, lane = row_lane(cur)
    y = pltpu.roll(cur, nlane - n, la)
    yn = pltpu.roll(nxt, nlane - n, la)
    # Row r's wrapped lanes come from row r+1; the last row's from the
    # next tile's first row.
    w = pltpu.roll(jnp.where(row == 0, yn, y), nrow - 1, ra)
    return jnp.where(lane < nlane - n, y, w)


def shift_right_flat(cur, prev, n):
    """``cur[i - n]`` (flat order) with elements flowing in from the
    previous tile; ``0 < n < LANES``."""
    ra, la = _axes(cur)
    nrow = cur.shape[ra]
    row, lane = row_lane(cur)
    y = pltpu.roll(cur, n, la)
    yp = pltpu.roll(prev, n, la)
    w = pltpu.roll(jnp.where(row == nrow - 1, yp, y), 1, ra)
    return jnp.where(lane >= n, y, w)


def fold_tiles(x, op):
    """One ``(R, L)`` tile folded elementwise from the tiles of a
    ``(G, R, L)`` block with ``op`` (a tree of lanewise ops, no cross-lane
    work); a lone tile is returned as it is."""
    if x.ndim == 2:
        return x
    tiles = [x[g] for g in range(x.shape[0])]
    while len(tiles) > 1:
        folded = [op(a, b) for a, b in zip(tiles[::2], tiles[1::2])]
        tiles = folded + tiles[len(folded) * 2:]
    return tiles[0]


def _per_tile(x, reduce):
    if x.ndim == 2:
        return reduce(x)
    return tuple(reduce(x[g]) for g in range(x.shape[0]))


def tile_sums(x):
    """Per-tile totals of an int32 ``(..., R, L)`` array: a scalar for a
    lone tile, a tuple of G scalars for a ``(G, R, L)`` block."""
    return _per_tile(x, jnp.sum)


def tile_max(x):
    """Per-tile maxima, shaped as :func:`tile_sums`'s totals."""
    return _per_tile(x, jnp.max)


def tile_min(x):
    """Per-tile minima, shaped as :func:`tile_sums`'s totals."""
    return _per_tile(x, jnp.min)


def all_of(mask):
    """Scalar bool: every element of ``mask`` is set (an int32 min
    reduction; boolean reductions do not lower)."""
    return jnp.min(fold_tiles(mask.astype(jnp.int32), jnp.minimum)) > 0


def prev_tail(xp, k):
    """Mask of the last ``k`` flat positions of a tile (the previous
    tile's lanes whose characters can reach into the current one)."""
    row, lane = row_lane(xp)
    return (row == xp.shape[-2] - 1) & (lane >= xp.shape[-1] - k)


def lut16(table, n):
    """``table[n]`` for a 16-entry constant table and nibbles ``n`` in
    0..15, as a select chain over the table's runs of equal entries (the
    TPU has no vector gather; the nibble tables have at most 9 runs)."""
    vals = [int(v) for v in table]
    assert len(vals) == 16
    out = jnp.full(n.shape, vals[-1], jnp.int32)
    k = 15
    while k > 0:
        j = k
        while j > 0 and vals[j - 1] == vals[k]:
            j -= 1
        if j == 0:
            break
        # entries j..k share a value; everything below j is decided later
        out = jnp.where(n < j, vals[j - 1], out)
        k = j - 1
    return out
