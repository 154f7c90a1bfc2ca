"""Stream compaction: the TPU-native replacement for pshufb compress-store.

The paper compacts variable-length output with byte shuffles driven by
table-loaded masks.  TPUs have no lane-crossing byte shuffle, but a 1D
cumulative sum plus a scatter (or gather from precomputed source indices)
expresses the same "compress the valid lanes to the front" operation in a
way XLA lowers efficiently.  Both forms are provided:

  * ``compact``          -- scatter form (out[rank(i)] = x[i]); best when the
                            value array is wide.
  * ``compact_gather``   -- gather form (out[j] = x[select(j)]), built from a
                            stable sort over the mask; avoids scatters, which
                            some backends serialize.

Both are jit-safe: output capacity is static, the logical length is returned
as a scalar.

The Pallas kernels (DESIGN.md §5, §9) replace the *global* cumsum +
scatter with hierarchical compaction: an intra-tile scan inside the kernel
(:func:`tile_exclusive_scan`) plus a running output offset carried across
the sequential grid, one scalar per tile.  No full-capacity index array
is materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def compact(values: jax.Array, mask: jax.Array, capacity: int, fill=0):
    """Compress ``values[mask]`` to the front of a ``capacity``-sized buffer.

    Returns (out, count).  values may have trailing dims (compacted along
    axis 0).
    """
    mask_i = mask.astype(jnp.int32)
    rank = jnp.cumsum(mask_i) - 1
    count = rank[-1] + 1 if mask_i.shape[0] > 0 else jnp.int32(0)
    dest = jnp.where(mask, rank, capacity)  # invalid lanes -> dropped
    out_shape = (capacity,) + values.shape[1:]
    out = jnp.full(out_shape, fill, values.dtype)
    out = out.at[dest].set(values, mode="drop")
    return out, count


def compact_offsets(values: jax.Array, lengths: jax.Array, mask: jax.Array,
                    capacity: int, fill=0):
    """Variable-length compaction: lane i contributes ``lengths[i]`` items.

    ``values`` has shape (N, K) with K >= max(lengths); item j of lane i goes
    to offset ``start[i] + j`` where start is the exclusive cumsum of the
    masked lengths.  This is the §5 UTF-8 egress pattern (each code point
    emits 1..4 bytes).

    Returns (out, total).
    """
    n, k = values.shape
    eff_len = jnp.where(mask, lengths, 0)
    start = jnp.cumsum(eff_len) - eff_len
    total = start[-1] + eff_len[-1] if n > 0 else jnp.int32(0)
    j = jnp.arange(k)[None, :]
    dest = start[:, None] + j
    keep = mask[:, None] & (j < eff_len[:, None])
    dest = jnp.where(keep, dest, capacity)
    out = jnp.full((capacity,), fill, values.dtype)
    out = out.at[dest.reshape(-1)].set(values.reshape(-1), mode="drop")
    return out, total


@functools.partial(jax.jit, static_argnames=("rows",))
def tile_exclusive_scan(x: jax.Array, rows: int = 8):
    """Row-major exclusive prefix sum of a VMEM tile, without ``cumsum``.

    ``x`` is an int32 tile of shape ``(rows, lanes)`` (or flat, viewed as
    ``(rows, -1)``), or a ``(G, rows, lanes)`` block of G tiles scanned
    each on its own.  Mosaic has no scan primitive, so the prefix is a
    Hillis-Steele log-step scan along the lanes (``log2(lanes)`` lane
    rotations, adds and selects) plus the same over the row totals
    across sublanes — integer adds only, so it is exact.

    Returns ``(exclusive, total)``: the exclusive prefix (same shape as
    ``x``) and the tile total (a scalar for one tile; ``(G, 1, 1)`` for
    a block).  Runs inside Pallas kernels (the ``jit`` only lets it also
    run eagerly: ``pltpu.roll`` has no eager rule).
    """
    flat = x.ndim == 1
    x2 = x.reshape(rows, -1) if flat else x
    ra, la = x2.ndim - 2, x2.ndim - 1
    nrow, nlane = x2.shape[ra], x2.shape[la]
    row = jax.lax.broadcasted_iota(jnp.int32, x2.shape, ra)
    lane = jax.lax.broadcasted_iota(jnp.int32, x2.shape, la)
    incl = x2
    s = 1
    while s < nlane:
        incl = incl + jnp.where(lane >= s, pltpu.roll(incl, s, la), 0)
        s <<= 1
    tot_r = jnp.broadcast_to(jnp.sum(x2, axis=la, keepdims=True), x2.shape)
    above = tot_r
    s = 1
    while s < nrow:
        above = above + jnp.where(row >= s, pltpu.roll(above, s, ra), 0)
        s <<= 1
    excl = incl + (above - tot_r) - x2
    if x2.ndim == 2:
        total = jnp.sum(x2)
    else:
        total = (excl + x2)[..., nrow - 1:, nlane - 1:]
    return (excl.reshape(x.shape) if flat else excl), total


def compact_gather(values: jax.Array, mask: jax.Array, capacity: int, fill=0):
    """Sort-based compaction (no scatter): stable-sort lanes by ~mask."""
    n = values.shape[0]
    key = jnp.where(mask, 0, 1).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    gathered = values[order]
    count = jnp.sum(mask.astype(jnp.int32))
    if capacity <= n:
        out = gathered[:capacity]
    else:
        pad = jnp.full((capacity - n,) + values.shape[1:], fill, values.dtype)
        out = jnp.concatenate([gathered, pad], 0)
    idx = jnp.arange(capacity)
    out = jnp.where((idx < count).reshape((-1,) + (1,) * (out.ndim - 1)), out, fill)
    return out, count
