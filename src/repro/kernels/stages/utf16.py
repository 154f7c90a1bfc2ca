"""UTF-16 codec stages: tile decode (surrogate-pair folding) + candidate
code-unit encode.

Decode side: per lane, classify the unit (BMP / surrogate half), fold
surrogate pairs into supplementary code points using one unit of
lookahead from the next tile (and one of lookbehind to identify consumed
trailing halves); the maximal-subpart analysis is the shared
``repro.core.utf16.analyze_units``.  Encode side: UTF-32 -> UTF-16
candidate production (``repro.core.utf16.encode_candidates`` bit layout).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import utf16 as u16core
from repro.kernels.stages.common import (
    all_of, shift_left_flat, shift_right_flat)
from repro.kernels.stages.utf8 import utf8_candidates

# Largest code point the speculative pair folding can fabricate from
# garbage (hi = 0xDBFF followed by any 16-bit unit): 0x10000 + 0xFFC00 +
# (0xFFFF - 0xDC00).  Note this exceeds 0x10FFFF — stage widths must size
# for it (see driver.stage_units; undersizing was a real overflow bug of
# the hand-sized per-pair stage constants on surrogate-flood garbage).
MAX_SPECULATIVE_CP = 0x111FFF


def speculative_decode(u, up, un):
    """Decode-stage entry: ``(cp, is_lead)`` for one tile.

    ``cp`` folds surrogate pairs (paper Fig. 4 surrogate construction,
    inverted); a low half claimed by the previous lane's high half is not
    a lead.
    """
    top6 = u >> 10
    is_hi = top6 == 0x36
    is_lo = top6 == 0x37

    nxt = shift_left_flat(u, un, 1)
    prv = shift_right_flat(u, up, 1)
    prv_is_hi = (prv >> 10) == 0x36

    pair_cp = 0x10000 + ((u - 0xD800) << 10) + (nxt - 0xDC00)
    cp = jnp.where(is_hi, pair_cp, u)
    is_lead = ~(is_lo & prv_is_hi)
    return cp, is_lead


def analyze_tile(u, up, un):
    """Unit analysis of one tile given its neighbour tiles.

    The body is the shared :func:`repro.core.utf16.analyze_units` (one
    unit of context each way), so the kernels' unpaired-surrogate
    location and errors="replace" semantics match the pure-jnp reference
    bit for bit.  Returns the analysis dict (``starts`` / ``valid`` /
    ``cp`` / ``err``).
    """
    return u16core.analyze_units(
        u, shift_left_flat(u, un, 1), shift_right_flat(u, up, 1))


def encode_tile(u, up, un):
    """Legacy fused UTF-16-decode + UTF-8-encode body of one tile.

    Kept for the standalone ``utf16_encode`` kernel (the pre-stages
    composition of this module's decode with the UTF-8 encode stage).
    Returns ``(b0, b1, b2, b3, L, err_map)``; ``L`` is 0 at consumed
    trailing surrogate halves.
    """
    cp, is_lead = speculative_decode(u, up, un)
    b0, b1, b2, b3, L = utf8_candidates(cp)
    L = jnp.where(is_lead, L, 0)

    is_hi = (u >> 10) == 0x36
    is_lo = (u >> 10) == 0x37
    nxt_is_lo = (shift_left_flat(u, un, 1) >> 10) == 0x37
    prv_is_hi = (shift_right_flat(u, up, 1) >> 10) == 0x36
    err_map = (is_hi & ~nxt_is_lo) | (is_lo & ~prv_is_hi)
    return b0, b1, b2, b3, L, err_map


# ---------------------------------------------------------------------------
# ≤2-byte tile class: units below 0x800 carry no surrogate halves, so
# decode is the identity and analysis is all-valid.  No inflow check is
# needed: a unit below 0x800 is never a low surrogate, so a trailing
# high surrogate in the previous tile cannot claim into this tile (its
# unpaired-half error is flagged in ITS tile via one unit of lookahead).


def class2_pred(u, up):
    del up
    return all_of((u >= 0) & (u < 0x800))


def decode2(u, up, un):
    del up, un
    return u, jnp.ones(u.shape, bool)


def analyze2(u, up, un):
    del up, un
    ones = jnp.ones(u.shape, bool)
    return {
        "starts": ones,
        "valid": ones,
        "cp": u,
        "units": ones.astype(jnp.int32),
        "err": jnp.zeros(u.shape, bool),
    }


# ---------------------------------------------------------------------------
# Encode side: code points -> candidate UTF-16 units.


def unit_len(cp):
    """UTF-16 code units per code point (1 or 2)."""
    return 1 + (cp >= 0x10000).astype(jnp.int32)


def py_unit_len(cp: int) -> int:
    return 1 + (cp >= 0x10000)


def encode_units(cp):
    """Encode-stage entry: the two candidate code-unit planes."""
    _units, u0, u1, _bad = u16core.encode_candidates(cp)
    return (u0, u1)
