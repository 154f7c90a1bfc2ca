"""UTF-8 codec stages: tile decode (source side) + candidate-byte encode
(destination side).

The decode side is the speculative block-parallel decode of DESIGN.md §3
(every byte treated as a lead, paper Figs. 2-4 bit surgery) plus the
maximal-subpart analysis shared verbatim with the pure-jnp reference
(``repro.core.utf8.analyze_subparts``).  The encode side is the paper §5
candidate-byte production: per code point, the four candidate UTF-8 bytes
and the 1..4 byte length.  Both sides are pure functions of VMEM-resident
int32 lanes, so the generic count/write driver
(``repro.kernels.stages.driver``) can compose them with any other format's
stages.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import tables as T
from repro.core import utf8 as u8mod
from repro.kernels.stages.common import (
    all_of, lut16, prev_tail, shift_left_flat, shift_right_flat)

# Largest code point the speculative decode can fabricate from garbage
# input: a 4-byte assembly with every data bit set ((0x07<<18)|...).
# The generic driver sizes per-tile stage windows from this.
MAX_SPECULATIVE_CP = 0x1FFFFF


def _seq_len(b):
    """Sequence length from the lead byte, as a where-tree.

    The paper uses a 32-entry L1 table keyed by ``b >> 3``; on the TPU VPU a
    four-node compare/select tree is cheaper than a gather, so the table is
    *computed* (DESIGN.md §3: the paper's own compute-vs-lookup observation,
    with the tradeoff flipped).
    """
    return jnp.where(
        b < 0x80, 1,
        jnp.where(b < 0xC0, 0,
        jnp.where(b < 0xE0, 2,
        jnp.where(b < 0xF0, 3,
        jnp.where(b < 0xF8, 4, 0)))))


def decode_tile(b, bp, bn):
    """Speculatively decode one tile given its two neighbour tiles.

    All three arguments are int32 arrays of identical (arbitrary) shape;
    the shift helpers treat them as row-major flat byte streams.  Returns
    ``(cp, is_lead, units, err_map)`` of the same shape: candidate code
    point, lead-position flag (bool), UTF-16 code units emitted by the
    character (0 at non-leads), and a per-position structural/range error
    map (bool).  Shared between the legacy standalone decode kernel and
    the generic driver.
    """
    b1 = shift_left_flat(b, bn, 1)
    b2 = shift_left_flat(b, bn, 2)
    b3 = shift_left_flat(b, bn, 3)

    seq_len = _seq_len(b)
    is_cont = (b & 0xC0) == 0x80
    is_lead = seq_len > 0

    # Branch-free bit surgery (paper Figs. 2-4).
    cp1 = b
    cp2 = ((b & 0x1F) << 6) | (b1 & 0x3F)
    cp3 = ((b & 0x0F) << 12) | ((b1 & 0x3F) << 6) | (b2 & 0x3F)
    cp4 = (
        ((b & 0x07) << 18)
        | ((b1 & 0x3F) << 12)
        | ((b2 & 0x3F) << 6)
        | (b3 & 0x3F)
    )
    cp = jnp.where(
        seq_len == 1,
        cp1,
        jnp.where(seq_len == 2, cp2, jnp.where(seq_len == 3, cp3, cp4)),
    )
    cp = jnp.where(is_lead, cp, 0)

    # Structural self-validation: expected-continuation bookkeeping.
    seq_len_prev = _seq_len(bp)
    sl_p1 = shift_right_flat(seq_len, seq_len_prev, 1)
    sl_p2 = shift_right_flat(seq_len, seq_len_prev, 2)
    sl_p3 = shift_right_flat(seq_len, seq_len_prev, 3)
    exp_cont = (sl_p1 >= 2) | (sl_p2 >= 3) | (sl_p3 >= 4)
    struct_err = (exp_cont != is_cont) | (b >= 0xF8)

    # Scalar-range validation (overlong / surrogate / too-large).
    # MIN_CP_FOR_LEN as a select tree (same compute-over-lookup adaptation).
    min_cp = jnp.where(seq_len == 2, 0x80,
             jnp.where(seq_len == 3, 0x800,
             jnp.where(seq_len == 4, 0x10000, 0)))
    range_err = is_lead & (
        (cp < min_cp) | ((cp >= 0xD800) & (cp < 0xE000)) | (cp > 0x10FFFF)
    )

    units = jnp.where(is_lead, 1 + (cp >= 0x10000).astype(jnp.int32), 0)
    return cp, is_lead, units, struct_err | range_err


def speculative_decode(b, bp, bn):
    """Decode-stage entry for the generic driver: ``(cp, is_lead)``."""
    cp, is_lead, _units, _err = decode_tile(b, bp, bn)
    return cp, is_lead


def analyze_tile(b, bp, bn):
    """Maximal-subpart analysis of one tile given its neighbour tiles.

    Same shift convention as :func:`decode_tile`; the body is the shared
    :func:`repro.core.utf8.analyze_subparts`, so the kernels' error
    location and errors="replace" semantics are bit-identical to
    the pure-jnp block-parallel reference.  Returns the analysis dict
    (``starts`` / ``valid`` / ``cp`` / ``units`` / ``err``).
    """
    return u8mod.analyze_subparts(
        b,
        shift_left_flat(b, bn, 1),
        shift_left_flat(b, bn, 2),
        shift_left_flat(b, bn, 3),
        shift_right_flat(b, bp, 1),
        shift_right_flat(b, bp, 2),
        shift_right_flat(b, bp, 3),
    )


def kl_error_tile(b, bp):
    """Keiser-Lemire nibble-table error map for one VMEM tile.

    ``b``/``bp`` are the current and previous tiles (int32, identical
    ``(..., ROWS, LANES)`` shape).  The three 16-entry nibble tables
    (``repro.core.tables.BYTE_*``) are compile-time constants, looked up
    with a compare/select chain over each table's runs
    (``stages.common.lut16``): the TPU has no vector gather, and the
    select chain is the in-register analogue of the paper's
    ``vpshufb`` lookup.  Returns a bool error map: positions where the
    three ANDed nibble lookups disagree with the expected-continuation
    bit (paper §4).  Errors surface at the *second byte* of each bad
    pair — use :func:`repro.core.utf8.analyze_subparts` when the
    lead-relative (Python ``exc.start``) position is needed.

    This is the ``extra_err`` detector the count pass folds in
    (``repro.kernels.stages.UTF8``) and the body of the standalone
    validation kernel (``repro.kernels.utf8_validate``).
    """
    prev1 = shift_right_flat(b, bp, 1)
    prev2 = shift_right_flat(b, bp, 2)
    prev3 = shift_right_flat(b, bp, 3)
    sc = (
        lut16(T.BYTE_1_HIGH, prev1 >> 4)
        & lut16(T.BYTE_1_LOW, prev1 & 0xF)
        & lut16(T.BYTE_2_HIGH, b >> 4)
    )
    is_third = prev2 >= 0xE0
    is_fourth = prev3 >= 0xF0
    must_be_cont = (is_third | is_fourth).astype(jnp.int32) * T.TWO_CONTS
    return (sc ^ must_be_cont) != 0


# ---------------------------------------------------------------------------
# ≤2-byte tile class (driver.onepass_tile dispatch, DESIGN.md §9): the
# restriction of the bodies above to tiles where every byte — and the
# 3-byte inflow window — is below 0xE0.  No 3-/4-byte candidate assembly,
# one lane of claim context instead of three.


def class2_pred(b, bp):
    """True when the tile (and its 3-lane inflow) holds only ASCII,
    2-byte leads, stray continuations and the C0/C1 overlongs — i.e. no
    byte that could start or extend a 3-/4-byte sequence.  Within that
    class :func:`decode2` / :func:`analyze2` are lanewise bit-identical
    to :func:`speculative_decode` / :func:`analyze_tile`.
    """
    return (all_of((b >= 0) & (b < 0xE0))
            & all_of(~prev_tail(bp, 3) | ((bp >= 0) & (bp < 0xE0))))


def decode2(b, bp, bn):
    """Class-specialized speculative decode: 1-/2-byte assembly only."""
    del bp
    b1 = shift_left_flat(b, bn, 1)
    cp = jnp.where(b < 0x80, b, ((b & 0x1F) << 6) | (b1 & 0x3F))
    is_lead = (b < 0x80) | (b >= 0xC0)
    return jnp.where(is_lead, cp, 0), is_lead


def analyze2(b, bp, bn):
    """Class-specialized maximal-subpart analysis.

    With every byte below 0xE0, strict lead lengths are 0/1/2, so of
    ``analyze_subparts``'s three claim terms only the 2-byte one
    survives and the first-continuation range is always the default
    80..BF.  Term-by-term restriction of
    :func:`repro.core.utf8.analyze_subparts`.
    """
    nxt1 = shift_left_flat(b, bn, 1)
    prv1 = shift_right_flat(b, bp, 1)

    # Strict lead length (C0/C1 overlongs are invalid leads -> 0).
    L = jnp.where(b < 0x80, 1,
        jnp.where((b >= 0xC2) & (b < 0xE0), 2, 0))
    is_cont = (b & 0xC0) == 0x80
    claimed = (prv1 >= 0xC2) & (prv1 <= 0xDF) & is_cont
    starts = ~claimed
    c1ok = (nxt1 & 0xC0) == 0x80
    valid = starts & ((L == 1) | ((L == 2) & c1ok))

    cp = jnp.where(L == 2, ((b & 0x1F) << 6) | (nxt1 & 0x3F), b)
    cp = jnp.where(valid, cp, jnp.where(starts, 0xFFFD, 0))
    return {
        "starts": starts,
        "valid": valid,
        "cp": cp,
        "units": starts.astype(jnp.int32),
        "err": starts & ~valid,
    }


# ---------------------------------------------------------------------------
# Encode side: code points -> candidate UTF-8 bytes (paper §5).


def unit_len(cp):
    """Encoded UTF-8 length per code point (1..4)."""
    return (
        1
        + (cp >= 0x80).astype(jnp.int32)
        + (cp >= 0x800).astype(jnp.int32)
        + (cp >= 0x10000).astype(jnp.int32)
    )


def py_unit_len(cp: int) -> int:
    """Host-side :func:`unit_len` for static stage-width computation."""
    return 1 + (cp >= 0x80) + (cp >= 0x800) + (cp >= 0x10000)


def utf8_candidates(cp):
    """Candidate UTF-8 bytes + length for per-lane code points.

    Pure function of ``cp`` (paper Fig. 1 bit layout): returns
    ``(b0, b1, b2, b3, L)`` where ``L`` in 1..4 is the encoded length.
    Shared by the strict speculative path and the errors="replace" path
    (where U+FFFD lanes encode as EF BF BD).
    """
    c0 = cp & 0x3F
    c1 = (cp >> 6) & 0x3F
    c2 = (cp >> 12) & 0x3F
    c3 = (cp >> 18) & 0x07
    L = unit_len(cp)
    z = jnp.zeros_like(cp)
    b0 = jnp.where(L == 1, cp,
         jnp.where(L == 2, 0xC0 | (cp >> 6),
         jnp.where(L == 3, 0xE0 | (cp >> 12), 0xF0 | c3)))
    b1 = jnp.where(L == 2, 0x80 | c0,
         jnp.where(L == 3, 0x80 | c1,
         jnp.where(L == 4, 0x80 | c2, z)))
    b2 = jnp.where(L == 3, 0x80 | c0,
         jnp.where(L == 4, 0x80 | c1, z))
    b3 = jnp.where(L == 4, 0x80 | c0, z)
    return b0, b1, b2, b3, L


def encode_units(cp):
    """Encode-stage entry for the generic driver: candidate unit planes."""
    b0, b1, b2, b3, _L = utf8_candidates(cp)
    return (b0, b1, b2, b3)
