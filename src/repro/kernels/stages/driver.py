"""Generic decode×encode tile driver: one count body, one write body,
any (source, destination) format pair.

The paper's pipeline — validate → decode to code points → re-encode →
compact — is format-symmetric; this module is that symmetry made
executable.  A :class:`Codec` bundles one format's personality on both
sides of the code-point intermediate:

  decode side   ``decode``  (speculative: every lane treated as a lead,
                returns per-lane candidate code point + lead mask) and
                ``analyze`` (maximal-subpart classification: unit starts,
                validity, replacement code points, error map — CPython
                ``UnicodeDecodeError.start`` / ``errors="replace"``
                semantics), plus an optional ``extra_err`` detector
                (the Keiser-Lemire nibble tables ride along for UTF-8).
  encode side   ``unit_len`` / ``encode`` (candidate unit planes per code
                point, paper §5), plus optional ``encode_bad`` for
                destinations that cannot represent every scalar (Latin-1).
  class side    ``max_lookback`` (how far a character can claim backward
                across a tile boundary — 3 source units for UTF-8, 1 for
                UTF-16, 0 for the fixed-width formats) and the optional
                ≤2-byte tile class (``class2_pred`` / ``decode2`` /
                ``analyze2``): a per-tile predicate plus specialized
                decode/analysis bodies with no 3-/4-unit assembly and no
                surrogate folding, for tiles whose every code point fits
                in 11 bits (DESIGN.md §9 tile-class dispatch).

:func:`onepass_tile` (transcode) and :func:`count_tile` (scan) compose
any pair of codecs (DESIGN.md §5/§8/§9); the per-pair tile bodies that
previously hardwired UTF-8→UTF-16 and UTF-16→UTF-8 are now thin
instantiations of these functions.  Both are compositions of three
primitives — :func:`decode_once` (ONE speculative decode /
maximal-subpart analysis of the tile), :func:`count_decoded` (lengths +
fused validation over the decoded lanes) and :func:`stage_decoded`
(in-tile compaction of the decoded lanes) — so the transcode runs count
AND write off one decode of each tile.  Each
primitive has a class-specialized twin (:func:`decode_once2` /
:func:`count_decoded2` / :func:`stage_decoded2`) for the ≤2-byte tile
class.

Stage windows are sized from first principles instead of per-pair
constants: the speculative worst case is ``dst.py_unit_len(src.
max_speculative_cp)`` units per source lane (:func:`stage_units`).  This
derivation fixed a real overflow of the hand-sized UTF-16→UTF-8 bound —
garbage dense in high surrogates folds to pair code points above
U+10000 at *every* lane (4 candidate bytes each, 4·BLOCK total), past the
old ``3*BLOCK + 1`` stage.  The ≤2-byte class narrows the same
derivation to ``dst.py_unit_len(0x7FF)`` units per lane
(:func:`stage_units2`) — half the window for a UTF-8 destination, a
quarter of the speculative UTF-16-source worst case.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import compaction
from repro.kernels.stages import common

ROWS = 8
LANES = 128
BLOCK = ROWS * LANES

# Sentinel for per-tile first-error min-reduction (int32 max; matches
# repro.core.result.NO_ERR_SENTINEL — re-declared here to keep the stages
# package import-light inside kernel bodies).
_IMAX = 2**31 - 1


class Codec(NamedTuple):
    """One format's decode/encode personality over the code-point
    intermediate (see module docstring)."""

    name: str
    dtype: Any                # narrow storage dtype (uint8/uint16/uint32)
    itemsize: int             # bytes per storage unit
    decode: Callable          # (x, xp, xn) -> (cp, is_lead)
    analyze: Callable         # (x, xp, xn) -> {starts, valid, cp, err}
    unit_len: Callable        # cp -> int32 units per code point
    encode: Callable          # cp -> tuple of candidate unit planes
    max_speculative_cp: int   # largest cp the speculative decode fabricates
    py_unit_len: Callable     # host-side unit_len (static stage sizing)
    extra_err: Optional[Callable] = None   # (x, xp) -> bool map
    encode_bad: Optional[Callable] = None  # cp -> bool (unencodable)
    # Source units of the previous tile that can still be part of a
    # character (or error subpart) reaching into the current tile: 3 for
    # UTF-8 (a 4-byte lead at the last position), 1 for UTF-16 (a high
    # surrogate), 0 for the fixed-width formats.  The per-tile ASCII and
    # ≤2-byte class predicates check exactly this inflow window, so
    # fixed-width sources no longer pay a UTF-8-sized 3-lane check.
    max_lookback: int = 3
    # ≤2-byte tile class (optional; None disables the class for this
    # source format — e.g. Latin-1, whose general path is already
    # 2-byte-max).  ``class2_pred(x, xp) -> bool`` must be True only when
    # decode2/analyze2 are lanewise bit-identical to decode/analyze on
    # the tile; ``class2_replaces`` marks sources whose class-2 analysis
    # can substitute U+FFFD (stage sizing must then cover its encoding).
    class2_pred: Optional[Callable] = None   # (x, xp) -> bool scalar
    decode2: Optional[Callable] = None       # (x, xp, xn) -> (cp, is_lead)
    analyze2: Optional[Callable] = None      # (x, xp, xn) -> analysis dict
    class2_replaces: bool = False
    # Every in-stream lane starts a character (fixed-width sources; for
    # ``class2_lanes_lead``, every lane of a ≤2-byte-class tile): the
    # occupied lanes are then a prefix and staging skips the compaction
    # network (:func:`_compact_units`).
    lanes_lead: bool = False
    class2_lanes_lead: bool = False


def stage_units(src: Codec, dst: Codec) -> int:
    """Speculative worst-case destination units per source lane."""
    return int(dst.py_unit_len(src.max_speculative_cp))


def stage_width(src: Codec, dst: Codec) -> int:
    """Per-tile staging window width for the (src, dst) write pass."""
    return BLOCK * stage_units(src, dst)


def stage_units2(src: Codec, dst: Codec) -> int:
    """Destination units per lane inside the ≤2-byte tile class.

    Every in-class code point fits in 11 bits, so the bound is
    ``dst.py_unit_len(0x7FF)`` — plus, for sources whose class-2 analysis
    can substitute U+FFFD (UTF-8 under ``errors="replace"``), enough room
    for the replacement character's encoding.  (For every enabled cell
    the two coincide: U+FFFD is 1 unit in all of UTF-8's destinations.)
    """
    u = int(dst.py_unit_len(0x7FF))
    if src.class2_replaces:
        u = max(u, int(dst.py_unit_len(0xFFFD)))
    return u


def _encode_err(dst: Codec, a, live):
    """Encode-side error map over analyzed unit starts (Latin-1 egress)."""
    if dst.encode_bad is None:
        return a["err"] & live
    return (a["err"] | (dst.encode_bad(a["cp"]) & a["starts"])) & live


def decode_once(src: Codec, x, xp, xn, *, errors: str, validate: bool):
    """The ONE speculative decode / analysis of a tile.

    Returns ``(a, cp, lead)``: the maximal-subpart analysis map (``None``
    when neither validation nor replacement needs it), the per-lane code
    point, and the unit-start mask the counting and staging primitives
    consume.  Under ``errors="replace"`` the code points/starts come from
    the analysis (replacement-substituted); under ``"strict"`` from the
    raw speculative decode — exactly the historical count/write split,
    now computed once per tile instead of once per pass.
    """
    need_analysis = validate or errors == "replace"
    a = src.analyze(x, xp, xn) if need_analysis else None
    if errors == "replace":
        return a, a["cp"], a["starts"]
    cp, is_lead = src.decode(x, xp, xn)
    return a, cp, is_lead


def decode_once2(src: Codec, x, xp, xn, *, errors: str, validate: bool):
    """Class-specialized :func:`decode_once` for a ≤2-byte tile.

    Same contract, but through ``src.decode2`` / ``src.analyze2``: no
    3-/4-unit candidate assembly, no surrogate folding, and a claim
    window of ONE previous lane instead of three.  Only valid on tiles
    where ``src.class2_pred`` holds.
    """
    need_analysis = validate or errors == "replace"
    a = src.analyze2(x, xp, xn) if need_analysis else None
    if errors == "replace":
        return a, a["cp"], a["starts"]
    cp, is_lead = src.decode2(x, xp, xn)
    return a, cp, is_lead


def count_decoded(src: Codec, dst: Codec, a, cp, lead, x, xp, live, gidx, *,
                  validate: bool, per_tile: bool = False):
    """Lengths + fused validation over an already-decoded tile.

    Returns the three scalars ``(total, err_flag, first_err_gidx)`` —
    first-error offsets are in *global* stream coordinates (callers
    subtract the document start).  Over a ``(G, ROWS, LANES)`` block,
    ``total`` is a tuple of the G tiles' totals and the two error
    scalars are folded over the block (one stream's carries fold by
    max/min anyway) — or, with ``per_tile``, are tuples of each tile's
    own, for a caller whose tiles belong to different documents and
    reduce per document downstream.
    """
    tot = common.tile_sums(jnp.where(lead & live, dst.unit_len(cp), 0))
    if validate:
        # Fused validation, one scan: the maximal-subpart map locates the
        # first decode error at its lead (Python exc.start semantics) and
        # the destination's encode_bad map folds in unencodable scalars.
        # An extra detector (the paper-faithful Keiser-Lemire nibble
        # tables for UTF-8) rides along deliberately: it feeds only the
        # flag, so a defect in either detector degrades to a located (or
        # offset-0) error rather than a silently accepted invalid stream.
        sub = _encode_err(dst, a, live)
        err = sub
        if src.extra_err is not None:
            err = err | (src.extra_err(x, xp) & live)
        err_flag, ferr = _error_scalars(err, sub, gidx, per_tile)
    else:
        err_flag, ferr = _no_errors(x, per_tile)
    return tot, err_flag, ferr


def _error_scalars(err, sub, gidx, per_tile):
    """``(err_flag, first_err_gidx)`` over a tile or a block of tiles,
    or with ``per_tile`` of each tile of the block."""
    if per_tile:
        return (common.tile_max(err.astype(jnp.int32)),
                common.tile_min(jnp.where(sub, gidx, _IMAX)))
    err_flag = jnp.max(common.fold_tiles(err.astype(jnp.int32),
                                         jnp.maximum))
    ferr = jnp.min(common.fold_tiles(jnp.where(sub, gidx, _IMAX),
                                     jnp.minimum))
    return err_flag, ferr


def _no_errors(x, per_tile):
    """The error scalars of clean tiles, shaped as :func:`_error_scalars`
    shapes them for ``x``."""
    if per_tile and x.ndim == 3:
        return (jnp.int32(0),) * x.shape[0], (jnp.int32(_IMAX),) * x.shape[0]
    return jnp.int32(0), jnp.int32(_IMAX)


def count_decoded2(src: Codec, dst: Codec, a, cp, lead, live, gidx, *,
                   validate: bool, per_tile: bool = False):
    """Class-specialized :func:`count_decoded` for a ≤2-byte tile.

    The extra Keiser-Lemire detector is skipped (its three nibble-table
    lookups are the most expensive part of the count): within the class
    the maximal-subpart map flags every invalid stream on its own, and
    the first-error offset always came from the subpart map — so the
    sticky per-document ``(err, ferr)`` folds are unchanged even though
    a per-tile flag may fire in a different tile than KL would have.
    """
    tot = common.tile_sums(jnp.where(lead & live, dst.unit_len(cp), 0))
    if validate:
        sub = _encode_err(dst, a, live)
        err_flag, ferr = _error_scalars(sub, sub, gidx, per_tile)
    else:
        err_flag, ferr = _no_errors(live, per_tile)
    return tot, err_flag, ferr


def _route(arrs, towards_front: bool, nbits: int):
    """A log-step routing network over ``(R, LANES)`` tiles (or ``(G, R,
    LANES)`` blocks: every tile routes on its own, through the same
    stages).

    ``arrs[0]`` is the per-slot unit count (0 marks an empty slot) and
    ``arrs[1]`` the displacement each slot travels; the other arrays
    travel with their slot.  Stage ``k`` moves every occupied slot whose
    displacement has bit ``k`` set by ``2**k`` flat positions — towards
    the front with bits low to high, or towards the back with bits high
    to low — and keeps the others.  The stages run as a loop with a
    dynamic rotation amount, so the network traces once.
    """
    nelem = arrs[0].shape[-2] * arrs[0].shape[-1]

    def stage(k, arrs):
        if towards_front:
            bit = jnp.left_shift(jnp.int32(1), k)
            moved = [common.flat_roll(a, nelem - bit) for a in arrs]
        else:
            bit = jnp.left_shift(jnp.int32(1), nbits - 1 - k)
            moved = [common.flat_roll(a, bit) for a in arrs]
        come = (moved[0] > 0) & ((moved[1] & bit) != 0)
        stay = (arrs[0] > 0) & ((arrs[1] & bit) == 0)
        return tuple(jnp.where(come, m, jnp.where(stay, a, 0))
                     for m, a in zip(moved, arrs))

    return jax.lax.fori_loop(0, nbits, stage, tuple(arrs))


def _compact_units(eff, planes, out_rows: int, prefix: bool):
    """In-tile compress-store of variable-length units, without gathers.

    The paper compacts with ``vpcompressb``; Mosaic has neither a vector
    gather nor a scatter, so the masked store is a pair of log-step
    routing networks (:func:`_route`) built from flat rotations and
    selects:

      1. compaction: lanes with ``eff > 0`` move towards the front by
         ``i - (#occupied lanes before i)``, displacement bits low to
         high.  Displacements are nondecreasing along the occupied
         lanes, so no two ever meet (the classic compress network), and
         the lanes' unit counts and candidate planes travel with them.
         Skipped when the occupied lanes are known to be a prefix
         (``prefix``: every in-stream lane starts a character).
      2. expansion: compacted lane ``k`` moves to its exclusive unit rank
         ``rank_k >= k``, bits high to low; ``rank_k - k`` grows by
         ``eff_k - 1 >= 0`` per lane, so this network is collision-free
         too.  Skipped when every lane emits one unit.

    Plane ``j`` of a lane then lands ``j`` positions past its rank (a
    static flat shift), where ``j < eff``.  Returns the ``(out_rows,
    LANES)`` int32 window holding the ``sum(eff)`` units at its front and
    zeros behind.  Exact by construction: integers are only moved and
    selected, never combined arithmetically.  A ``(G, R, LANES)`` block
    compacts each tile on its own, through one shared network.
    """
    nelem = eff.shape[-2] * eff.shape[-1]
    if prefix:
        eff_c, planes_c = eff, planes
    else:
        before, _ = compaction.tile_exclusive_scan(
            (eff > 0).astype(jnp.int32))
        disp = jnp.where(eff > 0, common.flat_index(eff) - before, 0)
        arrs = _route([eff, disp, *planes], True, (nelem - 1).bit_length())
        eff_c, planes_c = arrs[0], list(arrs[2:])

    pad = out_rows - eff.shape[-2]

    def grow(a):
        if not pad:
            return a
        zeros = jnp.zeros(a.shape[:-2] + (pad, LANES), a.dtype)
        return jnp.concatenate([a, zeros], axis=-2)

    if len(planes) == 1:
        # One unit per lane: the compacted lanes already sit at their
        # ranks.
        return grow(jnp.where(eff_c > 0, planes_c[0], 0))
    rank, _ = compaction.tile_exclusive_scan(eff_c)
    disp = jnp.where(eff_c > 0, rank - common.flat_index(eff_c), 0)
    nout = out_rows * LANES
    arrs = _route([grow(eff_c), grow(disp), *[grow(p) for p in planes_c]],
                  False, (nout - 1).bit_length())
    eff_x, planes_x = arrs[0], arrs[2:]
    out = jnp.zeros_like(eff_x)
    for j, p in enumerate(planes_x):
        unit = jnp.where(eff_x > j, p, 0)
        out = out | (common.flat_roll(unit, j) if j else unit)
    return out


def stage_decoded(src: Codec, dst: Codec, cp, lead, instream):
    """In-tile compaction of an already-decoded tile: the staging body.

    Returns the compact int32 stage window as ``(stage_width(src, dst) //
    LANES, LANES)`` rows (per tile of a block), units at the front; the
    caller stores it at the tile's base offset.
    """
    eff = jnp.where(lead & instream, dst.unit_len(cp), 0)
    nun = stage_units(src, dst)
    cands = dst.encode(cp)[:nun]
    return _compact_units(eff, cands, stage_width(src, dst) // LANES,
                          src.lanes_lead)


def stage_decoded2(src: Codec, dst: Codec, cp, lead, instream):
    """Class-specialized :func:`stage_decoded` for a ≤2-byte tile.

    Same compaction, but over ``stage_units2`` candidate planes (the
    class bounds every code point's encoding), written straight into the
    general window's rows so the class branches of the dispatch
    ``lax.cond`` agree on shape.
    """
    eff = jnp.where(lead & instream, dst.unit_len(cp), 0)
    nun = stage_units2(src, dst)
    cands = dst.encode(cp)[:nun]
    return _compact_units(eff, cands, stage_width(src, dst) // LANES,
                          src.class2_lanes_lead)


def count_tile(src: Codec, dst: Codec, x, xp, xn, live, gidx, *,
               errors: str, validate: bool):
    """One counting/validating scan of a VMEM tile, any format pair.

    ``live`` is the caller's in-stream mask (single stream: ``gidx < n``;
    ragged: ``gidx < doc_end``).  Returns the three per-tile scalars
    ``(total, err_flag, first_err_gidx)``.
    """
    a, cp, lead = decode_once(src, x, xp, xn, errors=errors,
                              validate=validate)
    return count_decoded(src, dst, a, cp, lead, x, xp, live, gidx,
                         validate=validate)


def ascii_tile_pred(x, xp, lookback: int = 3):
    """Per-tile ASCII fast-path predicate (paper Algorithm 3 at tile
    granularity).

    True when every lane of the tile is plain ASCII AND the boundary
    inflow — the trailing ``lookback`` lanes of the previous tile
    (``src.max_lookback``: 3 for UTF-8, 1 for UTF-16, 0 for the
    fixed-width formats), which are the only lanes whose characters (or
    error subparts) can reach into this tile — is pure ASCII too.  The
    inflow guard is deliberately conservative: a previous tile ending in
    a lead or continuation byte sends the tile down the general path
    even though a pure-ASCII tile can never be claimed by it.  The lower
    bound matters: lanes are int32 here, so a garbage UTF-32 scalar like
    0xFFFFFFFF wraps negative and must not ride the copy path.
    """
    ok = common.all_of((x >= 0) & (x < 0x80))
    if lookback > 0:
        ok = ok & common.all_of(~common.prev_tail(xp, lookback)
                                | ((xp >= 0) & (xp < 0x80)))
    return ok


def onepass_tile(src: Codec, dst: Codec, x, xp, xn, live, gidx, *,
                 errors: str, validate: bool, ascii_skip: bool = True,
                 per_tile: bool = False):
    """Count + stage one tile off a single decode: the one-pass body.

    Returns ``(total, err_flag, first_err_gidx, stage)`` — the three
    per-tile count scalars plus the compact stage window, computed from
    ONE decode/analysis of the tile.  ``x`` may also be a
    ``(G, ROWS, LANES)`` block of G consecutive tiles, with ``xp`` /
    ``xn`` each tile's own neighbours: ``total`` is then a tuple of the
    G tiles' totals, ``stage`` holds one window per tile, and the error
    scalars are folded over the block, or with ``per_tile`` are tuples
    of each tile's own (:func:`count_decoded`).  With ``ascii_skip`` the
    whole body sits behind a nested ``lax.cond`` — the three-way
    tile-class dispatch of DESIGN.md §9, taken once per block:

      ASCII     pure-ASCII tile, pure-ASCII boundary inflow
                (:func:`ascii_tile_pred` over ``src.max_lookback``
                lanes): reduces to a widening copy — live lanes are a
                prefix and dead lanes already zero, so the copy IS the
                compact stage.
      ≤2-byte   every lane in the source's 11-bit class and the inflow
                window clean (``src.class2_pred``): the specialized
                decode2/analyze2 bodies (no 3-/4-unit assembly, no
                surrogate folding, no Keiser-Lemire lookups) feed a
                compaction over fewer candidate planes
                (:func:`stage_decoded2`).
      general   everything else: the full speculative decode / subpart
                analysis / worst-case-width staging.

    Each class is lanewise bit-identical to the general body wherever
    its predicate admits a tile, so dispatch on/off (``ascii_skip``)
    never changes (buffer, count, status).  A block takes a class only
    when every tile of it would (the predicates hold over the whole
    block), so dispatching per block does not change them either.
    """
    width = stage_width(src, dst)

    def general(ops):
        x, xp, xn = ops
        a, cp, lead = decode_once(src, x, xp, xn, errors=errors,
                                  validate=validate)
        tot, err, ferr = count_decoded(src, dst, a, cp, lead, x, xp, live,
                                       gidx, validate=validate,
                                       per_tile=per_tile)
        return tot, err, ferr, stage_decoded(src, dst, cp, lead, live)

    if not ascii_skip:
        return general((x, xp, xn))

    def class2(ops):
        x, xp, xn = ops
        a, cp, lead = decode_once2(src, x, xp, xn, errors=errors,
                                   validate=validate)
        tot, err, ferr = count_decoded2(src, dst, a, cp, lead, live, gidx,
                                        validate=validate,
                                        per_tile=per_tile)
        return tot, err, ferr, stage_decoded2(src, dst, cp, lead, live)

    def ascii(ops):
        x, _xp, _xn = ops
        # ASCII lanes are 1 destination unit in every matrix format and
        # never claim (or get claimed by) a neighbour; dead lanes are
        # zeros, so the tile is already the front of the compact window.
        tot = common.tile_sums(live.astype(jnp.int32))
        rows = width // LANES - x.shape[-2]
        pad = jnp.zeros(x.shape[:-2] + (rows, LANES), jnp.int32)
        return tot, *_no_errors(x, per_tile), \
            jnp.concatenate([x, pad], axis=-2) if rows else x

    if src.class2_pred is None:
        inner = general
    else:
        def inner(ops):
            return jax.lax.cond(src.class2_pred(ops[0], ops[1]),
                                class2, general, ops)

    return jax.lax.cond(ascii_tile_pred(x, xp, src.max_lookback),
                        ascii, inner, (x, xp, xn))
