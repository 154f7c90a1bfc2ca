"""Ragged packed-batch transcode: one Pallas launch for a whole batch,
any cell of the codec matrix.

Documents are concatenated into ONE tile-aligned narrow buffer
(``repro.core.packing``), and the *same* generic tile bodies
(``repro.kernels.stages.driver``) run as a single grid launch over the
packed stream, so no document scans the padding of a fixed-width
``[B, L]`` batch and the batch pays one dispatch.  Per-document
bookkeeping is all per-tile scalars:

  Ownership map    ``packing.tile_ownership`` (on device): tile ->
                   owning document (a searchsorted over the [B+1] offset
                   vector), the tile's document-end offset (the live
                   mask), and same-document neighbour flags.

  One launch       The kernel differs from the single-stream one in
                   precisely two multiplies: neighbour-tile inflow is
                   zeroed when the neighbour belongs to a different
                   document (``xp * same_prev`` / ``xn * same_next`` —
                   a character must never claim elements across a
                   document boundary), and the live mask compares
                   against the tile's own document end.  Count and
                   write run off ONE decode per tile, with the output
                   offset carried as a scalar in SMEM scratch across the
                   sequential grid (DESIGN.md §9): because documents are
                   packed in order, the global running offset IS the
                   per-document segment scan.  Per-tile ``(total, err,
                   ferr)`` scalars still leave the kernel — they are the
                   *product* (the per-document reduction consumes them),
                   not inter-pass coordination.

  Per-doc reduce   counts = segment_sum(totals), error flags
                   segment_max, first-error offsets segment_min (the
                   NO_ERR_SENTINEL is int32 max, which is also
                   ``segment_min``'s empty-segment fill — zero-length
                   documents come out valid for free); statuses get the
                   document-relative offset with the same
                   ``status_from_first`` fold as the single-doc path.

Status/errors semantics are exactly :class:`repro.core.result.
TranscodeResult`'s, per document.  Every document's output slice is
bit-identical to running the single-document transcoder on that
document alone (pinned by ``tests/test_differential.py``).

Like the single-stream kernel, each grid step handles a block of G
consecutive tiles, ``(G, ROWS, LANES)``, G from the batch's tile count
(``window.tiles_per_step`` up to ``RAGGED_G_MAX``).  A block usually
spans several documents, so ownership stays per tile inside it: tile
j's neighbour inflow is zeroed by its own ``same_prev`` /
``same_next``, its live mask ends at its own document's end, and its
``(total, err, ferr)`` come out on their own instead of folded over the
block.  The three-way class dispatch (ASCII copy / narrowed ≤2-byte
body / general, DESIGN.md §9) is taken once per block: a block takes a
class only when each of its tiles would, so an ASCII document that
shares a block with a CJK one runs the general body there, with the
same result.

:func:`scan_ragged` is the counting kernel alone, one tile per grid
step, with the same ownership masking and per-document reduction.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import spans
from repro.core import packing
from repro.core import result as R
from repro.kernels import runtime
from repro.kernels import stages
from repro.kernels import window
from repro.kernels.stages import driver as sdrv
from repro.testing import faults

ROWS = window.ROWS
LANES = window.LANES
BLOCK = window.BLOCK

_IMAX = R.NO_ERR_SENTINEL

_check_errors = R.check_errors_policy

# The single-pass launch's name, in the profiler trace of the chip as in
# the HLO.
ONEPASS_KERNEL_NAME = "ragged_onepass_transcode"

# Most 1 KiB tiles one grid step handles (a power of two).  Blocks span
# documents, so nearly every block of a packed batch takes the general
# body.  On the chip, per 1,024-tile batch of packed UTF-8 documents to
# UTF-16 with errors="replace": 3.265 ms at G=1, 0.828 at 8, 0.757 at
# 16, 0.743 at 32, compiling in 2.0, 3.5, 5.6 and 10.3 s.  G=16 is
# within 3% of the best, but its compile raised the batch program's
# set-up by 16%; G=8 gives up 9% of kernel time, about 1% of a batch
# (PERF.md).
RAGGED_G_MAX = 8


def _nblk(total: int) -> int:
    return max(1, -(-total // BLOCK))


def _mask_to_docs(data, tile_end, nblk):
    """Zero every lane at or past its owning document's end.

    The packed layout already zero-fills inter-document slack; this is
    the defensive equivalent of the single-document wrappers' padding
    mask (``where(idx < n, x, 0)``), so garbage beyond a document's
    logical length can never leak into its neighbours' analysis.
    """
    pad = nblk * BLOCK - data.shape[0]
    d = jnp.concatenate([data, jnp.zeros((pad,), data.dtype)])
    lane_end = jnp.repeat(tile_end, BLOCK)
    live = jnp.arange(nblk * BLOCK, dtype=jnp.int32) < lane_end
    return jnp.where(live, d, jnp.zeros_like(d))


def _doc_reduce(totals, errs, ferrs, tile_doc, offsets, validate):
    """Per-tile scalars -> per-document (counts, out_offsets, statuses)."""
    n_docs = offsets.shape[0] - 1
    counts = jax.ops.segment_sum(totals, tile_doc, num_segments=n_docs)
    out_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts).astype(jnp.int32)])
    if not validate:
        statuses = jnp.full((n_docs,), R.STATUS_OK, jnp.int32)
        return counts, out_offsets, statuses
    # segment_min fills empty segments with int32 max == NO_ERR_SENTINEL
    # and segment_max with int32 min: zero-tile (empty) documents reduce
    # to a clean status without special-casing.
    err_doc = jax.ops.segment_max(errs, tile_doc, num_segments=n_docs)
    ferr_doc = jax.ops.segment_min(ferrs, tile_doc, num_segments=n_docs)
    first_rel = jnp.where(ferr_doc == _IMAX, _IMAX, ferr_doc - offsets[:-1])
    statuses = R.status_from_first(first_rel, err_doc > 0)
    return counts, out_offsets, statuses


# ---------------------------------------------------------------------------
# Ownership masking (cross-document neighbour inflow zeroed), shared by
# the counting kernel and the single-pass kernel.


def _owned_tiles(end_ref, sp_ref, sn_ref, xp_ref, x_ref, xn_ref):
    """The tile (or the ``(G, ROWS, LANES)`` block of tiles) and each
    tile's neighbours under the ownership map: inflow from a neighbour
    tile of a DIFFERENT document reads as zeros, exactly like the zero
    boundary tiles of the single-stream pipeline, and each tile's live
    mask ends at its own document's end.  Returns ``(x, xp, xn, live,
    gidx)``."""
    i = pl.program_id(0)
    x, xp, xn = window.load_block(xp_ref, x_ref, xn_ref)
    gidx = window.gidx(x.shape)
    if x.ndim == 2:
        return x, xp * sp_ref[i], xn * sn_ref[i], gidx < end_ref[i], gidx
    g = x.shape[0]

    def per_tile(ref):
        # Tile j's scalar of the block, broadcast over its lanes.
        return jnp.stack([jnp.full(x.shape[1:], ref[i * g + j], jnp.int32)
                          for j in range(g)])

    return (x, xp * per_tile(sp_ref), xn * per_tile(sn_ref),
            gidx < per_tile(end_ref), gidx)


def _rcount_kernel(end_ref, sp_ref, sn_ref, xp_ref, x_ref, xn_ref,
                   tot_ref, err_ref, ferr_ref, *, src, dst):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    x, xp, xn, live, gidx = _owned_tiles(end_ref, sp_ref, sn_ref, xp_ref,
                                         x_ref, xn_ref)
    tot_ref[i], err_ref[i], ferr_ref[i] = sdrv.count_tile(
        codec_s, codec_d, x, xp, xn, live, gidx, errors="strict",
        validate=True)


def _launch_geometry(data, offsets, lengths, g=1):
    """ONE definition of the ragged launch setup, shared by both ragged
    kernel calls (count/onepass): the ownership map, the masked +
    boundary-tiled data, and the matching in_specs/operand prefix, so
    that a scan counts exactly the tiles a transcode writes.

    With ``g > 1`` the data is tiled in blocks of ``g`` tiles (as
    ``window.block_specs(g)`` reads it) and the ownership vectors are
    padded to whole blocks with dead tiles (``tile_end`` 0, no
    neighbours).  Returns ``(nblk, tile_doc, owned, d3)``, ``nblk`` the
    data's own tiles.
    """
    nblk = _nblk(data.shape[0])
    tile_doc, tile_end, same_prev, same_next = packing.tile_ownership(
        offsets, lengths, nblk, BLOCK)
    dm = _mask_to_docs(data, tile_end, nblk)
    d3, padded = runtime.tile_with_boundaries(dm, ROWS, LANES,
                                              boundary_tiles=2, block=g)
    owned = (tile_end, same_prev, same_next)
    if padded > nblk:
        owned = tuple(jnp.pad(v, (0, padded - nblk)) for v in owned)
    return nblk, tile_doc, owned, d3


_OWNED_SPECS = [window.SMEM, window.SMEM, window.SMEM]
_TILE_SPECS = [window.tile_spec(0), window.tile_spec(1), window.tile_spec(2)]


def _rcount_call(data, offsets, lengths, src, dst, interpret):
    nblk, tile_doc, owned, d3 = _launch_geometry(data, offsets, lengths)
    window.check_launch(nblk, stages.get_pair(src, dst)[2], 6, interpret,
                        "ragged scan")
    kernel = functools.partial(_rcount_kernel, src=src, dst=dst)
    per_tile = jax.ShapeDtypeStruct((nblk,), jnp.int32)
    totals, errs, ferrs = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=_OWNED_SPECS + _TILE_SPECS,
        out_specs=[window.SMEM, window.SMEM, window.SMEM],
        out_shape=[per_tile, per_tile, per_tile],
        compiler_params=window.SEQUENTIAL,
        interpret=interpret,
    )(*owned, d3, d3, d3)
    return tile_doc, totals, errs, ferrs


# ---------------------------------------------------------------------------
# Single-pass ragged kernel: count + write in one grid launch off one
# decode, base offsets carried in SMEM.


def _ronepass_kernel(end_ref, sp_ref, sn_ref, xp_ref, x_ref, xn_ref,
                     out_ref, tot_ref, err_ref, ferr_ref, buf, pend, sem,
                     carry, *, src, dst, errors, validate, ascii_skip):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    g = x_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        carry[0] = 0

    # Ownership masking, exactly as the counting kernel, tile by tile
    # inside the block: cross-document neighbour inflow reads as zeros.
    # (The zeroed inflow also lets the ASCII predicate pass at document
    # starts — a document boundary is a clean inflow by construction.)
    x, xp, xn, live, gidx = _owned_tiles(end_ref, sp_ref, sn_ref, xp_ref,
                                         x_ref, xn_ref)
    tot, err, ferr, stage = sdrv.onepass_tile(
        codec_s, codec_d, x, xp, xn, live, gidx, errors=errors,
        validate=validate, ascii_skip=ascii_skip, per_tile=True)

    # Documents are packed in order, so the global running offset IS the
    # per-document segment scan (dense output, no inter-doc padding).
    base = carry[0]
    window.store_window(stage, base, tot, out_ref, buf, pend, sem)
    if g == 1:
        tot, err, ferr = (tot,), (err,), (ferr,)
    carry[0] = base + sum(tot)
    # Per-tile scalars are the per-document reduction's INPUT (segment
    # sum/min/max downstream), not inter-pass coordination; each tile of
    # the block keeps its own, since its neighbours may belong to other
    # documents.
    for j in range(g):
        tot_ref[i * g + j], err_ref[i * g + j], ferr_ref[i * g + j] = \
            tot[j], err[j], ferr[j]


@functools.partial(jax.jit, static_argnames=("src", "dst", "validate",
                                             "interpret", "errors"))
def _ragged_onepass_impl(data, offsets, lengths, src, dst, validate,
                         interpret, errors):
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    width = stages.stage_width(codec_s, codec_d)
    g = window.tiles_per_step(data.shape[0], RAGGED_G_MAX)
    nblk, tile_doc, owned, d3 = _launch_geometry(data, offsets, lengths, g)
    window.check_launch(nblk, factor, 6, interpret, "ragged transcode")
    kernel = functools.partial(_ronepass_kernel, src=src, dst=dst,
                               errors=errors, validate=validate,
                               ascii_skip=True)
    padded = owned[0].shape[0]
    per_tile = jax.ShapeDtypeStruct((padded,), jnp.int32)
    out_spec, out_shape, scratch = window.window_out(padded, width,
                                                     codec_d.dtype, g)
    with jax.named_scope(ONEPASS_KERNEL_NAME):
        outp, totals, errs, ferrs = pl.pallas_call(
            kernel,
            grid=(padded // g,),
            in_specs=_OWNED_SPECS + window.block_specs(g),
            out_specs=[out_spec, window.SMEM, window.SMEM, window.SMEM],
            out_shape=[out_shape, per_tile, per_tile, per_tile],
            scratch_shapes=scratch + [pltpu.SMEM((1,), jnp.int32)],
            compiler_params=window.SEQUENTIAL,
            interpret=interpret,
            name=ONEPASS_KERNEL_NAME,
        )(*owned, d3, d3, d3)
    outp = outp.reshape(-1)
    # The block padding's dead tiles hold nothing.
    totals, errs, ferrs = totals[:nblk], errs[:nblk], ferrs[:nblk]
    total = jnp.sum(totals)
    outp = window.clip_to_cap(outp, factor * nblk * BLOCK, total,
                              codec_d.dtype)
    counts, out_offsets, statuses = _doc_reduce(
        totals, errs, ferrs, tile_doc, offsets, validate)
    return R.RaggedTranscodeResult(outp, out_offsets, counts, statuses)


@functools.partial(jax.jit, static_argnames=("src", "dst", "interpret"))
def _ragged_scan_impl(data, offsets, lengths, src, dst, interpret):
    tile_doc, totals, errs, ferrs = _rcount_call(
        data, offsets, lengths, src, dst, interpret)
    counts, _oo, statuses = _doc_reduce(
        totals, errs, ferrs, tile_doc, offsets, True)
    return counts, statuses


@functools.partial(jax.profiler.annotate_function,
                   name=spans.RAGGED_CHECK_LAYOUT)
def _as_packed(data, offsets, lengths, dtype):
    data = jnp.asarray(data)
    if data.dtype != dtype:
        data = data.astype(dtype)
    offsets = jnp.asarray(offsets, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if offsets.ndim != 1 or offsets.shape[0] < 2:
        raise ValueError("offsets must be [B+1] with B >= 1")
    if lengths.shape[0] != offsets.shape[0] - 1:
        raise ValueError(
            f"lengths [B] must match offsets [B+1]: "
            f"{lengths.shape[0]} vs {offsets.shape[0]}")
    # Layout invariants are cheap to check on the host and silently
    # corrupt per-document results when violated (a mid-tile start makes
    # tile_ownership assign the tile to the wrong document).  Skip under
    # tracing — jitted callers (e.g. the pipeline batch entries) build
    # offsets from static shapes, which satisfy the invariants by
    # construction.
    if not isinstance(offsets, jax.core.Tracer) \
            and not isinstance(lengths, jax.core.Tracer):
        off_h = np.asarray(offsets)
        len_h = np.asarray(lengths)
        spans = np.diff(off_h)
        if off_h[0] != 0 or (off_h % BLOCK).any() or (spans < 0).any():
            raise ValueError(
                f"offsets must start at 0, be non-decreasing and "
                f"tile-aligned (multiples of {BLOCK}); use "
                f"repro.core.packing.pack_documents")
        if off_h[-1] > data.shape[0]:
            raise ValueError(
                f"data ({data.shape[0]} elements) does not cover "
                f"offsets[-1] ({int(off_h[-1])}): truncated documents "
                f"would silently report as empty and valid")
        if (len_h < 0).any() or (len_h > spans).any():
            raise ValueError(
                "lengths must fit within their documents' offset spans")
    return data, offsets, lengths


def transcode_ragged(data, offsets, lengths, *, src: str, dst: str,
                     validate: bool = True, errors: str = "strict",
                     interpret=None):
    """Ragged packed-batch transcode for any (src, dst) matrix cell.

    ``data``/``offsets``/``lengths`` is the tile-aligned packed layout of
    :func:`repro.core.packing.pack_documents`.  Returns a
    :class:`repro.core.result.RaggedTranscodeResult`: a dense output
    stream in the destination's narrow dtype plus per-document
    ``(offsets, counts, statuses)`` — each document's slice is
    bit-identical to the single-document transcoder's
    ``buffer[:count]`` / ``count`` / ``status``.

    The batch runs as ONE grid launch with the segment scan carried in
    SMEM (one read + one decode of the packed stream).
    """
    _check_errors(errors)
    faults.fire(faults.KERNEL_RAGGED)    # chaos-suite hook (no-op in prod)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    data, offsets, lengths = _as_packed(data, offsets, lengths,
                                        codec_s.dtype)
    with jax.profiler.TraceAnnotation(spans.RAGGED_DISPATCH):
        return _ragged_onepass_impl(data, offsets, lengths, src, dst,
                                    validate,
                                    runtime.resolve_interpret(interpret),
                                    errors)


def scan_ragged(data, offsets, lengths, *, src: str, dst: str,
                interpret=None):
    """Counting pass only, per document: ``(counts, statuses)``.

    One read of the packed batch yields every document's destination
    capacity and first-error status — the multi-request
    ingestion-boundary query (serve ingress validates a whole wave of
    prompts with one launch).
    """
    faults.fire(faults.KERNEL_RAGGED_SCAN)  # chaos-suite hook (no-op)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    data, offsets, lengths = _as_packed(data, offsets, lengths,
                                        codec_s.dtype)
    return _ragged_scan_impl(data, offsets, lengths, src, dst,
                             runtime.resolve_interpret(interpret))
