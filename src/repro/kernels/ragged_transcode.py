"""Ragged packed-batch transcode: one Pallas launch for a whole batch,
any cell of the codec matrix.

The padded-vmap batch path (``data/pipeline.py`` ``strategy="vmap"``)
maps the single-document fused pipeline over a ``[B, L]`` buffer: B
separate grid dispatches, and every document — however short — scans all
``ceil(L/1024)`` of its tiles.  The packed path removes both costs.
Documents are concatenated into ONE tile-aligned narrow buffer
(``repro.core.packing``), and the *same* generic count/write tile bodies
(``repro.kernels.stages.driver``) run as a single grid launch over the
packed stream.  Per-document bookkeeping is all per-tile scalars:

  Ownership map    ``packing.tile_ownership`` (on device): tile ->
                   owning document (a searchsorted over the [B+1] offset
                   vector), the tile's document-end offset (the live
                   mask), and same-document neighbour flags.

  Count pass       One grid launch over all tiles of all documents.
                   The kernels differ from the single-stream ones in
                   precisely two multiplies: neighbour-tile inflow is
                   zeroed when the neighbour belongs to a different
                   document (``xp * same_prev`` / ``xn * same_next`` —
                   a character must never claim elements across a
                   document boundary), and the live mask compares
                   against the tile's own document end.

  Segment scan     The per-tile totals feed the SAME nblk-element
                   exclusive cumsum as the single-stream pipeline
                   (``compaction.tile_base_offsets``): because documents
                   are packed in order, the dense global scan IS the
                   per-document segment scan.

  Write pass       One grid launch; each tile compacts in VMEM and
                   stores at ``base[tile]`` exactly as the single-stream
                   writer.

  Per-doc reduce   counts = segment_sum(totals), error flags
                   segment_max, first-error offsets segment_min (the
                   NO_ERR_SENTINEL is int32 max, which is also
                   ``segment_min``'s empty-segment fill — zero-length
                   documents come out valid for free); statuses get the
                   document-relative offset with the same
                   ``status_from_first`` fold as the single-doc path.

Status/errors semantics are exactly :class:`repro.core.result.
TranscodeResult`'s, per document.  Every document's output slice is
bit-identical to running the single-document fused transcoder on that
document alone (pinned by ``tests/test_differential.py``).

The description above is the two-launch (``strategy="fused"``) form.
The DEFAULT is now ``strategy="onepass"`` (DESIGN.md §9): the count and
write bodies run in ONE grid launch off ONE decode per tile, with the
inter-tile/segment scan carried as a scalar in SMEM scratch across the
sequential grid — because documents are packed in order, the global
running offset IS the per-document segment scan, and the per-document
ownership masks (cross-document inflow zeroing + per-tile live ends) are
exactly the "per-tile ownership resets" the carry needs.  Per-tile
``(total, err, ferr)`` scalars still leave the kernel — they are the
*product* (the per-document segment reductions consume them), not
inter-pass coordination.

Like the single-stream one-pass kernel, each grid step handles a block
of G consecutive tiles, ``(G, ROWS, LANES)``, G from the batch's tile
count (``onepass_transcode.tiles_per_step`` up to ``RAGGED_G_MAX``).
A block usually spans several documents, so ownership stays per tile
inside it: tile j's neighbour inflow is zeroed by its own
``same_prev`` / ``same_next``, its live mask ends at its own document's
end, and its ``(total, err, ferr)`` come out on their own instead of
folded over the block.  The three-way class dispatch (ASCII copy /
narrowed ≤2-byte body / general, DESIGN.md §9) is taken once per block:
a block takes a class only when each of its tiles would, so an ASCII
document that shares a block with a CJK one runs the general body
there, with the same result.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import spans
from repro.core import compaction, packing
from repro.core import result as R
from repro.kernels import fused_transcode as ft
from repro.kernels import onepass_transcode as op
from repro.kernels import runtime
from repro.kernels import stages
from repro.kernels.stages import driver as sdrv
from repro.testing import faults

ROWS = ft.ROWS
LANES = ft.LANES
BLOCK = ft.BLOCK

_IMAX = R.NO_ERR_SENTINEL

_SMEM = ft._SMEM
_tile_spec = ft._tile_spec

_check_errors = R.check_errors_policy

# The single-pass launch's name, in the profiler trace of the chip as in
# the HLO.
ONEPASS_KERNEL_NAME = "ragged_onepass_transcode"


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
# Generic ragged kernels: the single-stream generic bodies plus the
# ownership masking (cross-document neighbour inflow zeroed).


def _owned_tiles(end_ref, sp_ref, sn_ref, xp_ref, x_ref, xn_ref):
    """The tile (or the ``(G, ROWS, LANES)`` block of tiles) and each
    tile's neighbours under the ownership map: inflow from a neighbour
    tile of a DIFFERENT document reads as zeros, exactly like the zero
    boundary tiles of the single-stream pipeline, and each tile's live
    mask ends at its own document's end.  Returns ``(x, xp, xn, live,
    gidx)``."""
    i = pl.program_id(0)
    x, xp, xn = op._load_block(xp_ref, x_ref, xn_ref)
    gidx = ft._gidx(x.shape)
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
                   tot_ref, err_ref, ferr_ref, *, src, dst, errors,
                   validate):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    x, xp, xn, live, gidx = _owned_tiles(end_ref, sp_ref, sn_ref, xp_ref,
                                         x_ref, xn_ref)
    tot_ref[i], err_ref[i], ferr_ref[i] = sdrv.count_tile(
        codec_s, codec_d, x, xp, xn, live, gidx, errors=errors,
        validate=validate)


def _rwrite_kernel(end_ref, sp_ref, sn_ref, base_ref, tot_ref,
                   xp_ref, x_ref, xn_ref, out_ref, buf, pend, sem, *, src,
                   dst, errors):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    x, xp, xn, live, _gidx = _owned_tiles(end_ref, sp_ref, sn_ref, xp_ref,
                                          x_ref, xn_ref)
    stage = sdrv.write_stage(codec_s, codec_d, x, xp, xn, live,
                             errors=errors)
    ft.store_window(stage, base_ref[i], tot_ref[i], out_ref, buf, pend, sem)


def _launch_geometry(data, offsets, lengths, g=1):
    """ONE definition of the ragged launch setup, shared by every ragged
    kernel call (count/write/onepass): the ownership map, the masked +
    boundary-tiled data, and the matching in_specs/operand prefix.
    Desynchronizing these between the bodies would compute base offsets
    on a different tiling than the writer stores with.

    With ``g > 1`` the data is tiled in blocks of ``g`` tiles (as
    ``op._block_specs(g)`` reads it) and the ownership vectors are
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


_OWNED_SPECS = [_SMEM, _SMEM, _SMEM]
_TILE_SPECS = [_tile_spec(0), _tile_spec(1), _tile_spec(2)]


def _rcount_call(data, offsets, lengths, src, dst, errors, validate,
                 interpret):
    nblk, tile_doc, owned, d3 = _launch_geometry(data, offsets, lengths)
    ft.check_launch(nblk, stages.get_pair(src, dst)[2], 6, interpret,
                    "ragged scan")
    kernel = functools.partial(_rcount_kernel, src=src, dst=dst,
                               errors=errors, validate=validate)
    per_tile = jax.ShapeDtypeStruct((nblk,), jnp.int32)
    totals, errs, ferrs = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=_OWNED_SPECS + _TILE_SPECS,
        out_specs=[_SMEM, _SMEM, _SMEM],
        out_shape=[per_tile, per_tile, per_tile],
        compiler_params=ft._SEQUENTIAL,
        interpret=interpret,
    )(*owned, d3, d3, d3)
    return nblk, d3, tile_doc, owned, totals, errs, ferrs


# ---------------------------------------------------------------------------
# Single-pass ragged kernel (strategy="onepass", the default): count +
# write in one grid launch off one decode, base offsets carried in SMEM.


def _ronepass_kernel(end_ref, sp_ref, sn_ref, xp_ref, x_ref, xn_ref,
                     out_ref, tot_ref, err_ref, ferr_ref, buf, pend, sem,
                     carry, *, src, dst, errors, validate, ascii_skip):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    g = x_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        carry[0] = 0

    # Ownership masking, exactly as the two-launch kernels, tile by tile
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
    ft.store_window(stage, base, tot, out_ref, buf, pend, sem)
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
    g = op.tiles_per_step(data.shape[0], op.RAGGED_G_MAX)
    nblk, tile_doc, owned, d3 = _launch_geometry(data, offsets, lengths, g)
    ft.check_launch(nblk, factor, 6, interpret, "ragged transcode")
    kernel = functools.partial(_ronepass_kernel, src=src, dst=dst,
                               errors=errors, validate=validate,
                               ascii_skip=True)
    padded = owned[0].shape[0]
    per_tile = jax.ShapeDtypeStruct((padded,), jnp.int32)
    out_spec, out_shape, scratch = ft.window_out(padded, width,
                                                 codec_d.dtype, g)
    with jax.named_scope(ONEPASS_KERNEL_NAME):
        outp, totals, errs, ferrs = pl.pallas_call(
            kernel,
            grid=(padded // g,),
            in_specs=_OWNED_SPECS + op._block_specs(g),
            out_specs=[out_spec, _SMEM, _SMEM, _SMEM],
            out_shape=[out_shape, per_tile, per_tile, per_tile],
            scratch_shapes=scratch + [pltpu.SMEM((1,), jnp.int32)],
            compiler_params=ft._SEQUENTIAL,
            interpret=interpret,
            name=ONEPASS_KERNEL_NAME,
        )(*owned, d3, d3, d3)
    outp = outp.reshape(-1)
    # The block padding's dead tiles hold nothing.
    totals, errs, ferrs = totals[:nblk], errs[:nblk], ferrs[:nblk]
    total = jnp.sum(totals)
    cap = factor * nblk * BLOCK
    outp = outp[:cap]
    outp = jnp.where(jnp.arange(cap) < total, outp,
                     jnp.zeros((), codec_d.dtype))
    counts, out_offsets, statuses = _doc_reduce(
        totals, errs, ferrs, tile_doc, offsets, validate)
    return R.RaggedTranscodeResult(outp, out_offsets, counts, statuses)


@functools.partial(jax.jit, static_argnames=("src", "dst", "validate",
                                             "interpret", "errors"))
def _ragged_impl(data, offsets, lengths, src, dst, validate, interpret,
                 errors):
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    width = stages.stage_width(codec_s, codec_d)
    nblk, d3, tile_doc, owned, totals, errs, ferrs = _rcount_call(
        data, offsets, lengths, src, dst, errors, validate, interpret)
    base, total = compaction.tile_base_offsets(totals)
    out_spec, out_shape, scratch = ft.window_out(nblk, width, codec_d.dtype)
    outp = pl.pallas_call(
        functools.partial(_rwrite_kernel, src=src, dst=dst, errors=errors),
        grid=(nblk,),
        in_specs=_OWNED_SPECS + [_SMEM, _SMEM] + _TILE_SPECS,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=ft._SEQUENTIAL,
        interpret=interpret,
    )(*owned, base, totals, d3, d3, d3).reshape(-1)
    # Same capacity budget per document as the padded-vmap path (its
    # tile span); clear the write-window slack after the last tile.
    cap = factor * nblk * BLOCK
    outp = outp[:cap]
    outp = jnp.where(jnp.arange(cap) < total, outp,
                     jnp.zeros((), codec_d.dtype))
    counts, out_offsets, statuses = _doc_reduce(
        totals, errs, ferrs, tile_doc, offsets, validate)
    return R.RaggedTranscodeResult(outp, out_offsets, counts, statuses)


@functools.partial(jax.jit, static_argnames=("src", "dst", "interpret"))
def _ragged_scan_impl(data, offsets, lengths, src, dst, interpret):
    _nb, _d3, tile_doc, _owned, totals, errs, ferrs = _rcount_call(
        data, offsets, lengths, src, dst, "strict", True, interpret)
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
                     interpret=None, strategy: str = "onepass"):
    """Ragged packed-batch transcode for any (src, dst) matrix cell.

    ``data``/``offsets``/``lengths`` is the tile-aligned packed layout of
    :func:`repro.core.packing.pack_documents`.  Returns a
    :class:`repro.core.result.RaggedTranscodeResult`: a dense output
    stream in the destination's narrow dtype plus per-document
    ``(offsets, counts, statuses)`` — each document's slice is
    bit-identical to the single-document fused transcoder's
    ``buffer[:count]`` / ``count`` / ``status``.

    ``strategy="onepass"`` (default) runs the batch as ONE grid launch
    with the segment scan carried in SMEM (one read + one decode of the
    packed stream); ``strategy="fused"`` keeps the two-launch
    count/cumsum/write reference.  Both are bit-identical per document.
    """
    _check_errors(errors)
    faults.fire(faults.KERNEL_RAGGED)    # chaos-suite hook (no-op in prod)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    data, offsets, lengths = _as_packed(data, offsets, lengths,
                                        codec_s.dtype)
    if strategy not in ("onepass", "fused"):
        raise ValueError(
            f"transcode_ragged: unknown strategy {strategy!r} "
            f"(expected 'onepass' or 'fused')")
    impl = _ragged_onepass_impl if strategy == "onepass" else _ragged_impl
    with jax.profiler.TraceAnnotation(spans.RAGGED_DISPATCH):
        return impl(data, offsets, lengths, src, dst, validate,
                    runtime.resolve_interpret(interpret), errors)


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


# ---------------------------------------------------------------------------
# Thin per-pair instantiations (the pre-matrix public API).


def utf8_to_utf16_ragged(data, offsets, lengths, *, validate: bool = True,
                         errors: str = "strict", interpret=None,
                         strategy: str = "onepass"):
    """Ragged packed-batch UTF-8 -> UTF-16: one launch per batch."""
    return transcode_ragged(data, offsets, lengths, src="utf8", dst="utf16",
                            validate=validate, errors=errors,
                            interpret=interpret, strategy=strategy)


def utf8_scan_ragged(data, offsets, lengths, *, interpret=None):
    """Counting pass only, per document: ``(counts, statuses)``."""
    return scan_ragged(data, offsets, lengths, src="utf8", dst="utf16",
                       interpret=interpret)


def utf16_to_utf8_ragged(data, offsets, lengths, *, validate: bool = True,
                         errors: str = "strict", interpret=None,
                         strategy: str = "onepass"):
    """Ragged packed-batch UTF-16 -> UTF-8: one launch per batch."""
    return transcode_ragged(data, offsets, lengths, src="utf16", dst="utf8",
                            validate=validate, errors=errors,
                            interpret=interpret, strategy=strategy)


def utf16_scan_ragged(data, offsets, lengths, *, interpret=None):
    """Counting pass only, per document: ``(counts, statuses)``."""
    return scan_ragged(data, offsets, lengths, src="utf16", dst="utf8",
                       interpret=interpret)
