"""Fused two-pass Pallas transcode pipeline (strategy ``"fused"``) —
pair-agnostic over the codec matrix.

This is the hierarchical, in-kernel answer to the global cumsum+scatter
compaction of ``repro.core.transcode`` (DESIGN.md §5), generalized from
two hardwired format pairs to the full decode×encode matrix of
``repro.kernels.stages`` (DESIGN.md §8).  Nothing full-capacity and
nothing int32 ever leaves the kernels:

  Pass 1 (count)   Each grid step speculatively decodes its VMEM tile
                   through the *source* codec's decode stage, lengths it
                   through the *destination* codec's encode stage, and
                   emits THREE scalars — the tile's total output length,
                   a fused validation flag, and the tile's first-error
                   offset.  Validation is *folded into this scan*
                   (DESIGN.md §4): the source's maximal-subpart analysis
                   locates the first ill-formed sequence with Python
                   ``UnicodeDecodeError.start`` semantics, the
                   destination's encode-error map folds in unencodable
                   scalars (Latin-1 egress), and the source's extra
                   detector (the Keiser-Lemire nibble tables for UTF-8)
                   rides along in registers.  No standalone validation
                   pass re-reads the input.  HBM egress: 12 bytes per
                   1024-element tile.

  Inter-tile scan  An ``nblk``-element exclusive cumsum over the per-tile
                   totals (``compaction.tile_base_offsets``) yields each
                   tile's base offset in the compact output.  This is the
                   only global coordination: nblk scalars, not N lanes.

  Pass 2 (write)   Each grid step re-decodes its tile (decode is cheap;
                   bandwidth is not), compacts it *inside VMEM* with
                   log-step routing networks of lane rotations — the
                   hierarchical equivalent of AVX-512 ``vpcompressb``
                   compress-store — and DMAs the compact tile to
                   ``base[tile]`` of the HBM output (``store_window``).

Per-tile staging widths are sized for the SPECULATIVE worst case, derived
per pair by ``stages.driver.stage_units`` (the destination's unit length
at the source's largest fabricable code point).  The derivation replaced
hand-sized per-pair constants and fixed a real overflow: the old
UTF-16→UTF-8 bound of ``3*BLOCK + 1`` undersized surrogate-flood garbage,
where EVERY lane folds to a supplementary pair code point and claims 4
candidate bytes (``4*BLOCK`` per tile).

Error semantics (the ``errors=`` policy, DESIGN.md §4) and the
interpreter/compiled execution notes are unchanged from the two-pair
pipeline; see the strategy table in DESIGN.md §5 and the codec matrix in
§8.  I/O dtypes are narrow end-to-end (uint8/uint16/uint32 by format);
lanes widen to int32 only inside VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import compaction
from repro.core import result as R
from repro.kernels import runtime
from repro.kernels import stages
from repro.kernels.stages import common
from repro.kernels.stages import driver as sdrv
from repro.testing import faults

ROWS = sdrv.ROWS
LANES = sdrv.LANES
BLOCK = sdrv.BLOCK

_IMAX = R.NO_ERR_SENTINEL


def _tile(x):
    """Pad flat narrow array to whole tiles + one zero boundary tile/side."""
    return runtime.tile_with_boundaries(x, ROWS, LANES, boundary_tiles=2)


def _gidx(shape):
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


_check_errors = R.check_errors_policy


# Shared BlockSpecs: one definition of the tile geometry / neighbour-tile
# offset convention for the count and write passes of every pair —
# desynchronizing them would compute base offsets on a different tiling
# than the writer stores with.
def _tile_spec(off):
    """Current/prev/next tile of the (nblk+2, ROWS, LANES) padded array."""
    return pl.BlockSpec((1, ROWS, LANES), lambda i, off=off: (i + off, 0, 0))


def _load(ref):
    """A tile block as the ``(ROWS, LANES)`` int32 lanes the stages read."""
    return ref[0].astype(jnp.int32)


# Scalars and per-tile vectors live whole in SMEM and are indexed by grid
# step: a (1,)-element VMEM block per tile does not lower, and SMEM is
# where the scalar core reads offsets and writes counts anyway.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

# Every grid here runs in order on one core: the one-pass kernels carry
# the running output offset across steps, and every writer carries the
# partially filled output chunk (``_store_window``).  DESIGN.md §9.
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

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
# Generic kernels: ONE count body and ONE write body serve every
# (src, dst) cell of the codec matrix; the format pair is a static
# parameter resolved through the stages registry.  The per-tile bodies
# are free functions of VMEM-resident arrays so the ragged packed-batch
# kernels (``repro.kernels.ragged_transcode``) run EXACTLY the same scan
# with a per-document live mask — one definition of the transcode per
# pair, two launch geometries (single stream / packed batch).


def _count_kernel(n_ref, xp_ref, x_ref, xn_ref, tot_ref, err_ref, ferr_ref,
                  *, src, dst, errors, validate):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    x = _load(x_ref)
    gidx = _gidx(x.shape)
    tot_ref[i], err_ref[i], ferr_ref[i] = sdrv.count_tile(
        codec_s, codec_d, x, _load(xp_ref), _load(xn_ref), gidx < n_ref[0],
        gidx, errors=errors, validate=validate)


def _write_kernel(n_ref, base_ref, tot_ref, xp_ref, x_ref, xn_ref, out_ref,
                  buf, pend, sem, *, src, dst, errors):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    x = _load(x_ref)
    stage = sdrv.write_stage(codec_s, codec_d, x, _load(xp_ref),
                             _load(xn_ref), _gidx(x.shape) < n_ref[0],
                             errors=errors)
    store_window(stage, base_ref[i], tot_ref[i], out_ref, buf, pend, sem)


def _count_call(xm, n, src, dst, errors, validate, interpret):
    """One counting/validating scan over the tiled input.

    Returns (x3, nblk, totals, errs, ferrs): the padded tiles plus the
    per-tile output totals, fused error flags and first-error offsets.
    """
    x3, nblk = _tile(xm)
    check_launch(nblk, stages.get_pair(src, dst)[2], 3, interpret, "scan")
    n1 = jnp.asarray(n, jnp.int32).reshape(1)
    kernel = functools.partial(_count_kernel, src=src, dst=dst,
                               errors=errors, validate=validate)
    per_tile = jax.ShapeDtypeStruct((nblk,), jnp.int32)
    totals, errs, ferrs = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[_SMEM, _tile_spec(0), _tile_spec(1), _tile_spec(2)],
        out_specs=[_SMEM, _SMEM, _SMEM],
        out_shape=[per_tile, per_tile, per_tile],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(n1, x3, x3, x3)
    return x3, nblk, totals, errs, ferrs


def _write_call(x3, nblk, base, totals, n, src, dst, errors, interpret):
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    width = stages.stage_width(codec_s, codec_d)
    check_launch(nblk, factor, 2, interpret, "transcode (fused)")
    n1 = jnp.asarray(n, jnp.int32).reshape(1)
    out_spec, out_shape, scratch = window_out(nblk, width, codec_d.dtype)
    return pl.pallas_call(
        functools.partial(_write_kernel, src=src, dst=dst, errors=errors),
        grid=(nblk,),
        in_specs=[_SMEM, _SMEM, _SMEM,
                  _tile_spec(0), _tile_spec(1), _tile_spec(2)],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(n1, base, totals, x3, x3, x3).reshape(-1)


def _status(errs, ferrs, validate):
    if not validate:
        return jnp.int32(R.STATUS_OK)
    first = jnp.min(ferrs, initial=_IMAX)
    return R.status_from_first(first, jnp.max(errs, initial=0) > 0)


# The single-document wrapper contract, defined ONCE and shared with the
# one-pass pipeline (repro.kernels.onepass_transcode) so the two Pallas
# strategies cannot drift on padding-mask, drop-at-capacity or
# whole-buffer-ASCII semantics (they are pinned bit-identical).


def _mask_padding(x, n, dtype, masked):
    """Zero the lanes at/past ``n`` when an explicit n_valid was given."""
    if not masked:
        return x
    idx = jnp.arange(x.shape[0])
    return jnp.where(idx < n, x, 0).astype(dtype)


def _ascii_copy_result(xm, n, cap, dst_dtype):
    """Paper Algorithm 3 whole-buffer fast path: ASCII values are
    numerically identical in every matrix format — a widening copy."""
    out = xm.astype(dst_dtype)
    if cap > xm.shape[0]:
        out = jnp.concatenate(
            [out, jnp.zeros((cap - xm.shape[0],), dst_dtype)])
    return R.TranscodeResult(out, jnp.asarray(n, jnp.int32),
                             jnp.int32(R.STATUS_OK))


def _clip_to_cap(outp, cap, total, dst_dtype):
    """Keep the first ``cap`` lanes (the cross-strategy drop-at-capacity
    rule) and clear the write-window slack past ``total``."""
    outp = outp[:cap]
    return jnp.where(jnp.arange(cap) < total, outp,
                     jnp.zeros((), dst_dtype))


@functools.partial(jax.jit, static_argnames=("src", "dst", "validate",
                                             "interpret", "ascii_fastpath",
                                             "masked", "errors"))
def _transcode_impl(x, n, src, dst, validate, interpret, ascii_fastpath,
                    masked, errors):
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    cap = factor * x.shape[0]
    xm = _mask_padding(x, n, codec_s.dtype, masked)

    def general(xm):
        x3, nblk, totals, errs, ferrs = _count_call(
            xm, n, src, dst, errors, validate, interpret)
        base, total = compaction.tile_base_offsets(totals)
        outp = _write_call(x3, nblk, base, totals, n, src, dst, errors,
                           interpret)
        outp = _clip_to_cap(outp, cap, total, codec_d.dtype)
        return R.TranscodeResult(outp, total, _status(errs, ferrs, validate))

    def ascii(xm):
        return _ascii_copy_result(xm, n, cap, codec_d.dtype)

    if not ascii_fastpath:
        return general(xm)
    return jax.lax.cond(jnp.all(xm < 0x80), ascii, general, xm)


def transcode_fused(x, n_valid=None, *, src: str, dst: str,
                    validate: bool = True, errors: str = "strict",
                    interpret=None, ascii_fastpath: bool = True):
    """Fused two-pass transcode for any (src, dst) cell of the matrix.

    Returns ``TranscodeResult(buffer[dst dtype, capacity =
    cap_factor * len(x)], count, status)`` — under ``errors="strict"``,
    ``buffer[:count]`` and ``count`` are bit-identical to the
    block-parallel strategy and ``status`` carries the first invalid
    input offset (-1 = valid); under ``errors="replace"`` every maximal
    subpart of an ill-formed sequence becomes U+FFFD — and every
    Latin-1-unencodable code point becomes ``?`` — with CPython
    substitution semantics at full speed.  Validation is fused into the
    counting scan: the input is never read by a standalone pass.
    """
    _check_errors(errors)
    faults.fire(faults.KERNEL_FUSED)     # chaos-suite hook (no-op in prod)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    x = jnp.asarray(x)
    if x.dtype != codec_s.dtype:
        x = x.astype(codec_s.dtype)
    n = x.shape[0] if n_valid is None else n_valid
    return _transcode_impl(
        x, jnp.asarray(n, jnp.int32), src, dst, validate,
        runtime.resolve_interpret(interpret), ascii_fastpath,
        n_valid is not None, errors)


@functools.partial(jax.jit, static_argnames=("src", "dst", "interpret",
                                             "masked"))
def _scan_impl(x, n, src, dst, interpret, masked):
    codec_s = stages.get_codec(src)
    idx = jnp.arange(x.shape[0])
    xm = jnp.where(idx < n, x, 0).astype(codec_s.dtype) if masked else x
    _x3, _nblk, totals, errs, ferrs = _count_call(
        xm, n, src, dst, "strict", True, interpret)
    return jnp.sum(totals), _status(errs, ferrs, True)


def scan_fused(x, n_valid=None, *, src: str, dst: str, interpret=None):
    """Single-scan validation + capacity query: ``(count, status)``.

    Runs ONLY the fused pipeline's counting pass — one read of the input
    yields the simdutf-style verdict: ``status`` is -1 for valid
    streams, else the input offset of the first invalid maximal subpart
    (Python ``UnicodeDecodeError.start``), and ``count`` is the number
    of destination units a transcode would produce.  This is the
    ingestion-boundary API (serve ingress): validation with error
    location at the cost of a capacity query.
    """
    faults.fire(faults.KERNEL_SCAN)      # chaos-suite hook (no-op in prod)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    x = jnp.asarray(x)
    if x.dtype != codec_s.dtype:
        x = x.astype(codec_s.dtype)
    n = x.shape[0] if n_valid is None else n_valid
    return _scan_impl(x, jnp.asarray(n, jnp.int32), src, dst,
                      runtime.resolve_interpret(interpret),
                      n_valid is not None)


# ---------------------------------------------------------------------------
# Thin per-pair instantiations (the pre-matrix public API).


def utf8_to_utf16_fused(b, n_valid=None, *, validate: bool = True,
                        errors: str = "strict", interpret=None,
                        ascii_fastpath: bool = True):
    """Fused UTF-8 -> UTF-16 (the (utf8, utf16) matrix cell)."""
    return transcode_fused(b, n_valid, src="utf8", dst="utf16",
                           validate=validate, errors=errors,
                           interpret=interpret,
                           ascii_fastpath=ascii_fastpath)


def utf16_to_utf8_fused(u, n_valid=None, *, validate: bool = True,
                        errors: str = "strict", interpret=None,
                        ascii_fastpath: bool = True):
    """Fused UTF-16 -> UTF-8 (the (utf16, utf8) matrix cell)."""
    return transcode_fused(u, n_valid, src="utf16", dst="utf8",
                           validate=validate, errors=errors,
                           interpret=interpret,
                           ascii_fastpath=ascii_fastpath)


def utf8_scan_fused(b, n_valid=None, *, interpret=None):
    """Single-scan UTF-8 validation + UTF-16 length: (count, status)."""
    return scan_fused(b, n_valid, src="utf8", dst="utf16",
                      interpret=interpret)


def utf16_scan_fused(u, n_valid=None, *, interpret=None):
    """Single-scan UTF-16 validation + UTF-8 length: (count, status)."""
    return scan_fused(u, n_valid, src="utf16", dst="utf8",
                      interpret=interpret)
