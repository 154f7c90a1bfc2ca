"""Single-pass Pallas transcode pipeline (strategy ``"onepass"``, the
dispatch default): ONE grid launch, ONE decode per source tile.

A transcode has to know each tile's output base before it can store the
tile, and that base is the exclusive scan of the totals of all earlier
tiles.  On TPU, Pallas grid steps execute sequentially per core, so that
scan does not need a launch boundary (a count launch, an inter-launch
cumsum and a write launch that decodes every tile again): it is a scalar
**carry in SMEM scratch** (DESIGN.md §9).

Each grid step of the single launch handles a block of G consecutive
1 KiB tiles, shaped ``(G, ROWS, LANES)`` (G = ``window.tiles_per_step``:
the largest power of two up to ``G_MAX`` that the input's tile count
allows), and:

  1. decodes/analyzes the block ONCE (``stages.driver.decode_once``;
     over a block each tile is decoded as it would be alone, with its
     own neighbours, through one shared routing network),
  2. counts each tile's output units + the block's fused validation
     scalars off the decoded lanes (``count_decoded``),
  3. reads the running output offset from the SMEM carry — the exclusive
     scan, scalar adds instead of an inter-launch cumsum — and stores
     the G compact stage windows (``stage_decoded``, fed the
     *already-decoded* block) at that base plus each tile's prefix
     inside the block, with one DMA (``window.store_window``),
  4. advances the carry by the block's total and folds the block's
     error scalars into the sticky (err_flag, first_error) carry; the
     final ``(count, status)`` pair is emitted from the carry, so
     nothing per-tile ever round-trips to the host.

A grid step costs a fixed amount on the chip besides its vector work
(pipelining three input blocks, the branch dispatch, one DMA and its
wait); a block pays it once for G tiles, and the G tiles' vector work
forms G independent dependency chains.  The ragged kernel
(``repro.kernels.ragged_transcode``) steps over blocks the same way,
through the same ``window`` helpers, and each tile of its blocks keeps
its own document.

The whole-buffer ASCII ``lax.cond`` (paper Algorithm 3) additionally
becomes a **per-block three-way class dispatch** (Algorithm 3 at
block granularity plus the ≤2-byte class, ``stages.driver.onepass_tile``,
DESIGN.md §9; a block takes a class only when each of its tiles
would): a pure-ASCII block with clean boundary inflow reduces to a
widening copy, a block whose every code point fits 11 bits takes the
class-specialized ≤2-byte body (no 3-/4-unit assembly, no surrogate
folding, fewer candidate planes to stage), and only blocks holding a
wide character pay the general speculative decode.  Mostly-ASCII
documents keep the copy path block by block, and dense 2-byte scripts
ride the narrowed class instead of falling off it globally.  (The
whole-buffer cond survives in front of the launch — when the entire
buffer is ASCII, skipping the kernel dispatch outright is strictly
cheaper than taking the skip block by block.)

Results match the pure-jnp ``strategy="blockparallel"`` reference and
CPython ``codecs`` — (buffer, count, status) across every matrix cell ×
``errors=`` policy (pinned by ``tests/test_onepass.py``,
``tests/test_transcode_kernels.py`` and the differential fuzz) — and the
whole transcode traces to exactly ONE ``pallas_call``.

:func:`scan_onepass` is the counting kernel alone: one launch over one
read of the input that emits per-tile totals and error scalars, with no
write pass.

Sequential-grid assumption: the SMEM carry is only correct because grid
steps run in order on one core.  That holds for Mosaic's TPU lowering
(the grid is a sequential loop per core) and for the Pallas interpreter
(which executes the grid as a sequential scan carrying scratch buffers);
a parallel multi-core grid partition would need one carry per partition
plus a final fix-up pass — see DESIGN.md §9.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import spans
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

# SMEM carry layout (int32 x 3), initialized at grid step 0:
#   [0] running output offset  (the inter-tile exclusive scan)
#   [1] sticky error flag      (max over tiles)
#   [2] sticky first-error     (min over tiles; _IMAX = clean)
_CARRY = 3

# The launch's name, in the profiler trace of the chip as in the HLO.
KERNEL_NAME = "onepass_transcode"

# Most 1 KiB tiles one grid step handles (a power of two).  Chosen on the
# chip: the smallest G within 3% of the best kernel time on 4 MiB UTF-8
# -> UTF-16 calls of the general and the ≤2-byte tile class (PERF.md).
G_MAX = 32


def _onepass_kernel(n_ref, xp_ref, x_ref, xn_ref, out_ref, fin_ref, buf,
                    pend, sem, carry, *, src, dst, errors, validate,
                    ascii_skip):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry[0] = 0
        carry[1] = 0
        carry[2] = _IMAX

    x, xp, xn = window.load_block(xp_ref, x_ref, xn_ref)
    gidx = window.gidx(x.shape)
    tot, err, ferr, stage = sdrv.onepass_tile(
        codec_s, codec_d, x, xp, xn, gidx < n_ref[0], gidx, errors=errors,
        validate=validate, ascii_skip=ascii_skip)

    base = carry[0]
    window.store_window(stage, base, tot, out_ref, buf, pend, sem)
    carry[0] = base + (sum(tot) if isinstance(tot, tuple) else tot)
    carry[1] = jnp.maximum(carry[1], err)
    carry[2] = jnp.minimum(carry[2], ferr)
    # Written every step; the grid is sequential, so the last write is
    # the final (count, status) — no per-tile vectors leave the kernel.
    fin_ref[0] = carry[0]
    fin_ref[1] = R.status_from_first(carry[2], carry[1] > 0)


def _onepass_call(xm, n, src, dst, errors, validate, ascii_skip, interpret):
    """The single launch: returns ``(out_window, (count, status))``."""
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    width = stages.stage_width(codec_s, codec_d)
    # The limit counts the input's own tiles, not the block padding.
    window.check_launch(max(1, -(-xm.shape[0] // BLOCK)), factor, 0,
                        interpret, "transcode")
    g = window.tiles_per_step(xm.shape[0], G_MAX)
    x3, nblk = runtime.tile_with_boundaries(xm, ROWS, LANES, block=g)
    n1 = jnp.asarray(n, jnp.int32).reshape(1)
    kernel = functools.partial(_onepass_kernel, src=src, dst=dst,
                               errors=errors, validate=validate,
                               ascii_skip=ascii_skip)
    # The compact buffer is chunked HBM written by DMA at the carried,
    # data-dependent base (window.store_window); the (2,) SMEM vector is
    # the final (count, status) pair off the carry.
    out_spec, out_shape, scratch = window.window_out(nblk, width,
                                                     codec_d.dtype, g)
    with jax.named_scope(KERNEL_NAME):
        outp, fin = pl.pallas_call(
            kernel,
            grid=(nblk // g,),
            in_specs=[window.SMEM] + window.block_specs(g),
            out_specs=[out_spec, window.SMEM],
            out_shape=[out_shape, jax.ShapeDtypeStruct((2,), jnp.int32)],
            scratch_shapes=scratch + [pltpu.SMEM((_CARRY,), jnp.int32)],
            compiler_params=window.SEQUENTIAL,
            interpret=interpret,
            name=KERNEL_NAME,
        )(n1, x3, x3, x3)
    return outp.reshape(-1), fin


@functools.partial(jax.jit, static_argnames=("src", "dst", "validate",
                                             "interpret", "ascii_fastpath",
                                             "masked", "errors"))
def _transcode_impl(x, n, src, dst, validate, interpret, ascii_fastpath,
                    masked, errors):
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    cap = factor * x.shape[0]
    xm = window.mask_padding(x, n, codec_s.dtype, masked)

    def general(xm):
        outp, fin = _onepass_call(xm, n, src, dst, errors, validate,
                                  ascii_fastpath, interpret)
        total = fin[0]
        outp = window.clip_to_cap(outp, cap, total, codec_d.dtype)
        return R.TranscodeResult(outp, total, fin[1])

    def ascii(xm):
        # When EVERY tile would take the per-tile skip, skipping the
        # launch itself is strictly cheaper.
        return window.ascii_copy_result(xm, n, cap, codec_d.dtype)

    if not ascii_fastpath:
        return general(xm)
    # xm is the codec's (unsigned) storage dtype, so a single max
    # reduction decides ASCII-ness — measurably cheaper at the µs scale
    # of this path than materializing a comparison vector for jnp.all.
    return jax.lax.cond(jnp.max(xm, initial=0) < 0x80, ascii, general, xm)


def transcode_onepass(x, n_valid=None, *, src: str, dst: str,
                      validate: bool = True, errors: str = "strict",
                      interpret=None, ascii_fastpath: bool = True):
    """Single-pass transcode for any (src, dst) cell of the matrix.

    Returns ``TranscodeResult(buffer[dst dtype, capacity =
    cap_factor * len(x)], count, status)`` — under ``errors="strict"``,
    ``buffer[:count]`` and ``count`` are bit-identical to the
    block-parallel strategy and ``status`` carries the first invalid
    input offset (-1 = valid); under ``errors="replace"`` every maximal
    subpart of an ill-formed sequence becomes U+FFFD — and every
    Latin-1-unencodable code point becomes ``?`` — with CPython
    substitution semantics.  The input is read and decoded ONCE in a
    single Pallas launch: the inter-tile output offsets are a scalar
    SMEM carry across the sequential grid, and the count/status come
    off the carry.  ``ascii_fastpath`` controls both the whole-buffer
    cond and the per-block class dispatch.
    """
    _check_errors(errors)
    faults.fire(faults.KERNEL_ONEPASS)   # chaos-suite hook (no-op in prod)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    with jax.profiler.TraceAnnotation(spans.TRANSCODE_DISPATCH):
        x = jnp.asarray(x)
        if x.dtype != codec_s.dtype:
            x = x.astype(codec_s.dtype)
        n = x.shape[0] if n_valid is None else n_valid
        return _transcode_impl(
            x, jnp.asarray(n, jnp.int32), src, dst, validate,
            runtime.resolve_interpret(interpret), ascii_fastpath,
            n_valid is not None, errors)


# ---------------------------------------------------------------------------
# Counting kernel (scan): per-tile totals and fused validation scalars
# from one read of the input, one tile per grid step, no write pass.


def _count_kernel(n_ref, xp_ref, x_ref, xn_ref, tot_ref, err_ref, ferr_ref,
                  *, src, dst):
    codec_s, codec_d = stages.get_codec(src), stages.get_codec(dst)
    i = pl.program_id(0)
    x = window.load(x_ref)
    gidx = window.gidx(x.shape)
    tot_ref[i], err_ref[i], ferr_ref[i] = sdrv.count_tile(
        codec_s, codec_d, x, window.load(xp_ref), window.load(xn_ref),
        gidx < n_ref[0], gidx, errors="strict", validate=True)


@functools.partial(jax.jit, static_argnames=("src", "dst", "interpret",
                                             "masked"))
def _scan_impl(x, n, src, dst, interpret, masked):
    codec_s, _codec_d, factor = stages.get_pair(src, dst)
    x3, nblk = window.tile(window.mask_padding(x, n, codec_s.dtype, masked))
    window.check_launch(nblk, factor, 3, interpret, "scan")
    n1 = jnp.asarray(n, jnp.int32).reshape(1)
    per_tile = jax.ShapeDtypeStruct((nblk,), jnp.int32)
    totals, errs, ferrs = pl.pallas_call(
        functools.partial(_count_kernel, src=src, dst=dst),
        grid=(nblk,),
        in_specs=[window.SMEM, window.tile_spec(0), window.tile_spec(1),
                  window.tile_spec(2)],
        out_specs=[window.SMEM, window.SMEM, window.SMEM],
        out_shape=[per_tile, per_tile, per_tile],
        compiler_params=window.SEQUENTIAL,
        interpret=interpret,
    )(n1, x3, x3, x3)
    return jnp.sum(totals), window.status(errs, ferrs)


def scan_onepass(x, n_valid=None, *, src: str, dst: str, interpret=None):
    """Single-scan validation + capacity query: ``(count, status)``.

    Runs ONLY the counting kernel — one read of the input yields the
    simdutf-style verdict: ``status`` is -1 for valid streams, else the
    input offset of the first invalid maximal subpart (Python
    ``UnicodeDecodeError.start``), and ``count`` is the number of
    destination units a transcode would produce.  This is the
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
