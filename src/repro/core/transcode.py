"""Public transcoding API (paper's contribution, as composable JAX ops).

All functions are shape-polymorphic in the *static* buffer capacity and take
an explicit ``n_valid`` scalar for the logical length, so they jit cleanly
and batch with ``vmap`` / shard with ``pjit``.  Outputs are a
:class:`repro.core.result.TranscodeResult` ``(buffer, count, status)``: a
fixed-capacity buffer, the number of meaningful elements, and an int32
simdutf-style status — -1 for a valid stream, else the input offset of the
first invalid maximal subpart, with Python ``UnicodeDecodeError.start``
semantics (bytes for UTF-8/Latin-1, code units for UTF-16, code points for
UTF-32).  For Latin-1 *egress* the status additionally reports the first
unencodable code point, at the offset of its source lead (CPython
``UnicodeEncodeError.start`` mapped to source elements).

The codec matrix (DESIGN.md §8): :func:`transcode` dispatches any
``(src_format, dst_format)`` pair over the ``utf8`` / ``utf16`` / ``utf32``
/ ``latin1`` formats — every pair runs through ONE generic decode×encode
composition per strategy (the stage driver of ``repro.kernels.stages`` on
the onepass path, the shared speculative-decode + global-compaction body
on the block-parallel path).  Format names accept the codecs-module aliases
(``"utf-8"``, ``"utf-16-le"``, ``"utf-32-le"``, ``"latin-1"`` /
``"iso-8859-1"``).

Error policy (the ``errors=`` kwarg; full table in DESIGN.md §4):

  * ``"strict"``  (default) -- historical behavior: the buffer holds the
    speculative transcode and ``status`` reports where the stream broke;
    callers reject invalid input wholesale.
  * ``"replace"`` -- lossy ingestion: each maximal subpart of an
    ill-formed sequence (W3C / CPython substitution semantics) emits one
    U+FFFD — and each Latin-1-unencodable code point one ``?`` — and the
    transcode completes at full speed; ``status`` still reports the first
    substitution offset.

Strategies (the ``strategy=`` kwarg; full decision table in DESIGN.md §5):

  * ``onepass`` (matrix + per-doc default) -- single-launch Pallas
    pipeline (DESIGN.md §9): one read + one decode of the input, with
    the inter-tile output offsets carried as a scalar in SMEM across the
    sequential grid, hierarchical in-kernel compaction, narrow
    (uint8/uint16/uint32) I/O, validation folded into the decode, and a
    per-block ASCII fast path.
  * ``blockparallel``    -- speculative per-position decode + global XLA
    cumsum compaction; fully branch-free, pure-jnp (no Pallas), the
    portable beyond-paper form and the semantic reference.
  * ``windowed``         -- the paper-faithful Algorithm 2/3 structure
    (see ``repro.core.windowed``); serial window walk, the measured
    baseline.  UTF-8<->UTF-16 only, ``errors="strict"`` only.

The ASCII fast path of Algorithm 3 survives as a whole-chunk ``lax.cond``:
ASCII values are numerically identical in every matrix format, so
ASCII-pure chunks (the paper's Latin benchmark) reduce to a widening copy.
"""

from __future__ import annotations

import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from repro import spans
from repro.core import compaction, latin1 as l1mod, result as R
from repro.core import utf16 as u16mod, utf32 as u32mod, utf8 as u8mod
from repro.core.result import STATUS_OK, TranscodeResult  # noqa: F401  (re-export)


def _as_i32(x):
    return x.astype(jnp.int32)


def _n(x, n_valid):
    return x.shape[0] if n_valid is None else n_valid


_check_errors = R.check_errors_policy


def _check_input(x, what: str = "transcode"):
    """Reject wrong-dtype / wrong-rank inputs with a clear diagnosis
    instead of producing garbage downstream (lists are converted; jax
    arrays and tracers pass through untouched — a vmapped row is 1-D).
    """
    if not hasattr(x, "dtype"):
        x = np.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(
            f"{what}: input must have an integer dtype (narrow wire "
            f"dtype or int32), got {x.dtype}")
    if x.ndim != 1:
        raise ValueError(
            f"{what}: input must be 1-D (one document; use the ragged/"
            f"batched entry points for batches), got shape {x.shape}")
    return x


# Min-reduce of a per-position error map over the live region; the one
# definition lives next to the status semantics in core/result.py.
_first_error_status = R.first_error_status


# ---------------------------------------------------------------------------
# The codec matrix: formats, aliases and static capacity conventions.
# (``repro.kernels.stages`` imports these — the kernel registry and the
# public dispatch share one source of truth.)

FORMATS = ("utf8", "utf16", "utf32", "latin1")

_FORMAT_ALIASES = {
    "utf8": "utf8", "utf-8": "utf8",
    "utf16": "utf16", "utf-16": "utf16", "utf-16-le": "utf16",
    "utf16-le": "utf16", "utf16le": "utf16",
    "utf32": "utf32", "utf-32": "utf32", "utf-32-le": "utf32",
    "utf32-le": "utf32", "utf32le": "utf32",
    "latin1": "latin1", "latin-1": "latin1", "latin": "latin1",
    "iso-8859-1": "latin1", "iso8859-1": "latin1",
}

# Output capacity per input element for each (src, dst) pair: enough for
# every *valid* stream; speculative garbage beyond it drops at capacity
# in all strategies alike.
CAP_FACTOR = {
    ("utf8", "utf16"): 1, ("utf8", "utf32"): 1, ("utf8", "latin1"): 1,
    ("utf16", "utf8"): 3, ("utf16", "utf32"): 1, ("utf16", "latin1"): 1,
    ("utf32", "utf8"): 4, ("utf32", "utf16"): 2, ("utf32", "latin1"): 1,
    ("latin1", "utf8"): 2, ("latin1", "utf16"): 1, ("latin1", "utf32"): 1,
}

PAIRS = tuple(sorted(CAP_FACTOR))

# The strategy registry: every name `transcode` dispatches, in preference
# order.  `onepass` is the default (single launch, single decode) and the
# one device path; `blockparallel` (pure jnp) and `windowed` (the paper's
# algorithm) are the references it is pinned against.
STRATEGIES = ("onepass", "blockparallel", "windowed")

# The ragged (packed-batch) entry point additionally accepts "sharded":
# the packed stream split across a device mesh's data axis with one
# onepass launch per shard (repro.core.shard, DESIGN.md §12).
RAGGED_STRATEGIES = ("onepass", "sharded")

DEFAULT_STRATEGY = "onepass"

# The per-pair convenience wrappers below are DEPRECATED (DESIGN.md §11):
# the public surface is the four generic entry points (``transcode`` /
# ``scan`` / ``ragged_transcode`` / ``ragged_scan``) plus the streaming
# API.  Each name here is a one-line shim that emits a
# ``DeprecationWarning`` attributed to ITS CALLER (stacklevel past the
# shim), so CI can run with ``-W error::DeprecationWarning:repro`` and
# fail on internal use while external callers merely see the warning.
# The shims keep their historical default strategies, except that those
# whose default was the retired two-launch kernel ("fused") default to
# "onepass", which gives bit-identical results.
DEPRECATED = (
    "utf8_to_utf16", "utf8_to_utf32", "utf8_to_latin1",
    "latin1_to_utf8", "latin1_to_utf16",
    "utf16_to_utf8", "utf16_to_utf32",
    "utf32_to_utf8", "utf32_to_utf16",
    "transcode_utf8_to_utf16", "transcode_utf16_to_utf8",
    "ragged_utf8_to_utf16", "ragged_utf16_to_utf8",
    "ragged_scan_utf8", "ragged_scan_utf16",
    "scan_utf8", "scan_utf16",
)


def _warn_deprecated(name: str, repl: str):
    warnings.warn(
        f"repro.core.transcode.{name}() is deprecated; use {repl}",
        DeprecationWarning, stacklevel=3)


def normalize_format(name: str) -> str:
    """Resolve a format name or codecs-style alias to its canonical name."""
    try:
        return _FORMAT_ALIASES[str(name).lower()]
    except KeyError:
        raise ValueError(
            f"unknown format {name!r}; supported: {list(FORMATS)} "
            f"(and codecs aliases like 'utf-16-le')")


def _check_pair(src: str, dst: str):
    if (src, dst) not in CAP_FACTOR:
        raise ValueError(
            f"unsupported format pair {src!r} -> {dst!r}; "
            f"supported pairs: {list(PAIRS)}")
    return CAP_FACTOR[(src, dst)]


# ---------------------------------------------------------------------------
# Validation

def validate_utf8(b, n_valid=None):
    """Scalar bool: is the byte stream valid UTF-8 (Keiser-Lemire)."""
    return u8mod.validate_kl(_as_i32(b), n_valid)


def validate_utf16(u, n_valid=None):
    return u16mod.validate(_as_i32(u), n_valid)


# ---------------------------------------------------------------------------
# Block-parallel matrix body: whole-array speculative decode + analysis
# per source format, candidate production per destination format, global
# XLA compaction.  This is the pure-jnp semantic reference every onepass
# cell is pinned to bit-for-bit.


def _src_decode(src: str, x):
    """Speculative whole-array decode: ``(cp, lead_mask)``."""
    if src == "utf8":
        cp, is_lead, _err = u8mod.decode_speculative(x)
        return cp, is_lead
    if src == "utf16":
        cp, is_lead, _err = u16mod.decode_speculative(x)
        return cp, is_lead
    if src == "utf32":
        # Unrepresentable scalars substitute U+FFFD in the buffer even
        # under errors="strict" (status still locates the offender), so
        # the speculative output is a well-defined narrow value in every
        # strategy.
        return jnp.where(u32mod.invalid_scalar(x), 0xFFFD, x), \
            jnp.ones(x.shape, bool)
    # latin1: every byte is a code point.
    return x, jnp.ones(x.shape, bool)


def _src_analyze(src: str, x):
    """Whole-array maximal-subpart analysis: {starts, valid, cp, err}."""
    if src == "utf8":
        return u8mod.analyze(x)
    if src == "utf16":
        return u16mod.analyze(x)
    if src == "utf32":
        bad = u32mod.invalid_scalar(x)
        return {"starts": jnp.ones(x.shape, bool), "valid": ~bad,
                "cp": jnp.where(bad, 0xFFFD, x), "err": bad}
    ones = jnp.ones(x.shape, bool)
    return {"starts": ones, "valid": ones, "cp": x,
            "err": jnp.zeros(x.shape, bool)}


def _dst_encode(dst: str, cp):
    """Candidate production: ``(lengths, values[N, K], encode_bad)``."""
    if dst == "utf16":
        units, u0, u1, _bad = u16mod.encode_candidates(cp)
        return units, jnp.stack([u0, u1], -1), None
    if dst == "utf8":
        L, cand, _bad = u32mod.encode_utf8_candidates(cp)
        return L, cand, None
    if dst == "utf32":
        return jnp.ones_like(cp), cp[..., None], None
    L, byte, bad = l1mod.encode_candidates(cp)
    return L, byte[..., None], bad


def _blockparallel_pair(x, n_valid, src: str, dst: str, validate: bool,
                        errors: str, ascii_fastpath: bool = True):
    """Generic block-parallel (src, dst) transcode; see module docstring."""
    factor = _check_pair(src, dst)
    x = _mask_padding(_as_i32(x), n_valid)
    n = _n(x, n_valid)
    cap = factor * x.shape[0]
    idx = jnp.arange(x.shape[0])

    def general(x):
        need_analysis = validate or errors == "replace"
        a = _src_analyze(src, x) if need_analysis else None
        if errors == "replace":
            cp, mask = a["cp"], a["starts"] & (idx < n)
        else:
            cp, is_lead = _src_decode(src, x)
            mask = is_lead & (idx < n)
        lens, vals, enc_bad = _dst_encode(dst, cp)
        out, count = compaction.compact_offsets(vals, lens, mask, cap)
        if validate:
            err_map = a["err"]
            if enc_bad is not None:
                _l, _v, a_bad = _dst_encode(dst, a["cp"])
                err_map = err_map | (a_bad & a["starts"])
            status = _first_error_status(err_map, n)
        else:
            status = jnp.int32(STATUS_OK)
        return TranscodeResult(out, count, status)

    def ascii(x):
        # Paper Algorithm 3 fast path: ASCII values are numerically
        # identical in every matrix format — a widening copy.
        out = x if cap == x.shape[0] else jnp.concatenate(
            [x, jnp.zeros((cap - x.shape[0],), x.dtype)])
        return TranscodeResult(out, jnp.asarray(n, jnp.int32),
                               jnp.int32(STATUS_OK))

    if not ascii_fastpath:
        return general(x)
    # The lower bound matters: lanes are int32 here, so a garbage UTF-32
    # scalar like 0xFFFFFFFF wraps negative and would pass a bare
    # ``x < 0x80`` (the onepass path compares in the unsigned narrow dtype
    # and needs no guard).
    return jax.lax.cond(jnp.all((x >= 0) & (x < 0x80)), ascii, general, x)


def _blockparallel_count(x, n_valid, src: str, dst: str):
    """Single-scan validation + capacity, pure jnp: ``(count, status)``."""
    _check_pair(src, dst)
    x = _mask_padding(_as_i32(x), n_valid)
    n = _n(x, n_valid)
    idx = jnp.arange(x.shape[0])
    cp, is_lead = _src_decode(src, x)
    lens, _vals, _bad = _dst_encode(dst, cp)
    count = jnp.sum(jnp.where(is_lead & (idx < n), lens, 0))
    a = _src_analyze(src, x)
    err_map = a["err"]
    _l, _v, a_bad = _dst_encode(dst, a["cp"])
    if a_bad is not None:
        err_map = err_map | (a_bad & a["starts"])
    return count, _first_error_status(err_map, n)


def scan_utf8(b, n_valid=None, *, strategy: str = DEFAULT_STRATEGY):
    """DEPRECATED shim: use :func:`scan` with ``dst_format="utf16"``."""
    _warn_deprecated("scan_utf8", 'scan(b, "utf16", src_format="utf8")')
    return scan(b, "utf16", src_format="utf8", n_valid=n_valid,
                strategy=strategy)


def scan_utf16(u, n_valid=None, *, strategy: str = DEFAULT_STRATEGY):
    """DEPRECATED shim: use :func:`scan` with ``dst_format="utf8"``."""
    _warn_deprecated("scan_utf16", 'scan(u, "utf8", src_format="utf16")')
    return scan(u, "utf8", src_format="utf16", n_valid=n_valid,
                strategy=strategy)


def scan(x, dst_format, *, src_format: str = "utf8", n_valid=None,
         strategy: str = DEFAULT_STRATEGY):
    """Single-scan validation + destination capacity for any matrix cell.

    One read of the input yields ``(count, status)``: the number of
    ``dst_format`` units a transcode would produce and the simdutf-style
    verdict (DESIGN.md §4) — the ingestion-boundary query.
    """
    x = _check_input(x, "scan")
    src = normalize_format(src_format)
    dst = normalize_format(dst_format)
    _check_pair(src, dst)
    if strategy == "onepass":
        from repro.kernels import onepass_transcode
        return onepass_transcode.scan_onepass(x, n_valid, src=src, dst=dst)
    if strategy != "blockparallel":
        raise ValueError(
            f"scan: unknown strategy {strategy!r} "
            f"(supported: ['onepass', 'blockparallel'])")
    return _blockparallel_count(x, n_valid, src, dst)


# ---------------------------------------------------------------------------
# UTF-8 -> UTF-32 / UTF-16


def _mask_padding(b, n_valid):
    if n_valid is None:
        return b
    idx = jnp.arange(b.shape[0])
    return jnp.where(idx < n_valid, b, 0)


def utf8_to_utf32(b, n_valid=None, validate: bool = True,
                  errors: str = "strict", *,
                  strategy: str = "blockparallel"):
    """DEPRECATED shim: use :func:`transcode` (``dst_format="utf32"``).
    Historical default strategy: ``blockparallel``."""
    _warn_deprecated("utf8_to_utf32",
                     'transcode(b, "utf32", src_format="utf8")')
    return transcode(b, "utf32", src_format="utf8", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


def utf8_to_utf16(b, n_valid=None, validate: bool = True,
                  ascii_fastpath: bool = True, errors: str = "strict"):
    """DEPRECATED shim: use :func:`transcode` with
    ``strategy="blockparallel"`` (this wrapper WAS the pure-jnp
    block-parallel reference cell)."""
    _warn_deprecated(
        "utf8_to_utf16",
        'transcode(b, "utf16", src_format="utf8", strategy="blockparallel")')
    if not ascii_fastpath:
        # The generic surface has no ascii_fastpath switch (it is a
        # kernel-level knob); keep the legacy escape hatch bit-exact.
        _check_errors(errors)
        return _blockparallel_pair(b, n_valid, "utf8", "utf16", validate,
                                   errors, ascii_fastpath=False)
    return transcode(b, "utf16", src_format="utf8", n_valid=n_valid,
                     strategy="blockparallel", validate=validate,
                     errors=errors)


def utf8_to_latin1(b, n_valid=None, validate: bool = True,
                   errors: str = "strict", *, strategy: str = "onepass"):
    """DEPRECATED shim: use :func:`transcode` (``dst_format="latin1"``).
    Default strategy: ``onepass`` (historically ``fused``)."""
    _warn_deprecated("utf8_to_latin1",
                     'transcode(b, "latin1", src_format="utf8")')
    return transcode(b, "latin1", src_format="utf8", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


def latin1_to_utf8(b, n_valid=None, validate: bool = True,
                   errors: str = "strict", *, strategy: str = "onepass"):
    """DEPRECATED shim: use :func:`transcode` (``src_format="latin1"``).
    Default strategy: ``onepass`` (historically ``fused``)."""
    _warn_deprecated("latin1_to_utf8",
                     'transcode(b, "utf8", src_format="latin1")')
    return transcode(b, "utf8", src_format="latin1", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


def latin1_to_utf16(b, n_valid=None, validate: bool = True,
                    errors: str = "strict", *, strategy: str = "onepass"):
    """DEPRECATED shim: use :func:`transcode` (``src_format="latin1"``).
    Default strategy: ``onepass`` (historically ``fused``)."""
    _warn_deprecated("latin1_to_utf16",
                     'transcode(b, "utf16", src_format="latin1")')
    return transcode(b, "utf16", src_format="latin1", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


# ---------------------------------------------------------------------------
# UTF-16 -> UTF-32 / UTF-8


def utf16_to_utf32(u, n_valid=None, validate: bool = True,
                   errors: str = "strict", *,
                   strategy: str = "blockparallel"):
    """DEPRECATED shim: use :func:`transcode` (``dst_format="utf32"``).
    Historical default strategy: ``blockparallel``."""
    _warn_deprecated("utf16_to_utf32",
                     'transcode(u, "utf32", src_format="utf16")')
    return transcode(u, "utf32", src_format="utf16", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


def utf16_to_utf8(u, n_valid=None, validate: bool = True,
                  ascii_fastpath: bool = True, errors: str = "strict"):
    """DEPRECATED shim: use :func:`transcode` with
    ``strategy="blockparallel"`` (this wrapper WAS the pure-jnp
    block-parallel reference cell)."""
    _warn_deprecated(
        "utf16_to_utf8",
        'transcode(u, "utf8", src_format="utf16", strategy="blockparallel")')
    if not ascii_fastpath:
        _check_errors(errors)
        return _blockparallel_pair(u, n_valid, "utf16", "utf8", validate,
                                   errors, ascii_fastpath=False)
    return transcode(u, "utf8", src_format="utf16", n_valid=n_valid,
                     strategy="blockparallel", validate=validate,
                     errors=errors)


# ---------------------------------------------------------------------------
# UTF-32 egress


def _invalid_scalar(cp):
    """Code points no encoding may represent: surrogates, > U+10FFFF,
    negatives.  (Single definition: ``repro.core.utf32.invalid_scalar``.)"""
    return u32mod.invalid_scalar(cp)


def utf32_to_utf8(cp, n_valid=None, validate: bool = True,
                  errors: str = "strict", *,
                  strategy: str = "blockparallel"):
    """DEPRECATED shim: use :func:`transcode` (``src_format="utf32"``).
    Historical default strategy: ``blockparallel``."""
    _warn_deprecated("utf32_to_utf8",
                     'transcode(cp, "utf8", src_format="utf32")')
    return transcode(cp, "utf8", src_format="utf32", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


def utf32_to_utf16(cp, n_valid=None, validate: bool = True,
                   errors: str = "strict", *,
                   strategy: str = "blockparallel"):
    """DEPRECATED shim: use :func:`transcode` (``src_format="utf32"``).
    Historical default strategy: ``blockparallel``."""
    _warn_deprecated("utf32_to_utf16",
                     'transcode(cp, "utf16", src_format="utf32")')
    return transcode(cp, "utf16", src_format="utf32", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


# ---------------------------------------------------------------------------
# Length counting (simdutf-style capacity queries)


def _mask_padding_cont(b, n_valid):
    """Mask padding with a continuation byte (counts as 0 characters)."""
    if n_valid is None:
        return b
    idx = jnp.arange(b.shape[0])
    return jnp.where(idx < n_valid, b, 0x80)


def utf16_length_from_utf8(b, n_valid=None):
    b = _mask_padding_cont(_as_i32(b), n_valid)
    return u8mod.utf16_length(b)


def utf8_length_from_utf16(u, n_valid=None):
    u = _as_i32(u)
    if n_valid is not None:
        idx = jnp.arange(u.shape[0])
        # 0xDC00 (lone low surrogate) contributes 2 bytes; use a masked sum
        # instead: zero units count 1 byte each, so subtract the padding.
        pad = jnp.sum((idx >= n_valid).astype(jnp.int32))
        u = jnp.where(idx < n_valid, u, 0)
        return u16mod.utf8_length(u) - pad
    return u16mod.utf8_length(u)


def count_utf8_chars(b, n_valid=None):
    b = _mask_padding_cont(_as_i32(b), n_valid)
    return u8mod.count_chars(b)


# ---------------------------------------------------------------------------
# Byte-level helpers (LE byte buffers <-> unit arrays).  All are explicit
# little-endian jnp byte math — no ``.view()`` / ``frombuffer`` host-
# endianness dependence anywhere on the wire path.


def utf16le_bytes_to_units(by):
    """UTF-16LE byte buffer -> int32 unit array (explicit LE byte math)."""
    by = _as_i32(by)
    if by.shape[0] % 2:
        raise ValueError(
            f"utf16le_bytes_to_units: odd byte length {by.shape[0]}")
    return by[0::2] | (by[1::2] << 8)


def units_to_utf16le_bytes(u):
    """int32/uint16 unit array -> UTF-16LE byte array (explicit LE)."""
    u = _as_i32(u)
    lo = u & 0xFF
    hi = (u >> 8) & 0xFF
    return jnp.stack([lo, hi], -1).reshape(-1)


def utf32le_bytes_to_cps(by):
    """UTF-32LE byte buffer -> int32 code-point array (explicit LE)."""
    by = _as_i32(by)
    if by.shape[0] % 4:
        raise ValueError(
            f"utf32le_bytes_to_cps: byte length {by.shape[0]} not a "
            f"multiple of 4")
    return (by[0::4] | (by[1::4] << 8) | (by[2::4] << 16)
            | (by[3::4] << 24))


def cps_to_utf32le_bytes(cp):
    """int32/uint32 code-point array -> UTF-32LE byte array (explicit LE)."""
    cp = _as_i32(cp)
    return jnp.stack([cp & 0xFF, (cp >> 8) & 0xFF, (cp >> 16) & 0xFF,
                      (cp >> 24) & 0xFF], -1).reshape(-1)


# ---------------------------------------------------------------------------
# Strategy dispatch (onepass = single-launch Pallas, windowed =
# paper-faithful; kernels imported lazily to avoid circular imports).
# The STRATEGIES registry and DEFAULT_STRATEGY live next to the format
# registry above.

# The serial paper baseline exists for the paper's own two directions.
_WINDOWED_PAIRS = {("utf8", "utf16"), ("utf16", "utf8")}


@functools.partial(jax.profiler.annotate_function, name=spans.TRANSCODE)
def transcode(src, dst_format, *, src_format: str = "utf8", n_valid=None,
              strategy: str = DEFAULT_STRATEGY, validate: bool = True,
              errors: str = "strict"):
    """Strategy-dispatched transcode for any cell of the codec matrix.

    ``src`` is the input buffer (narrow dtype or int32); ``src_format`` /
    ``dst_format`` name any two distinct formats of ``FORMATS`` (codecs
    aliases accepted).  Returns a :class:`TranscodeResult` whose buffer
    capacity is ``CAP_FACTOR[(src, dst)] * len(src)``.  See the module
    docstring for strategy / ``errors=`` semantics.
    """
    _check_errors(errors)
    src = _check_input(src)
    s = normalize_format(src_format)
    d = normalize_format(dst_format)
    _check_pair(s, d)
    if strategy == "onepass":
        from repro.kernels import onepass_transcode
        return onepass_transcode.transcode_onepass(
            src, n_valid, src=s, dst=d, validate=validate, errors=errors)
    elif strategy == "blockparallel":
        return _blockparallel_pair(src, n_valid, s, d, validate, errors)
    elif strategy == "windowed":
        if (s, d) not in _WINDOWED_PAIRS:
            raise ValueError(
                f"strategy='windowed' (the paper-faithful serial baseline) "
                f"supports utf8<->utf16 only, not {s!r} -> {d!r}")
        if errors != "strict":
            raise ValueError(
                "strategy='windowed' supports errors='strict' only "
                "(the serial baseline has no replacement path)")
        from repro.core import windowed
        if s == "utf8":
            return windowed.utf8_to_utf16_windowed(src, n_valid,
                                                   validate=validate)
        return windowed.utf16_to_utf8_windowed(src, n_valid,
                                               validate=validate)
    raise ValueError(
        f"unknown strategy: {strategy} (supported: {list(STRATEGIES)})")


def transcode_utf8_to_utf16(b, n_valid=None, *, strategy: str = DEFAULT_STRATEGY,
                            validate: bool = True, errors: str = "strict"):
    """DEPRECATED shim: use :func:`transcode` (``dst_format="utf16"``)."""
    _warn_deprecated("transcode_utf8_to_utf16",
                     'transcode(b, "utf16", src_format="utf8")')
    return transcode(b, "utf16", src_format="utf8", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


def transcode_utf16_to_utf8(u, n_valid=None, *, strategy: str = DEFAULT_STRATEGY,
                            validate: bool = True, errors: str = "strict"):
    """DEPRECATED shim: use :func:`transcode` (``dst_format="utf8"``)."""
    _warn_deprecated("transcode_utf16_to_utf8",
                     'transcode(u, "utf8", src_format="utf16")')
    return transcode(u, "utf8", src_format="utf16", n_valid=n_valid,
                     strategy=strategy, validate=validate, errors=errors)


# ---------------------------------------------------------------------------
# Ragged packed-batch entry points (one Pallas launch per batch).


@functools.partial(jax.profiler.annotate_function,
                   name=spans.RAGGED_TRANSCODE)
def ragged_transcode(data, offsets, lengths, *, src_format: str = "utf8",
                     dst_format: str = "utf16", validate: bool = True,
                     errors: str = "strict",
                     strategy: str = DEFAULT_STRATEGY,
                     n_shards=None, shard_mesh=None, chunk_budget=None):
    """Ragged packed-batch transcode for any matrix cell: ONE launch per
    batch over a :func:`repro.core.packing.pack_documents` layout.

    Returns a :class:`repro.core.result.RaggedTranscodeResult` whose
    per-document slices are bit-identical to the single-document
    transcoder; ``errors=`` carries the usual strict/replace policy per
    document.  This is the padding-tax-free batch path (DESIGN.md §7).
    ``strategy="onepass"`` (default) is the single-pass launch with the
    segment scan carried in SMEM (DESIGN.md §9);
    ``strategy="sharded"`` splits the packed batch across the data axis
    of a device mesh with one onepass launch per shard (DESIGN.md §12 —
    ``n_shards`` / ``shard_mesh`` / ``chunk_budget`` apply only there)
    and gathers a bit-identical result.
    """
    if strategy == "sharded":
        from repro.core import shard
        return shard.ragged_transcode_sharded(
            data, offsets, lengths, src_format=src_format,
            dst_format=dst_format, validate=validate, errors=errors,
            n_shards=n_shards, mesh=shard_mesh, chunk_budget=chunk_budget)
    if strategy != "onepass":
        raise ValueError(
            f"ragged_transcode: unknown strategy {strategy!r} "
            f"(supported: {list(RAGGED_STRATEGIES)})")
    if n_shards is not None or shard_mesh is not None:
        raise ValueError(
            "n_shards/shard_mesh require strategy='sharded'")
    from repro.kernels import ragged_transcode as rt
    return rt.transcode_ragged(
        data, offsets, lengths, src=normalize_format(src_format),
        dst=normalize_format(dst_format), validate=validate, errors=errors)


def ragged_scan(data, offsets, lengths, *, src_format: str = "utf8",
                dst_format: str = "utf16"):
    """Per-document single-scan validation + capacity: (counts, statuses)."""
    from repro.kernels import ragged_transcode as rt
    return rt.scan_ragged(
        data, offsets, lengths, src=normalize_format(src_format),
        dst=normalize_format(dst_format))


def ragged_utf8_to_utf16(data, offsets, lengths, *, validate: bool = True,
                         errors: str = "strict",
                         strategy: str = DEFAULT_STRATEGY):
    """DEPRECATED shim: use :func:`ragged_transcode`."""
    _warn_deprecated(
        "ragged_utf8_to_utf16",
        'ragged_transcode(data, offsets, lengths, src_format="utf8", '
        'dst_format="utf16")')
    return ragged_transcode(data, offsets, lengths, src_format="utf8",
                            dst_format="utf16", validate=validate,
                            errors=errors, strategy=strategy)


def ragged_utf16_to_utf8(data, offsets, lengths, *, validate: bool = True,
                         errors: str = "strict",
                         strategy: str = DEFAULT_STRATEGY):
    """DEPRECATED shim: use :func:`ragged_transcode`."""
    _warn_deprecated(
        "ragged_utf16_to_utf8",
        'ragged_transcode(data, offsets, lengths, src_format="utf16", '
        'dst_format="utf8")')
    return ragged_transcode(data, offsets, lengths, src_format="utf16",
                            dst_format="utf8", validate=validate,
                            errors=errors, strategy=strategy)


def ragged_scan_utf8(data, offsets, lengths):
    """DEPRECATED shim: use :func:`ragged_scan`."""
    _warn_deprecated(
        "ragged_scan_utf8",
        'ragged_scan(data, offsets, lengths, src_format="utf8", '
        'dst_format="utf16")')
    return ragged_scan(data, offsets, lengths, src_format="utf8",
                       dst_format="utf16")


def ragged_scan_utf16(data, offsets, lengths):
    """DEPRECATED shim: use :func:`ragged_scan`."""
    _warn_deprecated(
        "ragged_scan_utf16",
        'ragged_scan(data, offsets, lengths, src_format="utf16", '
        'dst_format="utf8")')
    return ragged_scan(data, offsets, lengths, src_format="utf16",
                       dst_format="utf8")


# ---------------------------------------------------------------------------
# Resumable streaming transcode (chunked input, whole-buffer-bit-exact
# results; DESIGN.md §10).  The implementation lives in
# ``repro.core.stream``; re-exported here so the streaming API rides the
# same import as the rest of the matrix.

from repro.core.stream import (  # noqa: E402,F401  (re-export)
    StreamState, finalize as stream_finalize, stream_init,
    transcode_stream, transcode_stream_chunk)
