"""simdutf-style transcode result: (buffer, count, status).

Every ``repro.core`` transcoder and the onepass Pallas pipeline return a
:class:`TranscodeResult` — a NamedTuple (so it unpacks like the
historical 3-tuple and traverses as a jax pytree under
``jit``/``vmap``/``lax.cond``) whose third element is an int32
**status** instead of a bare validity bool.  (The legacy kernel path
``repro.kernels.ops`` still returns its historical ``(buffer, count,
bool-err)`` triple.)  Status semantics:

  * ``status == STATUS_OK`` (-1): the input was valid (or ``validate``
    was off) and ``buffer[:count]`` is the faithful transcode.
  * ``status >= 0``: the offset — in *input elements*: bytes for UTF-8,
    code units for UTF-16, code points for UTF-32 — of the first invalid
    maximal subpart, exactly where Python's ``bytes.decode`` reports
    ``UnicodeDecodeError.start``.  Under ``errors="strict"`` the buffer
    holds the speculative (reject-wholesale) output; under
    ``errors="replace"`` the buffer is still a complete, valid transcode
    with U+FFFD substituted per maximal subpart and ``status`` tells the
    caller where the first substitution happened.

This is the accelerator form of simdutf's ``result { error; count; }``:
one scan yields the transcode, the validity verdict *and* the error
location (arXiv:2111.08692 §"unicode at gigabytes per second" makes the
case for error-locating single-scan APIs at the ingestion boundary).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

STATUS_OK = -1

ERROR_POLICIES = ("strict", "replace")


def check_errors_policy(errors: str) -> None:
    """Validate an ``errors=`` kwarg (shared by every transcoder entry)."""
    if errors not in ERROR_POLICIES:
        raise ValueError(
            f"errors= must be one of {ERROR_POLICIES}: {errors!r}")

# Sentinel used while reducing per-tile first-error indices: any real
# offset is smaller, so min() over tiles recovers the global first error.
NO_ERR_SENTINEL = 2**31 - 1


class TranscodeResult(NamedTuple):
    """(buffer, count, status) — unpacks like the legacy 3-tuple."""

    buffer: jax.Array
    count: jax.Array    # int32: meaningful elements in ``buffer``
    status: jax.Array   # int32: STATUS_OK or first-error input offset

    @property
    def err(self) -> jax.Array:
        """Legacy validity flag: True iff the input stream was invalid."""
        return self.status >= 0

    @property
    def ok(self) -> jax.Array:
        return self.status < 0


class RaggedTranscodeResult(NamedTuple):
    """Per-batch result of a ragged packed transcode (one kernel launch).

    The per-document fields carry exactly the :class:`TranscodeResult`
    semantics, element-wise: document ``d``'s output occupies
    ``buffer[offsets[d] : offsets[d] + counts[d]]`` (a *dense* packed
    stream — no inter-document padding), ``counts[d]`` is its output
    element count and ``statuses[d]`` its int32 status (``STATUS_OK`` or
    the first-error offset *relative to the document's own start*, with
    Python ``UnicodeDecodeError.start`` semantics).
    """

    buffer: jax.Array    # dense packed output stream (uint16 / uint8)
    offsets: jax.Array   # int32 [B+1]: per-document output row offsets
    counts: jax.Array    # int32 [B]: per-document output element counts
    statuses: jax.Array  # int32 [B]: STATUS_OK or doc-relative offset

    @property
    def ok(self) -> jax.Array:
        return self.statuses < 0


def first_error_status(err_map, n):
    """Min-reduce a per-position error map into an int32 status.

    Only positions in the live region ``[0, n)`` count; returns
    ``STATUS_OK`` when the map is clean there.  The single definition of
    the reduce every strategy (blockparallel, windowed, the kernels'
    per-tile variant) derives its status from.
    """
    idx = jnp.arange(err_map.shape[0])
    errpos = jnp.where(err_map & (idx < n), idx, NO_ERR_SENTINEL)
    return status_from_first(jnp.min(errpos, initial=NO_ERR_SENTINEL))


def status_from_first(first_index, err_any=None):
    """Fold a min-reduced first-error index (NO_ERR_SENTINEL = clean) and
    an optional independent error flag into one int32 status.

    ``err_any`` is a belt-and-braces flag from a second detector (the
    Keiser-Lemire nibble tables in the kernels' count): if it fires
    without a located position — the detectors are equivalent, so this
    should never happen — the status degrades to offset 0 rather than
    silently reporting a valid stream.
    """
    first = jnp.asarray(first_index, jnp.int32)
    located = first != NO_ERR_SENTINEL
    if err_any is None:
        return jnp.where(located, first, jnp.int32(STATUS_OK))
    flagged = located | err_any
    pos = jnp.where(located, first, jnp.int32(0))
    return jnp.where(flagged, pos, jnp.int32(STATUS_OK))
