"""jit'd public wrappers around the Pallas kernels.

Each op pads the flat input to whole (ROWS, LANES) VMEM tiles (adding the
zero boundary tiles the kernels' prev/next BlockSpecs expect), invokes the
kernel, and strips the padding.  Execution mode is auto-detected
(``repro.kernels.runtime``): kernels run interpreted on CPU hosts and
compiled on TPU; pass ``interpret=True/False`` to force either.

The kernel-backed transcoders compose a Pallas compute stage (per-lane
classification + bit surgery + fused validation) with an XLA compaction
stage (cumsum + scatter) — the TPU-native split of the paper's
"decode-in-register, then pshufb-compress" structure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compaction
from repro.core import utf16 as u16mod
from repro.kernels import runtime
from repro.kernels import utf8_decode as kdec
from repro.kernels import utf8_validate as kval
from repro.kernels import utf16_encode as kenc

ROWS, LANES, BLOCK = kdec.ROWS, kdec.LANES, kdec.BLOCK


def _mask_padding(x, n_valid):
    x = x.astype(jnp.int32)
    if n_valid is None:
        return x, x.shape[0]
    idx = jnp.arange(x.shape[0])
    return jnp.where(idx < n_valid, x, 0), n_valid


def _tile(x, boundary_tiles: int):
    """Pad flat int32 x to whole BLOCK tiles + zero boundary tiles."""
    return runtime.tile_with_boundaries(x, ROWS, LANES, boundary_tiles)


def validate_utf8(b, n_valid=None, interpret=None):
    """Keiser-Lemire validation via the Pallas kernel.  Scalar bool."""
    return _validate_utf8_jit(b, n_valid, runtime.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _validate_utf8_jit(b, n_valid, interpret):
    b, n = _mask_padding(b, n_valid)
    b3, _ = _tile(b, boundary_tiles=1)
    errs = kval._call(b3, interpret=interpret)
    # Tail truncation (needs the logical length; checked outside the kernel).
    return (jnp.max(errs) == 0) & ~kdec.tail_lead_err(b, n)


def decode_utf8(b, n_valid=None, interpret=None):
    """Per-position speculative decode via the Pallas kernel.

    Returns (cp, lead, units, err) over the original buffer length.
    """
    return _decode_utf8_jit(b, n_valid, runtime.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_utf8_jit(b, n_valid, interpret):
    b, n = _mask_padding(b, n_valid)
    cap = b.shape[0]
    b3, nblk = _tile(b, boundary_tiles=2)
    cp, lead, units, errs = kdec._call(b3, interpret=interpret)
    cp = cp.reshape(-1)[:cap]
    lead = lead.reshape(-1)[:cap]
    units = units.reshape(-1)[:cap]
    return cp, lead, units, (jnp.max(errs) > 0) | kdec.tail_lead_err(b, n)


def utf8_to_utf16(b, n_valid=None, interpret=None, validate: bool = True):
    """Kernel-backed UTF-8 -> UTF-16 transcode.  (buffer, count, err)."""
    return _utf8_to_utf16_jit(b, n_valid, runtime.resolve_interpret(interpret),
                              validate)


@functools.partial(jax.jit, static_argnames=("interpret", "validate"))
def _utf8_to_utf16_jit(b, n_valid, interpret, validate):
    b, n = _mask_padding(b, n_valid)
    cap = b.shape[0]
    cp, lead, units, dec_err = _decode_utf8_jit(b, None, interpret)
    idx = jnp.arange(cap)
    mask = (lead > 0) & (idx < n)
    _, u0, u1, _bad = u16mod.encode_candidates(cp)
    vals = jnp.stack([u0, u1], -1)
    out, count = compaction.compact_offsets(vals, units, mask, cap)
    err = dec_err if validate else jnp.bool_(False)
    if validate:
        err = err | ~_validate_utf8_jit(b, n, interpret)
    return out, count, err


def utf16_to_utf8(u, n_valid=None, interpret=None, validate: bool = True):
    """Kernel-backed UTF-16 -> UTF-8 transcode.  (buffer, count, err)."""
    return _utf16_to_utf8_jit(u, n_valid, runtime.resolve_interpret(interpret),
                              validate)


@functools.partial(jax.jit, static_argnames=("interpret", "validate"))
def _utf16_to_utf8_jit(u, n_valid, interpret, validate):
    u, n = _mask_padding(u, n_valid)
    cap_in = u.shape[0]
    cap = 3 * cap_in
    u3, nblk = _tile(u, boundary_tiles=2)
    b0, b1, b2, b3, L, errs = kenc._call(u3, interpret=interpret)
    flat = lambda t: t.reshape(-1)[:cap_in]
    cand = jnp.stack([flat(b0), flat(b1), flat(b2), flat(b3)], -1)
    L = flat(L)
    idx = jnp.arange(cap_in)
    mask = (L > 0) & (idx < n)
    out, count = compaction.compact_offsets(cand, L, mask, cap)
    err = (jnp.max(errs) > 0) if validate else jnp.bool_(False)
    return out, count, err
