"""Shared runtime helpers for the Pallas kernels: execution mode + tiling.

Every ``pl.pallas_call`` wrapper in this package takes ``interpret=None``
and resolves it here: on a TPU backend the kernel compiles (Mosaic), on
anything else (CPU CI containers, GPU hosts) it runs under the Pallas
interpreter, which executes the kernel body as ordinary traced jax ops.
Callers can still force either mode explicitly — the resolved value is a
static jit argument, so both variants cache independently.

``tile_with_boundaries`` is the one place the pad-to-VMEM-tiles + zero
boundary-tile convention lives; every kernel wrapper (ops.py and the
transcode kernels) shares it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax._src.pallas.mosaic.error_handling import MosaicError
from jax._src.pallas.mosaic.lowering import LoweringException


def default_interpret() -> bool:
    """True when Pallas kernels must run interpreted (no TPU present).

    A backend that fails to initialize raises here: silently falling
    back to the interpreter would hide a missing or broken chip."""
    return jax.default_backend() != "tpu"


class LaunchTooLarge(ValueError):
    """A launch whose state cannot fit the chip (the message gives the
    limit).  Raised before compiling, instead of a compiler failure or
    a clipped result."""


def refused(exc: BaseException) -> bool:
    """True when ``exc`` is a refusal to lower, compile or size a kernel
    launch: the same program fails the same way every time, so retrying
    it or serving around it only hides a broken device path.  Pallas
    raises ``NotImplementedError`` / ``LoweringException`` (and
    ``ValueError`` for block shapes) while lowering, Mosaic raises
    ``MosaicError`` while compiling; ``LaunchTooLarge`` is a
    ``ValueError`` too.  Runtime faults (``RuntimeError``, the fault
    harness's injections) are not refusals."""
    return isinstance(exc, (NotImplementedError, ValueError, TypeError,
                            LoweringException, MosaicError))


def resolve_interpret(interpret) -> bool:
    """Resolve an ``interpret=None`` kwarg to a concrete static bool."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)


def tile_with_boundaries(x, rows: int, lanes: int, boundary_tiles: int = 2,
                         block: int = 1):
    """Pad flat ``x`` (dtype preserved) to whole (rows, lanes) tiles and
    add zero boundary tiles: one leading tile for kernels that only look
    back (``boundary_tiles=1``), one on each end for kernels with
    prev/next BlockSpecs (``boundary_tiles=2``).  Returns ``(x3, nblk)``.

    With ``block > 1`` (``boundary_tiles=2`` only) the tiles are padded
    to whole blocks of ``block`` tiles and ``block`` zero tiles lead, so
    that block ``i`` of the data is block ``i + 1`` of ``x3`` and the
    tiles before and after it are single tiles of ``x3``; ``nblk`` counts
    the padded tiles.
    """
    tile = rows * lanes
    n = x.shape[0]
    nblk = -(-max(1, -(-n // tile)) // block) * block
    pad = nblk * tile - n
    x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    x3 = x.reshape(nblk, rows, lanes)
    z = jnp.zeros((1, rows, lanes), x.dtype)
    if boundary_tiles == 1:
        return jnp.concatenate([z, x3], 0), nblk
    lead = jnp.zeros((block, rows, lanes), x.dtype)
    return jnp.concatenate([lead, x3, z], 0), nblk
