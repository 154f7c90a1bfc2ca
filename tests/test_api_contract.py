"""API-contract suite for the public surface (DESIGN.md §11).

Pins the redesigned API shape itself, not behavior: everything in
``repro.__all__`` imports; the generic entry points keep their
keyword-only configuration knobs; every deprecated per-pair wrapper
warns exactly once and stays bit-identical to the generic call it
delegates to; and no ``src/`` module calls a deprecated name (the
CI tier-1 jobs additionally enforce that last one at runtime with
``-W error::DeprecationWarning:repro``).
"""

import inspect
import pathlib
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.core import packing, transcode as tc
from repro.serve import engine as eng

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

S = "héllo ωorld \U0001F600 ok"
B8 = jnp.asarray(np.frombuffer(S.encode("utf-8"), np.uint8)
                 .astype(np.int32))
U16 = jnp.asarray(np.frombuffer(S.encode("utf-16-le"), np.uint16)
                  .astype(np.int32))
CP32 = jnp.asarray(np.frombuffer(S.encode("utf-32-le"), np.uint32)
                   .astype(np.int32))
L1 = jnp.asarray(np.frombuffer("héllo".encode("latin-1"), np.uint8)
                 .astype(np.int32))
# Latin-1-encodable UTF-8 (every code point <= U+00FF).
B8L = jnp.asarray(np.frombuffer("héllo".encode("utf-8"), np.uint8)
                  .astype(np.int32))

_PK8 = packing.pack_documents([b"hi", "ωorld".encode("utf-8")])
_PK16 = packing.pack_documents(
    [np.frombuffer(s.encode("utf-16-le"), np.uint16) for s in ("hi", "ωo")])
RAGGED8 = (jnp.asarray(_PK8.data), jnp.asarray(_PK8.offsets),
           jnp.asarray(_PK8.lengths))
RAGGED16 = (jnp.asarray(_PK16.data), jnp.asarray(_PK16.offsets),
            jnp.asarray(_PK16.lengths))

# Every deprecated shim with the generic call it must match bit-for-bit
# (including each shim's default strategy).
SHIM_CASES = {
    "utf8_to_utf16": ((B8,), lambda: tc.transcode(
        B8, "utf16", src_format="utf8", strategy="blockparallel")),
    "utf8_to_utf32": ((B8,), lambda: tc.transcode(
        B8, "utf32", src_format="utf8", strategy="blockparallel")),
    "utf8_to_latin1": ((B8L,), lambda: tc.transcode(
        B8L, "latin1", src_format="utf8", strategy="onepass")),
    "latin1_to_utf8": ((L1,), lambda: tc.transcode(
        L1, "utf8", src_format="latin1", strategy="onepass")),
    "latin1_to_utf16": ((L1,), lambda: tc.transcode(
        L1, "utf16", src_format="latin1", strategy="onepass")),
    "utf16_to_utf8": ((U16,), lambda: tc.transcode(
        U16, "utf8", src_format="utf16", strategy="blockparallel")),
    "utf16_to_utf32": ((U16,), lambda: tc.transcode(
        U16, "utf32", src_format="utf16", strategy="blockparallel")),
    "utf32_to_utf8": ((CP32,), lambda: tc.transcode(
        CP32, "utf8", src_format="utf32", strategy="blockparallel")),
    "utf32_to_utf16": ((CP32,), lambda: tc.transcode(
        CP32, "utf16", src_format="utf32", strategy="blockparallel")),
    "transcode_utf8_to_utf16": ((B8,), lambda: tc.transcode(
        B8, "utf16", src_format="utf8")),
    "transcode_utf16_to_utf8": ((U16,), lambda: tc.transcode(
        U16, "utf8", src_format="utf16")),
    "scan_utf8": ((B8,), lambda: tc.scan(B8, "utf16", src_format="utf8")),
    "scan_utf16": ((U16,), lambda: tc.scan(U16, "utf8",
                                           src_format="utf16")),
    "ragged_utf8_to_utf16": (RAGGED8, lambda: tc.ragged_transcode(
        *RAGGED8, src_format="utf8", dst_format="utf16")),
    "ragged_utf16_to_utf8": (RAGGED16, lambda: tc.ragged_transcode(
        *RAGGED16, src_format="utf16", dst_format="utf8")),
    "ragged_scan_utf8": (RAGGED8, lambda: tc.ragged_scan(
        *RAGGED8, src_format="utf8", dst_format="utf16")),
    "ragged_scan_utf16": (RAGGED16, lambda: tc.ragged_scan(
        *RAGGED16, src_format="utf16", dst_format="utf8")),
}


def test_every_public_name_imports():
    for name in repro.__all__:
        assert getattr(repro, name) is not None, name
    assert set(repro.__all__) <= set(dir(repro))


def test_public_symbols_are_canonical_objects():
    # The lazy exports must BE the defining modules' objects, not copies.
    assert repro.transcode is tc.transcode
    assert repro.ragged_scan is tc.ragged_scan
    assert repro.Engine is eng.Engine
    assert repro.ResultCode is eng.ResultCode


def test_unknown_attribute_raises():
    with pytest.raises(AttributeError):
        repro.utf8_to_utf16  # per-pair wrappers are NOT public


@pytest.mark.parametrize("fn,kwonly", [
    (tc.transcode, {"src_format", "n_valid", "strategy", "validate",
                    "errors"}),
    (tc.scan, {"src_format", "n_valid", "strategy"}),
    (tc.ragged_transcode, {"src_format", "dst_format", "validate",
                           "errors", "strategy"}),
    (tc.ragged_scan, {"src_format", "dst_format"}),
])
def test_generic_entry_points_keyword_only(fn, kwonly):
    params = inspect.signature(fn).parameters
    for name in kwonly:
        assert params[name].kind is inspect.Parameter.KEYWORD_ONLY, \
            f"{fn.__name__}(..., {name}=) must be keyword-only"


# The strategy names each entry point accepts.  A name outside the
# registry falls to the unknown-strategy error, which names the
# registry; every registered name is accepted.
_DOCS = np.zeros((2, 64), np.uint8)
_DOCS[0, :5] = np.frombuffer(b"hello", np.uint8)
_DOCS[1, :3] = np.frombuffer(b"abc", np.uint8)
_DOC_LENS = np.asarray([5, 3], np.int32)


def _entry(entry, strategy):
    """Call ``entry`` on a small valid input with ``strategy``."""
    if entry == "transcode":
        return tc.transcode(B8, "utf16", strategy=strategy)
    if entry == "scan":
        return tc.scan(B8, "utf16", strategy=strategy)
    if entry == "ragged_transcode":
        return tc.ragged_transcode(*RAGGED8, strategy=strategy)
    from repro.data import pipeline
    return pipeline.batch_transcode(_DOCS, _DOC_LENS, strategy=strategy)


def _registered():
    from repro.data import pipeline
    return ([("transcode", s) for s in tc.STRATEGIES]
            + [("ragged_transcode", s) for s in tc.RAGGED_STRATEGIES]
            + [("batch_transcode", s) for s in pipeline.BATCH_STRATEGIES])


# Retired with the two-launch kernels ("fused") and the vmap batch,
# whose per-document names ran a transcoder under jax.vmap.
RETIRED = [("transcode", "fused"), ("scan", "fused"),
           ("ragged_transcode", "fused"), ("batch_transcode", "fused"),
           ("batch_transcode", "vmap"), ("batch_transcode", "onepass"),
           ("batch_transcode", "blockparallel"),
           ("batch_transcode", "windowed")]


@pytest.mark.parametrize("entry,strategy", RETIRED,
                         ids=[f"{e}-{s}" for e, s in RETIRED])
def test_retired_strategy_is_refused(entry, strategy):
    with pytest.raises(ValueError,
                       match=r"unknown strategy.*supported") as e:
        _entry(entry, strategy)
    assert f"'{strategy}'" not in str(e.value).split("supported")[1]


@pytest.mark.parametrize("entry,strategy", _registered(),
                         ids=[f"{e}-{s}" for e, s in _registered()])
def test_registered_strategy_is_accepted(entry, strategy):
    res = _entry(entry, strategy)
    if entry == "batch_transcode":
        assert np.array_equal(np.asarray(res.count), [5, 3])
        assert np.array_equal(np.asarray(res.buffer)[0, :5],
                              np.frombuffer(b"hello", np.uint8))
        return
    if entry == "ragged_transcode":
        assert np.array_equal(np.asarray(res.counts), [2, 5])
        assert np.array_equal(np.asarray(res.statuses), [-1, -1])
        return
    out, cnt, status = res
    want = np.frombuffer(S.encode("utf-16-le"), np.uint16)
    assert int(status) == -1 and int(cnt) == len(want)
    assert np.array_equal(np.asarray(out)[: len(want)], want)


def test_stream_entry_point_keyword_only():
    params = inspect.signature(repro.transcode_stream).parameters
    for name in ("src_format", "dst_format", "errors", "validate"):
        assert params[name].kind is inspect.Parameter.KEYWORD_ONLY, name


def test_deprecated_registry_is_complete():
    assert set(SHIM_CASES) == set(tc.DEPRECATED)


@pytest.mark.parametrize("name", sorted(SHIM_CASES))
def test_shim_warns_once_and_matches_generic(name):
    args, generic = SHIM_CASES[name]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = getattr(tc, name)(*args)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1, f"{name}: expected exactly one warning, " \
                          f"got {[str(w.message) for w in dep]}"
    assert name in str(dep[0].message)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        want = generic()              # the generic path must NOT warn
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_l) == len(want_l), name
    for g, w in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_no_src_module_calls_deprecated_names():
    # Module-qualified calls/imports only: kernels/ops.py legitimately
    # defines same-named KERNEL entry points at a lower layer.
    names = "|".join(tc.DEPRECATED)
    call = re.compile(rf"\b(?:tc|transcode)\.({names})\s*\(")
    imp = re.compile(
        rf"from\s+repro\.core\.transcode\s+import\s+.*\b({names})\b")
    offenders = []
    for path in sorted(SRC.rglob("*.py")):
        if path.samefile(SRC / "repro" / "core" / "transcode.py"):
            continue                  # the shims' own definition site
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if call.search(line) or imp.search(line):
                offenders.append(f"{path.relative_to(SRC)}:{i}: "
                                 f"{line.strip()}")
    assert not offenders, \
        "src/ modules must use the generic API:\n" + "\n".join(offenders)


def test_result_codes_are_enum_and_strings():
    assert issubclass(eng.ResultCode, str)
    assert eng.OK is eng.ResultCode.OK
    assert eng.ResultCode.OK == "ok"
    assert eng.ResultCode.REJECTED_OVERLOAD == "rejected_overload"
    assert str(eng.ResultCode.REJECTED_DEADLINE) == "rejected_deadline"
    assert f"{eng.ResultCode.FAILED_TRANSCODE}" == "failed_transcode"
    assert eng.Result(ok=True).code is eng.ResultCode.OK


def test_engine_surface_shape():
    # submit/poll/drain are the primary surface; serve is the shim.
    for name in ("submit", "poll", "drain", "serve"):
        assert callable(getattr(eng.Engine, name)), name
    params = inspect.signature(eng.Engine.__init__).parameters
    assert "scheduler" in params
    assert params["scheduler"].default == "continuous"
