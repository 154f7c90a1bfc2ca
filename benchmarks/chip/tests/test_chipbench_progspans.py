"""The reader of the program's spans (``progspans``) and the metrics built
on it: idle time put down to the innermost span, dispatches counted once,
silence without the program's spans; and, on traces recorded on a TPU
v5e (``data/*.spans.xspace``, written by ``run.py --trace 1`` over a
window of about 0.15 s, with the checkout prefix of their source paths
replaced by ``/checkout0/`` of the same length; ``data/*.spans.json``
holds what that run printed), the idle inside the harness's call split
between the program's spans and the runtime."""

import json
import pathlib
import shutil
import sys
from types import SimpleNamespace as NS

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

import devtrace as dt  # noqa: E402
import progspans as ps  # noqa: E402
import run  # noqa: E402

DATA = CHIP / "tests" / "data"
NEW = ["dispatch_ms", "dispatches_per_call", "layout_check_ms",
       "pack_documents_ms", "runtime_idle_ms"]
WINDOW = (0.0, 100.0)
PJIT = "PjitFunction(_ragged_onepass_impl)"


def _profile(*lines):
    """A stand-in for ``jax.profiler.ProfileData``: one host plane with
    these lines of ``(name, start, end)`` events."""
    return NS(planes=[NS(name="/device:TPU:0", lines=[]), NS(
        name=dt.HOST_PLANE, lines=[
            NS(name=f"t{k}", events=[NS(name=n, start_ns=s,
                                        duration_ns=e - s)
                                     for n, s, e in evs])
            for k, evs in enumerate(lines)])])


# One ingest call on the window's thread (ns):
ONE_CALL = [
    (dt.WINDOW_SPAN, *WINDOW),
    ("bench.call", 10, 60),
    ("repro.ragged_transcode", 12, 40),
    ("repro.ragged.check_layout", 13, 25),
    ("repro.ragged.dispatch", 26, 38),
    (PJIT, 27, 37), (PJIT, 27, 36.5),       # nested twice, one launch
    ("PythonRefManager::CollectGarbage", 30, 31),
    ("bench.pack", 60, 80),
    ("repro.pack_documents", 61, 75),
    ("PjitFunction(convert_element_type)", 76, 78),   # not in an entry
]
# The device runs 45-55 (the kernel, after the program returned) and
# 85-90; the rest of the window is idle.
OPS = {0: [dt.Event("k", 45, 55), dt.Event("f", 85, 90)]}
WANT_IDLE = {
    (): 10 + 5 + 10,
    ("bench.call",): 2 + 5 + 5,
    ("bench.call", "repro.ragged_transcode"): 1 + 1 + 2,
    ("bench.call", "repro.ragged_transcode",
     "repro.ragged.check_layout"): 12,
    ("bench.call", "repro.ragged_transcode", "repro.ragged.dispatch"): 12,
    ("bench.pack",): 1 + 5,
    ("bench.pack", "repro.pack_documents"): 14,
}


def _host():
    # Another thread's spans are not the caller's.
    other = [("repro.transcode", 0, 99), ("bench.call", 1, 2)]
    return ps.from_profile(_profile(other, ONE_CALL), WINDOW)


def test_span_names_match_the_program():
    from repro import spans

    program = {v for k, v in vars(spans).items() if k.isupper()}
    read = set(ps.ENTRY_SPANS + ps.DISPATCH_SPANS) | {
        ps.LAYOUT_CHECK_SPAN, ps.PACK_SPAN}
    assert read == program


def test_spans_and_dispatches_of_the_windows_thread():
    host = _host()
    assert [e.name for e in host.spans] == [
        "bench.call", "repro.ragged_transcode", "repro.ragged.check_layout",
        "repro.ragged.dispatch", "bench.pack", "repro.pack_documents"]
    assert [(e.name, e.start, e.end) for e in host.dispatches] == [
        (PJIT, 27, 37), ("PjitFunction(convert_element_type)", 76, 78)]
    assert host.mean_ms("repro.ragged.check_layout") == pytest.approx(12e-6)
    assert host.mean_ms("repro.transcode.dispatch") is None
    assert host.calls() == 1


def test_idle_goes_to_the_innermost_span():
    idle = _host().idle_by_path(dt.Trace(OPS, [], WINDOW))
    assert idle == pytest.approx({k: v / 1e9 for k, v in WANT_IDLE.items()})
    assert sum(idle.values()) == pytest.approx(85e-9)


def test_innermost_partitions_the_window():
    evs = [dt.Event("a", -5, 30), dt.Event("b", 10, 20),
           dt.Event("c", 20, 20), dt.Event("d", 90, 120)]
    assert ps.innermost(evs, WINDOW) == [
        (0, 10, ("a",)), (10, 20, ("a", "b")), (20, 30, ("a",)),
        (30, 90, ()), (90, 100, ("d",))]


def test_each_launch_counts_once_per_call():
    two_calls = ONE_CALL + [
        ("repro.ragged_transcode", 81, 99),
        ("repro.ragged.dispatch", 82, 98),
        (PJIT, 83, 90), (PJIT, 83, 89),
        ("PjitFunction(convert_element_type)", 91, 95),
        ("PjitFunction(convert_element_type)", 91, 94),
    ]
    host = ps.from_profile(_profile(two_calls), WINDOW)
    assert host.dispatches_per_call() == pytest.approx(3 / 2)


def test_nothing_without_the_programs_spans():
    harness_only = [e for e in ONE_CALL if not e[0].startswith("repro.")]
    assert ps.from_profile(_profile(harness_only), WINDOW) is None
    # A window that no thread holds.
    assert ps.from_profile(_profile(ONE_CALL), (0.0, 99.0)) is None


def _obs(trace):
    return run.Observed(calls=1, latencies_s=[0.05], spans={},
                        window_compiles=0, kernel_bytes=0,
                        peak={"hbm_bytes_per_s": 819e9}, trace=trace)


def _reader(name):
    return run.load_module(CHIP / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_are_silent_with_nothing_to_read(name, monkeypatch):
    monkeypatch.setattr(ps, "_load", lambda _w: None)
    assert _reader(name).read(_obs(None)) is None
    # A trace without the program's spans, as an older checkout writes.
    assert _reader(name).read(_obs(dt.Trace(OPS, [], WINDOW))) is None
    # A trace without a chip in it: no device-trace number.
    monkeypatch.setattr(ps, "_load", lambda _w: _host())
    assert _reader(name).read(_obs(dt.Trace({}, [], WINDOW))) is None


def test_new_readers_read_the_programs_spans(monkeypatch):
    monkeypatch.setattr(ps, "_load", lambda _w: _host())
    obs = _obs(dt.Trace(OPS, [], WINDOW))
    got = {n: _reader(n).read(obs) for n in NEW}
    assert got == pytest.approx({
        "dispatch_ms": 12e-6, "dispatches_per_call": 1.0,
        "layout_check_ms": 12e-6, "pack_documents_ms": 14e-6,
        "runtime_idle_ms": 12e-6})


# ---------------------------------------------------------------------------
# Traces recorded on the chip.

SPAN_TRACES = sorted(DATA.glob("*.spans.xspace"))
OLD_TRACES = sorted(set(DATA.glob("*.xspace")) - set(SPAN_TRACES))


def _recorded(path):
    import jax

    printed = json.loads(path.with_suffix(".json").read_text())
    prof = jax.profiler.ProfileData.from_serialized_xspace(path.read_bytes())
    trace = dt.from_profile(prof, [0])
    return prof, trace, printed


def test_span_traces_were_recorded():
    assert {p.name.split(".")[0] for p in SPAN_TRACES} == {"bulk", "ingest"}


@pytest.mark.parametrize("path", OLD_TRACES, ids=lambda p: p.stem)
def test_a_program_without_spans_reads_nothing(path):
    prof, trace, _ = _recorded(path)
    assert ps.from_profile(prof, trace.window) is None


@pytest.mark.parametrize("path", SPAN_TRACES, ids=lambda p: p.stem)
def test_call_idle_splits_into_program_spans_and_runtime(path):
    prof, trace, printed = _recorded(path)
    host = ps.from_profile(prof, trace.window)
    idle = host.idle_by_path(trace)
    in_call = dict(trace.breakdown()["idle_gaps"])[ps.CALL_SPAN]
    under_program = sum(s for p, s in idle.items()
                        if p[:1] == (ps.CALL_SPAN,) and len(p) > 1)
    runtime_ms = printed["metrics"]["runtime_idle_ms"]["value"]
    assert under_program > 0
    assert under_program + runtime_ms / 1e3 * host.calls() == \
        pytest.approx(in_call, rel=0.01)


@pytest.mark.parametrize("path", SPAN_TRACES, ids=lambda p: p.stem)
def test_recorded_trace_gives_the_printed_span_metrics(
        path, tmp_path, monkeypatch):
    """``for_run`` finds the run's file by its window among others, and
    every new metric the cell lists reads what the run printed."""
    _, trace, printed = _recorded(path)
    for k, other in enumerate([path] + OLD_TRACES):
        d = tmp_path / f"run{k}" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        shutil.copy(other, d / "host.xplane.pb")
    monkeypatch.setattr(ps, "TRACE_DIR", tmp_path)
    ps._load.cache_clear()
    try:
        obs = _obs(trace)
        cell = path.name.split(".spans.")[0]
        listed = [m["name"] for m in run.resolve(cell).per_layer
                  if m["name"] in NEW]
        assert listed
        for name in listed:
            assert _reader(name).read(obs) == pytest.approx(
                printed["metrics"][name]["value"]), name
    finally:
        ps._load.cache_clear()
