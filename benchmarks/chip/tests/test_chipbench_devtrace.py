"""The reduction from trace to device numbers: interval arithmetic on
synthetic intervals, and the whole reduction on traces recorded on a
TPU v5e (``data/*.xspace``, written by ``run.py --trace 1`` over a
window of 0.15 s, with the checkout prefix of their source paths
replaced by ``/checkout0/`` of the same length; ``data/*.json`` holds
what that run printed)."""

import json
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import devtrace as dt  # noqa: E402

DATA = CHIP / "tests" / "data"


@pytest.mark.parametrize("intervals,want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 2), (1, 3)], [(0, 3)]),            # overlap
    ([(0, 4), (1, 2)], [(0, 4)]),            # nested
    ([(2, 3), (0, 1)], [(0, 1), (2, 3)]),    # unsorted, disjoint
    ([(0, 1), (1, 2)], [(0, 2)]),            # touching
    ([(5, 5), (1, 0)], []),                  # empty ones drop
])
def test_union(intervals, want):
    assert dt.union(intervals) == want


def test_covered_and_gaps_inside_a_window():
    iv = [(-5, 2), (4, 6), (5, 7), (9, 20)]
    assert dt.covered(iv, (0, 10)) == 2 + 3 + 1
    assert dt.gaps(iv, (0, 10)) == [(2, 4), (7, 9)]
    assert dt.gaps([], (0, 10)) == [(0, 10)]
    assert dt.covered([(0, 10)], (2, 3)) == 1


def test_idle_and_roofline_arithmetic():
    assert dt.idle_pct(2.5, 10.0) == pytest.approx(75.0)
    # 819 MB at 819 GB/s takes 1 ms; a kernel taking 100 ms is at 1%.
    assert dt.roofline_pct(819e6, 819e9, 0.1) == pytest.approx(1.0)


def test_self_time_leaves_out_nested_ops():
    evs = [dt.Event("cond", 0, 100), dt.Event("kernel", 10, 90),
           dt.Event("fusion", 100, 110), dt.Event("kernel", 200, 210)]
    st = dt.self_times(evs)
    assert st["cond"] == pytest.approx(20e-9)
    assert st["kernel"] == pytest.approx(90e-9)
    assert st["fusion"] == pytest.approx(10e-9)


def test_idle_time_is_shared_out_among_the_host_spans():
    tr = dt.Trace({0: [dt.Event("op", 0, 10), dt.Event("op", 50, 60)]},
                  [dt.Event("bench.h2d", 8, 45), dt.Event("bench.d2h", 60, 110)],
                  (0, 110))
    assert tr.busy_s() == pytest.approx(20e-9)
    bd = tr.breakdown()
    assert bd["idle_gaps"] == [["bench.d2h", pytest.approx(50e-9)],
                               ["bench.h2d", pytest.approx(35e-9)],
                               [dt.NO_SPAN, pytest.approx(5e-9)]]
    assert bd["device_ops"] == [["op", pytest.approx(20e-9)]]


# ---------------------------------------------------------------------------
# Traces recorded on the chip.

TRACES = sorted(DATA.glob("*.xspace"))


def _recorded(path):
    import jax

    printed = json.loads(path.with_suffix(".json").read_text())
    prof = jax.profiler.ProfileData.from_serialized_xspace(path.read_bytes())
    return dt.from_profile(prof, [0]), printed


def test_traces_were_recorded():
    assert len(TRACES) >= 2


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_recorded_trace_gives_what_the_run_printed(path):
    tr, printed = _recorded(path)
    assert tr.window_s == pytest.approx(printed["device"]["window_s"])
    assert tr.busy_s() == pytest.approx(printed["device"]["busy_s"])
    assert 0 < tr.busy_s() < tr.window_s
    idle = dt.idle_pct(tr.busy_s(), tr.window_s)
    assert idle == pytest.approx(
        printed["metrics"]["device_idle_pct"]["value"])


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_recorded_trace_has_the_transcode_kernels(path):
    tr, _ = _recorded(path)
    kernels = tr.kernels()
    assert kernels, "no transcode kernel found in a window that ran one"
    assert {k.module for k in kernels} <= {"jit__transcode_impl",
                                           "jit__ragged_onepass_impl"}
    kernel_s = sum(e.end - e.start for e in kernels) / 1e9
    assert 0 < kernel_s <= tr.busy_s()
    bd = tr.breakdown()
    # The kernel takes most of the device time; every idle second is put
    # down to a harness span or to none, and adds up to the idle time.
    assert bd["device_ops"][0][0] == kernels[0].label
    assert {n for n, _ in bd["idle_gaps"]} <= {
        "bench.pack", "bench.h2d", "bench.call", "bench.d2h", dt.NO_SPAN}
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        tr.window_s - tr.busy_s())
