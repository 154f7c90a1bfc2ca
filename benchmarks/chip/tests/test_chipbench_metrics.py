"""Every per-layer reader: silent when it has nothing to read, and the
number it reads when it has."""

import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import devtrace as dt  # noqa: E402
import run  # noqa: E402

READERS = sorted(p.stem for p in (CHIP / "metrics").glob("*.py"))


def _obs(**kw):
    base = dict(calls=4, latencies_s=[0.01, 0.02, 0.03, 0.04], spans={},
                window_compiles=0, kernel_bytes=0,
                peak={"hbm_bytes_per_s": 819e9}, trace=None)
    base.update(kw)
    return run.Observed(**base)


def _reader(name):
    return run.load_module(CHIP / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", ["transcode_roofline",
                                  "device_idle_pct", "pack_ms", "h2d_ms",
                                  "d2h_ms"])
def test_silent_with_nothing_to_read(name):
    assert _reader(name).read(_obs(latencies_s=[])) is None


def test_every_reader_is_named_in_the_benchmark():
    import json

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(READERS) == {m["name"] for m in bench["per_layer"]}


def test_span_means_in_milliseconds():
    obs = _obs(spans={"bench.pack": [0.001, 0.003], "bench.h2d": [0.002],
                      "bench.d2h": [0.004, 0.004]})
    assert _reader("pack_ms").read(obs) == pytest.approx(2.0)
    assert _reader("h2d_ms").read(obs) == pytest.approx(2.0)
    assert _reader("d2h_ms").read(obs) == pytest.approx(4.0)


def test_call_tail_and_compiles():
    lat = [i / 1000 for i in range(1, 101)]
    assert _reader("call_p95_ms").read(_obs(latencies_s=lat)) == \
        pytest.approx(95.05)
    assert _reader("window_compiles").read(_obs(window_compiles=3)) == 3


def _kernel(start, end):
    return dt.Event('%k.1 = u16[8] custom-call(u8[8] %x), '
                    'custom_call_target="tpu_custom_call"', start, end,
                    "jit__transcode_impl")


def test_roofline_from_kernel_time_and_bytes():
    # Two 1 ms kernels moving 819 kB each: 1 us each at the peak, 0.1%.
    ops = [_kernel(0, 1e6), dt.Event("%fusion = fusion()", 1e6, 2e6),
           _kernel(3e6, 4e6), _kernel(2e7, 3e7)]     # the last one is late
    tr = dt.Trace({0: ops}, [], (0, 1e7))
    obs = _obs(trace=tr, kernel_bytes=2 * 819_000)
    assert _reader("transcode_roofline").read(obs) == \
        pytest.approx(0.1)
    assert _reader("device_idle_pct").read(obs) == pytest.approx(70.0)
    assert tr.breakdown()["device_ops"][0] == [
        "jit__transcode_impl/%k.1", pytest.approx(2e-3)]


def test_no_kernel_in_the_window_reads_nothing():
    tr = dt.Trace({0: [dt.Event("fusion", 0, 10)]}, [], (0, 100))
    obs = _obs(trace=tr, kernel_bytes=1000)
    assert _reader("transcode_roofline").read(obs) is None
