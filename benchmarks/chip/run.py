"""Run one benchmark cell once, on the chips of this machine.

    python benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration (``configs/<config>.json``), its
traffic mix (``traffic/<traffic>.json``), the driver the configuration
names for the cell's chip count (``drivers/<driver>.py``), and one reader
per per-layer metric (``metrics/<metric>.py``).  A new cell, mix or
metric is a new file and a new entry, never an edit here.

A run makes its inputs from ``--seed``, warms every shape its traffic
uses (set-up), then drives the configuration's entry point in a closed
loop for ``--seconds``: one caller, each call waiting for its result
before the next starts.  Once the window has closed it compares a
seeded sample of the window's results with the configuration's plain
reference.  With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` it records the profiler trace of the window and
reports the per-layer metrics.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, optionally ``breakdown``, and ``checks``: each number
compared, beside its limit).  Without a TPU, or with fewer chips than
the cell needs, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))


class BenchError(Exception):
    """The run cannot produce a result (missing file, no chip, ...)."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name.


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        "-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: dict, e2e_names=None) -> bool:
    """A metric with a ``workloads`` list applies to those cells; one
    without applies to every cell (end-to-end), or to every cell that
    reports the end-to-end metric it moves (per-layer)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


@dataclasses.dataclass
class Cell:
    spec: dict               # the workload entry
    config: dict             # configs/<config>.json
    traffic: dict            # traffic/<traffic>.json
    driver: object           # drivers/<driver>.py
    end_to_end: list         # metric entries of BENCHMARK.json
    per_layer: list
    readers: dict            # per-layer metric name -> metrics/<name>.py
    reference: object        # references/<reference>.py


def resolve(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """Every file of one cell, by the names in ``BENCHMARK.json`` of the
    checkout at ``root``."""
    here = root / HERE.relative_to(ROOT)
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[cell["config"]]["file"])
    traffic = _read_json(here / "traffic" / f"{cell['traffic']}.json")
    drivers = config["drivers"]
    if str(cell["chips"]) not in drivers:
        raise BenchError(f"configuration {cell['config']!r} names no driver "
                         f"for {cell['chips']} chip(s)")
    driver = load_module(here / "drivers" / f"{drivers[str(cell['chips'])]}.py")
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, cell, e2e_names)]
    readers = {m["name"]: load_module(here / "metrics" / f"{m['name']}.py")
               for m in per_layer}
    reference = load_module(here / "references" / f"{config['reference']}.py")
    return Cell(cell, config, traffic, driver, e2e, per_layer, readers,
                reference)


# ---------------------------------------------------------------------------
# What a run observes: spans, counters, per-call records.


class Spans:
    """The harness's own spans around the calls into each layer: host
    seconds per span name, and a profiler annotation of the same name so
    that a trace can say what the host was doing in a device gap."""

    def __init__(self):
        self.seconds: dict[str, list] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t0)

    def clear(self):
        self.seconds.clear()


class CompileCounter:
    """Programs traced and lowered: one per jit cache miss, whether or
    not the persistent cache then supplies the executable."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Observed:
    """What the per-layer readers read (``metrics/<name>.py``)."""

    calls: int
    latencies_s: list         # every call of the window, seconds
    spans: dict               # span name -> seconds per call
    window_compiles: int
    kernel_bytes: int         # bytes the window's kernel launches need
    peak: dict                # peaks.json entry of the device kind
    trace: object = None      # devtrace.Trace of the window, or None


def run_window(driver, state, seconds: float, spans: Spans,
               compiles: CompileCounter):
    """The closed loop: each call waits for its result before the next."""
    spans.clear()
    c0 = compiles.count
    chars = kbytes = calls = 0
    lat = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t1 = t0
    while t1 < deadline:
        s = time.perf_counter()
        rec = driver.step(state, spans)
        t1 = time.perf_counter()
        lat.append(t1 - s)
        chars += rec.chars
        kbytes += rec.kernel_bytes
        calls += 1
    return dict(chars=chars, kernel_bytes=kbytes, calls=calls,
                window_s=t1 - t0, latencies_s=lat,
                window_compiles=compiles.count - c0)


def check_device(chips: int):
    """The platform and devices, or BenchError without a TPU."""
    import jax

    from repro.kernels import runtime

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    if runtime.resolve_interpret(None) is not False:
        raise BenchError("Pallas kernels would run interpreted here")
    return devs


def peak_for(kind: str) -> dict:
    peaks = _read_json(HERE / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def format_checks(checks: dict) -> tuple[bool, dict]:
    """``{name: (value, limit)}`` -> (every value within its limit, the
    JSON form)."""
    ok = all(v <= lim for v, lim in checks.values())
    return ok, {k: {"value": v, "limit": lim} for k, (v, lim) in
                checks.items()}


def execute(cell: Cell, seed: int, seconds: float, trace_on: bool,
            devs, peak: dict) -> dict:
    """One run of ``cell`` on ``devs``, whose peaks are ``peak``: set-up,
    window, checks, metrics.  Returns the result object (without
    printing it)."""
    import jax

    import devtrace as tr

    chips = cell.spec["chips"]
    used = devs[:chips]
    compiles = CompileCounter()
    spans = Spans()
    state = cell.driver.setup(cell.config, cell.traffic, seed, used,
                              cell.reference)
    setup_s = time.perf_counter() - T_START

    trace_dir = TRACE_DIR / cell.spec["name"]
    if trace_on:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # spans and ops, not every call
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        w = run_window(cell.driver, state, seconds, spans, compiles)
    trace = None
    if trace_on:
        jax.profiler.stop_trace()
        trace = tr.load(trace_dir, [d.id for d in used])
    mem = memory_peak(used)
    checks = cell.driver.check(state)
    del state
    correct, checks_json = format_checks(checks)

    kind = used[0].device_kind
    obs = Observed(calls=w["calls"], latencies_s=w["latencies_s"],
                   spans=spans.seconds, window_compiles=w["window_compiles"],
                   kernel_bytes=w["kernel_bytes"], peak=peak, trace=trace)
    metrics = {}
    if not trace_on:
        values = {"chars_per_s": w["chars"] / w["window_s"] / 1e6,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": w["calls"], "failed": 0,
           "metrics": metrics, "device": device}
    if trace_on:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        out["breakdown"] = trace.breakdown()
    out["checks"] = checks_json
    return out


def prepare(cell: Cell):
    """The program on the path, its compile cache on, and the cell's
    chips: the devices, or BenchError."""
    # The TPU runtime would otherwise log to a fixed directory under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The persistent compile cache lives in the checkout, at one fixed
    # path, whatever directory the environment names.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        raise BenchError(f"the program (src/repro) is not in this "
                         f"checkout: {e}") from e
    compile_cache.enable()
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))

    # Every program goes to the persistent cache, however quickly it
    # compiled, so a second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return check_device(cell.spec["chips"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve(args.workload)
        devs = prepare(cell)
        out = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peak_for(devs[0].device_kind))
    except BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
