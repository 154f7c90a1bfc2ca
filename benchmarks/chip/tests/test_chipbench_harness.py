"""The harness: files found by name, a run without a chip, and each cell
driven end to end at a tiny size on the CPU (Pallas interpreted), with
the program, its control and planted faults."""

import copy
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 99
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ---------------------------------------------------------------------------
# Files by name.


def test_benchmark_json_is_well_formed():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("workload", CELLS)
def test_every_name_resolves_to_its_files(workload):
    cell = run.resolve(workload)
    assert cell.spec["name"] == workload
    for fn in ("setup", "step", "check"):
        assert callable(getattr(cell.driver, fn))
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cell.readers[m["name"]].read)
    assert callable(cell.reference.transcode)
    conf = next(c for c in BENCH["configs"] if c["name"] == cell.spec["config"])
    assert conf["file"].startswith("benchmarks/chip/configs/")
    assert cell.config["name"] == conf["name"]


def test_new_cell_mix_and_metric_come_from_files_alone(tmp_path):
    """A later change adds a traffic mix, a metric reader and a cell by
    adding files and entries; the harness finds them without an edit."""
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = tmp_path / "benchmarks" / "chip"
    mix = json.loads((here / "traffic" / "lipsum-multibyte.json").read_text())
    mix["profiles"] = ["emoji"]
    (here / "traffic" / "lipsum-emoji.json").write_text(json.dumps(mix))
    (here / "metrics" / "calls_made.py").write_text(
        "def read(obs):\n    return obs.calls\n")
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append(
        {"name": "bulk.lipsum-emoji", "config": "bulk-utf8-utf16",
         "traffic": "lipsum-emoji", "chips": 1, "why": "four-byte only"})
    bench["per_layer"].append(
        {"name": "calls_made", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "entry point",
         "moves": "chars_per_s", "workloads": ["bulk.lipsum-emoji"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.resolve("bulk.lipsum-emoji", tmp_path)
    assert cell.traffic["profiles"] == ["emoji"]
    assert [m["name"] for m in cell.per_layer] == ["calls_made"]
    assert cell.readers["calls_made"].read(type("O", (), {"calls": 3})) == 3


def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    r = _run_cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "src/repro" in r.stderr


# ---------------------------------------------------------------------------
# Whole runs at a tiny size on the CPU.


def tiny(workload):
    """The cell with its sizes cut to what the interpreter runs quickly:
    8 KiB calls, or 16-tile batches of short documents."""
    cell = run.resolve(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    if "call_bytes" in cell.config:
        cell.config["call_bytes"] = 8192
        cell.traffic["pool"] = 8
    else:
        cell.config["batch_bytes_per_chip"] = 16 * 1024
        cell.traffic["pool_docs"] = 48
        cell.traffic["length"]["cap"] = 4096
        cell.traffic["sample_batches"] = 4
    return cell


def execute(cell, trace=False):
    import jax

    return run.execute(cell, SEED, 0.5, trace, jax.devices()[:1],
                       {"hbm_bytes_per_s": 819e9})


ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_a_run_is_correct(workload):
    out = execute(tiny(workload))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"chars_per_s", "setup_s"}
    assert out["metrics"]["chars_per_s"]["value"] > 0
    assert out["checks"]["uncompared"]["value"] == 0


@pytest.mark.parametrize("workload", ONE_CHIP[:1])
def test_a_traced_run_reports_per_layer_metrics(workload):
    out = execute(tiny(workload), trace=True)
    assert out["correct"] is True
    cell = run.resolve(workload)
    # No chip here, so no device op: the trace readers stay silent and
    # the harness's own readers report.
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert "transcode_roofline" not in out["metrics"]
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert out["device"]["window_s"] > 0


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_the_control_fails(workload):
    """The control each configuration names (the program's
    validate=False path, or the UCS-2 reference in its place) comes out
    not correct."""
    cell = tiny(workload)
    cell.config.update(cell.config["control"])
    out = execute(cell)
    assert out["correct"] is False, out["checks"]


def test_the_ucs2_control_narrows_only_supplementary_characters():
    import numpy as np

    ucs2 = run.load_module(CHIP / "controls" / "ucs2.py")
    text = "a\u0416\u4e00\U0001F600z"
    r = ucs2.transcode(np.frombuffer(text.encode(), np.uint8), "utf16",
                       src_format="utf8", errors="strict")
    assert (int(r.count), int(r.status)) == (5, -1)
    assert np.asarray(r.buffer)[:5].tolist() == [
        0x61, 0x416, 0x4E00, 0xF600, 0x7A]
    bad = ucs2.transcode(np.frombuffer(b"ab\xff", np.uint8), "utf16",
                         src_format="utf8", errors="strict")
    assert (int(bad.count), int(bad.status)) == (0, 2)


def _altered(fn):
    """The entry with one output unit changed where it is produced."""
    def wrapped(*a, **kw):
        r = fn(*a, **kw)
        return r._replace(buffer=r.buffer.at[0].add(1))
    return wrapped


def _half_left_out(fn):
    """The ragged entry run on the first half of the batch's documents
    only: the rest come out empty."""
    def wrapped(data, offsets, lengths, **kw):
        import jax.numpy as jnp
        n = lengths.shape[0]
        keep = jnp.arange(n) < jnp.count_nonzero(lengths) // 2
        return fn(data, offsets, jnp.where(keep, lengths, 0), **kw)
    return wrapped


FAULTS = [(w, f) for w in ONE_CHIP for f in ("altered", "half")
          if f == "altered" or w.startswith("ingest.")]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_a_planted_fault_fails(workload, fault, monkeypatch):
    from repro.core import transcode as tc

    cell = tiny(workload)
    name = cell.config["entry"].split(":")[1]
    wrap = _altered if fault == "altered" else _half_left_out
    monkeypatch.setattr(tc, name, wrap(getattr(tc, name)))
    out = execute(cell)
    assert out["correct"] is False, out["checks"]
