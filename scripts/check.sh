#!/usr/bin/env bash
# Fast regression gate: the tier-1 tests.  Run from anywhere; exits
# non-zero on any test failure.  Speed is measured on the chip
# (benchmarks/chip/), never here: interpret-mode timings are not speed.
#
# -e: any failing command (pytest included) aborts the script with its
#     exit code — the gate cannot silently pass over a red suite.
# -u: unset variables are errors.
# -o pipefail: a failure anywhere in a pipeline is the pipeline's status.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# --chaos: run ONLY the robustness surface — the fault-injection chaos
# suite, every streaming test (module names test_faults/test_stream and
# the test_stream_* incremental fuzz in test_differential) and the
# shard-level fault-tolerance suite (test_recovery: supervised launches,
# feeder watchdog, serve circuit breaker) — with the fixed fuzz seed CI
# pins.  Fast inner loop for robustness work.  The degraded-mesh replan
# cases need >= 8 devices, so the recovery suite's multi-device half is
# re-run under the forced 8-device host platform in a FRESH process
# (XLA locks the device count at first jax init).
if [ "${1:-}" = "--chaos" ]; then
    REPRO_FUZZ_SEED="${REPRO_FUZZ_SEED:-20260801}" \
        python -m pytest tests -k "fault or stream or recovery" -q
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        REPRO_FUZZ_SEED="${REPRO_FUZZ_SEED:-20260801}" \
        python -m pytest tests/test_recovery.py -k "8dev" -q
    exit $?
fi

# --serve: run ONLY the serving surface — the engine/serve tests.  Fast
# inner loop for scheduler work; CI runs this as its own job.
if [ "${1:-}" = "--serve" ]; then
    python -m pytest tests -k "serve or engine" -q
    exit $?
fi

# --shard: run ONLY the sharding surface — the shard planner / shard_map
# bit-identity / feeder tests plus the pipeline host-sharding pin — under
# the forced 8-device host platform, which un-skips the full 12-cell
# sharded fuzz that single-device runs skip.  CI runs this as its own
# job.
if [ "${1:-}" = "--shard" ]; then
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        REPRO_FUZZ_SEED="${REPRO_FUZZ_SEED:-20260801}" \
        python -m pytest tests -k "shard" -q
    exit $?
fi

# set -e would abort on a bare failing pytest too; capture and re-raise
# the exact code explicitly so a future edit can't swallow it.
pytest_rc=0
python -m pytest -x -q || pytest_rc=$?
if [ "$pytest_rc" -ne 0 ]; then
    echo "check.sh: pytest failed (rc=$pytest_rc)" >&2
    exit "$pytest_rc"
fi
