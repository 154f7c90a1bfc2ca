"""Readings for the limits of ``correct``: the program and its control,
on many seeds, in one process.

    python benchmarks/chip/control.py --workload <name> \\
        --program-seeds 1,2,3 --control-seeds 4,5,6 --seconds 3

The configurations state no precision, so the control breaks one
guarantee a configuration states, the one a later change would be
tempted to drop.  The configuration names it as data: ``control`` holds
the keys it overrides, such as ``{"validate": false}`` (the program's
own path that skips validation) or an ``entry`` in ``controls/`` (the
reference one step below the stated format, in the program's place).
Each run is a whole run of the cell (set-up, a closed-loop window of
``--seconds``, the comparison) and prints one JSON line with the numbers
compared; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

import run


def readings(workload: str, seeds, seconds: float, control: bool, devs,
             peak) -> list:
    out = []
    for seed in seeds:
        cell = run.resolve(workload)
        if control:
            cell.config = copy.deepcopy(cell.config)
            cell.config.update(cell.config["control"])
        res = run.execute(cell, seed, seconds, False, devs, peak)
        out.append({"workload": workload, "seed": seed,
                    "variant": "control" if control else "program",
                    "correct": res["correct"], "attempted": res["attempted"],
                    "checks": res["checks"]})
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    seeds = {k: [int(s) for s in v.split(",") if s]
             for k, v in (("program", args.program_seeds),
                          ("control", args.control_seeds))}
    try:
        cell = run.resolve(args.workload)
        devs = run.prepare(cell)
        peak = run.peak_for(devs[0].device_kind)
    except run.BenchError as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 1
    readings(args.workload, seeds["program"], args.seconds, False, devs,
             peak)
    readings(args.workload, seeds["control"], args.seconds, True, devs,
             peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
