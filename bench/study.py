"""Readings for a cell's limit, in one process so that compiles are paid
once: the program on many seeds, the control on a few, and planted faults.

    python3 -m bench.study --workload <cell> --seconds <s> \\
        --seeds 11,12,... [--control-seeds 11,12,13 --controls fp8] \\
        [--fault token_altered]

Each seed is a whole run of the cell (weights, warm-up, window, sample),
with ``--fault`` broken underneath as ``bench/faults.py`` plants it.  On a
control seed the reference computed under each named control (see
``bench/references/decoder.py``) is also read on the same sample and put
through the cell's comparison in the program's place.  One JSON line per
seed: each side's readings and verdict under the cell's limits, and the
run's end-to-end metrics.  The benchmark's own runs never run a control or
a fault.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from bench import check
from bench import run as bench_run
from bench.cells import resolve
from bench.faults import FAULTS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("study: needs a TPU", file=sys.stderr)
        return 3
    bench_run.use_compile_cache()
    cell = resolve(args.workload)
    limits = bench_run.limits(cell.name)
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    kinds = [k for k in args.controls.split(",") if k]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, reference, sample = bench_run.serve(
            cell, seed, args.seconds, False, t_start=t0,
            breaker=FAULTS.get(args.fault))
        sides = {args.fault or "program": reference.served_gaps(sample)}
        if seed in control_seeds:
            sides.update({f"control_{k}": reference.control_gaps(sample, k)
                          for k in kinds})
        row = {"workload": cell.name, "seed": seed,
               "tokens": len(next(iter(sides.values()))),
               "metrics": {k: v["value"]
                           for k, v in result["metrics"].items()},
               "attempted": result["attempted"],
               "peak_bytes": result["device"]["memory_peak_bytes"],
               "seconds": time.perf_counter() - t0}
        for side, gaps in sides.items():
            verdict = check.compare(gaps, limits)
            row[side] = {"correct": verdict["ok"],
                         "readings": check.readings(gaps)}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as out:
                out.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
