"""Readings that a cell's limits are set from, taken on the chip in one
process (this is not one of the benchmark's own runs):

* the program, as the configuration states it, on ``--seeds`` seeds: the
  lower reading of each number compared is the largest of these;
* the control, the program with its matmul precision one step below the
  configuration's (``CONTROL_PRECISION``: one bfloat16 pass), on the first
  ``--control-seeds`` of them: the upper reading is the smallest of these.

The numbers read are the checks that the configuration's family names in
its ``TOLERANCE_CHECKS``: those whose limits the configuration sets.

    python3 bench/control.py --workload <name> --seeds 12 --seconds 3

Prints one JSON line per run and a summary line last: for each of those
checks, its readings on both sides, its lower and its upper reading.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import run as bench_run  # noqa: E402
from bench import spec  # noqa: E402

#: the matmul precision one step below the configurations' ``highest``
CONTROL_PRECISION = "default"
#: seed k of a reading is FIRST_SEED + 7919 * k
FIRST_SEED = 3_000_000_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        return bench_run.fail(f"{cell.name} needs {cell.chips} TPU chip(s)")
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(bench_run.CACHE_DIR, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = spec.load_json(os.path.join(ROOT, "bench", "peaks.json"))[
        devices[0].device_kind]
    control = dataclasses.replace(cell, config={
        **cell.config, "matmul_precision": CONTROL_PRECISION})
    names = cell.family.TOLERANCE_CHECKS
    readings = {n: {"program": [], "control": []} for n in names}
    for k in range(args.seeds):
        seed = FIRST_SEED + 7919 * k
        sides = [("program", cell)]
        if k < args.control_seeds:
            sides.append(("control", control))
        for side, c in sides:
            res = bench_run.run_cell(c, seed, args.seconds, False,
                                     devices[:cell.chips],
                                     time.perf_counter(), peaks)
            for n in names:
                readings[n][side].append(res["checks"][n]["value"])
            print(json.dumps({"side": side, "seed": seed, "correct":
                              res["correct"], "attempted": res["attempted"],
                              "checks": res["checks"]}), flush=True)
    print(json.dumps({"workload": cell.name, "checks": {n: {
        "readings": r, "lower": max(r["program"]),
        "upper": min(r["control"]) if r["control"] else None}
        for n, r in readings.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
