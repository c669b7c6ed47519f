"""Readings that the limits of ``correct`` are set from, for one cell, on
the chip at the cell's own size, in one process.

    python3 benchmarks/chip/calibrate.py --workload yi-6b-2L.train-full \\
        --seeds 11,12,13 --control-seeds 3 --out calib.json

Training cells: for each seed, the program's first steps through
``repro.launch.train.main`` (the benchmark's seams) against the float32 reference; the half-batch fault planted in
the feed; and, on the first ``--control-seeds`` seeds, the control (the
reference computed with fp8 operands). A step that leaves its state
unchanged reads 1 by construction and is not run.

Serving cells: for each seed, one short run of the cell's driver; per
cycle, the served tokens' widest gap, the control's and that of a served
token altered where it is produced.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

from chipbench.harness import (COMPILE_CACHE, Spans, find_cell,
                               load_benchmark)


def train_cell(cell, seeds, n_control):
    from chipbench import compare
    from chipbench.train_driver import TrainSession, reference_readings
    t = cell.traffic
    out = []
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        ref = reference_readings(cell.config, t, seed)
        for fault in (None, "half_batch"):
            with tempfile.TemporaryDirectory() as work:
                ses = TrainSession(cell, seed, os.path.join(work, "ckpt"),
                                   Spans(), [], fault)
                ses.main(t["checked_steps"])
                prog = ses.readings()
            row[fault or "program"] = compare.train_numbers(prog, ref, 0.0)
        if i < n_control:
            ctl = reference_readings(cell.config, t, seed, "fp8")
            row["control"] = compare.train_numbers(ctl, ref, 0.0)
        row["loss_ref"] = ref["loss"]
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def serve_cell(cell, seeds, seconds):
    from chipbench import serve_driver
    out = []
    for seed in seeds:
        with tempfile.TemporaryDirectory() as work:
            rec = serve_driver.run(cell, seed, seconds, False, work,
                                   control=True)
        row = {"seed": seed, "program": rec.counters["served_gap"],
               "control": rec.counters["control_gap"],
               "altered_token": rec.counters["altered_gap"],
               "params_mismatch": [c.value for c in rec.compared
                                   if c.name == "params_mismatch"],
               "cycles": rec.counters["cycles"],
               "save_to_served_s": rec.end_to_end["save_to_served_s"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from chipbench.program import import_program
    import_program()
    cell = find_cell(load_benchmark(), args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    t0 = time.perf_counter()
    if cell.traffic["driver"] == "train":
        rows = train_cell(cell, seeds, args.control_seeds)
    else:
        rows = serve_cell(cell, seeds, args.seconds)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows,
                   "device": jax.devices()[0].device_kind,
                   "seconds": time.perf_counter() - t0}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
