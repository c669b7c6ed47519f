"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload yi-6b-2L.train-full \\
        --seed 1234 --seconds 45 --trace 0

The cell's configuration, traffic and correctness limits are found by
name under this directory (see ``chipbench/harness.py``). With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of one
save cycle. The run fails, and prints no result, where JAX finds no TPU
or fewer chips than the cell asks for. The last line of standard output
is the result; the numbers compared for ``correct`` are the last lines of
standard error too.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import json  # noqa: E402

from chipbench.harness import (COMPILE_CACHE, BenchError, find_cell,  # noqa: E402
                               load_benchmark, load_reader, peaks_for,
                               result_line)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, *, require_tpu: bool = True, fault=None, cell=None,
            tracer_selectors=None, process_start: float = PROCESS_START):
    """Run the cell; returns the result line (a dict) and the run's
    record. ``require_tpu``, ``fault``, ``cell`` and ``tracer_selectors``
    exist for the tests, which drive a run on the CPU at a toy size."""
    bench = load_benchmark()
    import jax
    if require_tpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
    cell = cell or find_cell(bench, args.workload)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise BenchError(f"this cell runs on {cell.chips} TPU chip(s); JAX "
                         f"found {len(devices)} {dev.platform} device(s)")
    peaks = peaks_for(dev.device_kind) if require_tpu else None

    from chipbench.program import import_program
    import_program()
    from chipbench import serve_driver, train_driver
    driver = {"train": train_driver,
              "serve_refresh": serve_driver}[cell.traffic["driver"]]
    from chipbench.trace import Tracer
    with tempfile.TemporaryDirectory(prefix="chipbench_") as work:
        tracer = Tracer(os.path.join(work, "trace"),
                        **(tracer_selectors or {})) if args.trace else None
        rec = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                         work, tracer=tracer, fault=fault)
    rec.peaks, rec.chips = peaks, cell.chips
    rec.end_to_end["setup_s"] = rec.spans.marks["window_start"] - \
        process_start
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        values = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(rec)
            if v is not None:
                values[m["name"]] = v
    else:
        values = {m["name"]: rec.end_to_end[m["name"]]
                  for m in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec.memory_peak_bytes}
    if args.trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
    return result_line(rec, bool(args.trace), device, units, values), rec


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out, rec = execute(args)
    except BenchError as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 2
    shown = {c.name for c in rec.compared}
    for k, v in rec.counters.get("readings", {}).items():
        if k not in shown:
            print(f"[chipbench] {k} = {v!r} (read, not compared)",
                  file=sys.stderr)
    for c in rec.compared:
        print(f"[chipbench] {c.name} = {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=sys.stderr, flush=True)
    print(f"[chipbench] attempted {rec.attempted}, failed {rec.failed}, "
          f"correct {rec.correct}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
