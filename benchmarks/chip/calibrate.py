#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process (the benchmark's runs never run this):

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 11,12,... [--control-seeds 21,22,23] \
        [--faults half_batch,answer --fault-seeds 31,32,33]

For each seed: the program's first steps against the reference (a sound
run), or the control (the reference in float8, put in the program's
place) against the reference, or the program with a planted fault. One
JSON line per reading on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the TPU runtime's logs go under this run's own temporary directory
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)

    from benchmarks.chip import bench

    cell = bench.find_cell(args.workload)
    bench.check_devices(cell.chips)
    jobs = [("sound", None, s) for s in _seeds(args.seeds)]
    jobs += [("fault", f, s) for f in args.faults.split(",") if f
             for s in _seeds(args.fault_seeds)]
    for kind, fault, seed in jobs:
        t0 = time.perf_counter()
        prog, state, _, readings = bench.start(cell, seed, fault=fault)
        del state
        gc.collect()
        ref = bench.reference_run(prog, seed, bench.check_batches(cell, seed))
        _emit(kind, fault, seed, readings, ref, t0)
    for seed in _seeds(args.control_seeds):
        t0 = time.perf_counter()
        prog = bench.Program(cell, traced=False)
        batches = bench.check_batches(cell, seed)
        low = bench.reference_run(prog, seed, batches, precision="fp8")
        ref = bench.reference_run(prog, seed, batches)
        _emit("control", "fp8", seed, low, ref, t0)
    return 0


def _emit(kind, what, seed, run, ref, t0):
    from benchmarks.chip import bench

    print(json.dumps({"kind": kind, "what": what, "seed": seed,
                      "numbers": bench.compare(run, ref), "run": run, "ref": ref,
                      "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
