#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result (JSON). With ``--trace 0``
its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of a short window. The last
lines of standard error are the numbers compared for ``correct``, each
beside its limit. Exits non-zero with no result line when JAX finds no TPU
or fewer chips than the cell needs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the TPU runtime's logs go under this run's own temporary directory
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import bench

    cell = bench.find_cell(args.workload)
    try:
        result = bench.run(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    except bench.NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    for k, v in result["compared"].items():
        print(f"compared {k} = {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
