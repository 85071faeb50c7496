#!/usr/bin/env python3
"""Compile a one-chip cell's train step for a described TPU v5e, with no chip:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/describe.py --workload <name>

Prints the compiled step's ``memory_analysis()``, the fused-kernel dispatch
decisions counted while it traced, and the Pallas calls in its HLO. The
program's kernel dispatch asks the backend whether it runs on a TPU; here
the answer is steered to yes, so the step traces the kernels a chip runs.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import bench
    from repro import compat
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops.on_tpu = lambda: True
    cell = bench.find_cell(args.workload)
    if cell.chips != 1:
        raise SystemExit(f"{cell.name}: describes one-chip cells only, not {cell.chips} chips")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:1x1",
                                        chips_per_host_bounds=(1, 1, 1))
    one = SingleDeviceSharding(topo.devices[0])
    prog = bench.Program(cell, traced=False)
    tr = cell.traffic
    B, S = int(tr["batch"]), int(tr["seq_len"])

    state = jax.eval_shape(lambda: prog.make_state(0))
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    state = jax.tree.map(sds, state)
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
             for k in ("tokens", "labels")}
    key = jax.ShapeDtypeStruct((), compat.key_dtype(), sharding=one)
    step = prog.runtime.train_step(prog.cfg, prog.opt)
    compiled = step.lower(state, batch, key).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    out = {"workload": cell.name, "topology": "v5e:1x1",
           "argument_gb": mem.argument_size_in_bytes / 1e9,
           "output_gb": mem.output_size_in_bytes / 1e9,
           "temp_gb": mem.temp_size_in_bytes / 1e9,
           "alias_gb": mem.alias_size_in_bytes / 1e9,
           "counters": prog.counters(),
           "pallas_calls": hlo.count("tpu_custom_call")}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
