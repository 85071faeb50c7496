"""A configuration brings its own architecture as files: a reference
module that names the weights, where the program keeps them and the
model's FLOPs. Here a two-kind architecture (the program's zamba smoke
shape: Mamba2 periods with a shared attention block, then a remainder
segment) goes through the harness's set-up with no edit to the harness."""
import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmarks.chip import bench, cost

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "..", "..")
WORKLOAD = "zamba-smoke.train4k.exact"


def _cell(tmp_path, edit_reference=lambda src: src):
    """The zamba smoke cell, its files and reference under ``tmp_path``."""
    for d in ("configs", "traffic", "limits"):
        (tmp_path / d).mkdir()
    shutil.copy(os.path.join(HERE, "data", "zamba-smoke.json"), tmp_path / "configs")
    shutil.copy(os.path.join(ROOT, "benchmarks", "chip", "traffic", "train4k.exact.json"),
                tmp_path / "traffic")
    (tmp_path / "limits" / f"{WORKLOAD}.json").write_text(json.dumps({"loss1_gap": 1e-3}))
    src = open(os.path.join(HERE, "data", "zamba_smoke_ref.py")).read()
    (tmp_path / "zamba_smoke_ref.py").write_text(edit_reference(src))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": WORKLOAD, "config": "zamba-smoke", "traffic": "train4k.exact",
                       "chips": 1, "why": "test"}],
        "per_layer": []}))
    return bench.find_cell(WORKLOAD, root=str(tmp_path), here=str(tmp_path))


def test_the_config_runs_the_programs_zamba_smoke_shape(tmp_path):
    from repro.configs import zamba2_7b

    cell = _cell(tmp_path)
    assert bench.arch_config(cell.config) == zamba2_7b.SMOKE.replace(name="zamba-smoke")


def test_a_two_kind_architecture_builds_its_state_from_the_reference(tmp_path):
    cell = _cell(tmp_path)
    prog = bench.Program(cell, traced=False)
    assert os.path.dirname(prog.ref.__file__) == str(tmp_path)
    paths = set(prog.paths.values())
    # Mamba2 sub-layers of every period and of the remainder, and the shared block
    assert {p[:3] for p in paths if p[0] == "segments"} == {
        ("segments", 0, 0), ("segments", 0, 1), ("segments", 0, 2), ("segments", 1, 0)}
    assert ("shared", "attn", "q", "w") in paths
    seed = 2 ** 33 + 7
    state = prog.make_state(seed)      # raises on any floating leaf left uncovered
    params = prog.params(state)
    assert set(params) == set(prog.specs)
    # the benchmark's weights, drawn as the reference draws them on the device
    weights = jax.jit(lambda k: prog.ref.make_weights(k, prog.specs))(prog.ref.weight_key(seed))
    for n, a in params.items():
        np.testing.assert_array_equal(np.asarray(a), np.asarray(weights[n]))
    norms = prog.grad1_norms(state)
    assert set(norms) == set(prog.specs) and all(v == 0.0 for v in norms.values())


@pytest.mark.parametrize("edit,error", [
    # a weight the reference does not name is refused, not left at the program's init
    (lambda s: s.replace('"shared.mlp_out": ((d, F), dt, F ** -0.5)})', '})').replace(
        'for k in ("in", "gate", "out")', 'for k in ("in", "gate")'),
     "the reference does not know"),
    # a weight without a path in the program's tree
    (lambda s: s.replace('    return p\n', '    p.pop("lm_head")\n    return p\n'),
     "program_paths names"),
])
def test_a_reference_that_misses_a_weight_is_refused(tmp_path, edit, error):
    cell = _cell(tmp_path, edit)
    with pytest.raises(ValueError, match=error):
        bench.Program(cell, traced=False).make_state(3)


def test_mfu_counts_the_references_flops(tmp_path):
    cell = _cell(tmp_path)
    seq = 4096
    d, di, H, N, F, V, L, P = 64, 128, 8, 64, 128, 256, 7, 2
    mamba = d * (2 * di + 2 * N + H) + di * d      # in_z, in_x, in_B, in_C, in_dt; out
    shared = 4 * d * d + 3 * d * F                 # q, k, v, o; in, gate, out
    flops = (6 * (L * mamba + P * shared + V * d) + P * 12 * 4 * 16 * (seq + 1) / 2
             + L * 12 * di * N)
    got = cost.model_flops_per_token(cell.config, seq, cell.here)
    assert got == flops
    # not the Llama-family count
    assert got != (6 * cost.matmul_params_per_token(cell.config)
                   + cost.attention_flops_per_token(cell.config, seq))
