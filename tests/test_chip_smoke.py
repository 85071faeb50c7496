"""chip_smoke.py rehearsed on the CPU: its phase functions on the Gemma3-1B
smoke config, with the Pallas kernels in interpret mode, and its refusal to
report a result without a TPU."""
import json

import pytest

import chip_smoke
from repro.configs import gemma3_1b


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")


def test_phases_train_and_dispatch_the_kernels(interpret):
    # block 16 divides the smoke widths (48, 96) so the block kernels run
    results = {name: chip_smoke.run_phase(gemma3_1b.SMOKE, policy, steps=2,
                                          batch=2, seq=16)
               for name, policy in chip_smoke.phase_policies(block=16,
                                                             budget=0.5)}
    chip_smoke.check_phases(results)
    assert all(len(r["losses"]) == 2 for r in results.values())
    assert results["exact"]["counts"]["kernels.fused.dispatch"] == 0
    assert results["stale"]["counts"]["kernels.fused.dispatch"] > 0


def test_four_chip_phases_on_virtual_devices(capsys):
    """The ``--chips 4`` path on 4 of the suite's host devices: equal step-0
    loss, collectives over all 4 devices, read from the executable the steps
    ran (which fails if the step had to compile again at step 1)."""
    chip_smoke.four_chips(gemma3_1b.SMOKE, seq=16)
    out = capsys.readouterr().out
    assert "phase compact_sharded: collectives" in out
    assert "reduce-scatter" in out


def test_check_phases_rejects_disagreeing_step0():
    ok = {"counts": {k: 1 for k in chip_smoke.COUNTERS}}
    results = {"exact": dict(ok, losses=[5.0, 4.9]),
               "pallas": dict(ok, losses=[5.1, 4.9]),
               "onepass": dict(ok, losses=[5.0, 4.9]),
               "stale": dict(ok, losses=[5.0, 4.9])}
    with pytest.raises(chip_smoke.SmokeFailure, match="step-0"):
        chip_smoke.check_phases(results)
    results["pallas"]["losses"] = [5.0, float("nan")]
    with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
        chip_smoke.check_phases(results)


def test_kernel_check_agrees_with_oracles(interpret):
    errs = chip_smoke.kernel_check(64, 64, 48, 2, block=16)
    assert set(errs) == {"fused.dX", "fused.dW", "fused.db", "stream.dX",
                         "stream.dW", "stream.db", "stream.scores"}
    assert all(v <= chip_smoke.KERNEL_TOL for v in errs.values()), errs


def test_collectives_read_from_hlo():
    hlo = ("  %a = f32[8] all-reduce(f32[8] %x), replica_groups={{0,1}}\n"
           "  %b = f32[4] reduce-scatter(f32[8] %a), replica_groups={{2},{3}}\n"
           "  %c = f32[4] all-gather-start(f32[2] %b), replica_groups=[2,2]<=[4]\n"
           "  %d = f32[4] add(f32[4] %c, f32[4] %c)\n")
    assert chip_smoke.collective_counts(hlo) == {
        "all-reduce": 1, "reduce-scatter": 1, "all-gather": 1}
    assert chip_smoke.collective_devices(hlo) == {0, 1, 2, 3}
    assert chip_smoke.collective_devices(hlo.splitlines()[0]) == {0, 1}


def test_main_refuses_without_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
