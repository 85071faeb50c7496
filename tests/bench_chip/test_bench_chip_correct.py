"""The check that decides ``correct``, at a size a test run holds: a sound
run passes; the control (the reference in float8 put in the program's
place) and each planted fault of the timed path fail. The run drives the
harness's set-up and check steps with the chip check skipped."""
import functools
import json
import os

import pytest

from benchmarks.chip import bench

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "..", "..")
LIMITS = json.load(open(os.path.join(HERE, "data", "tiny-limits.json")))


def _cell(config, traffic):
    conf = json.load(open(os.path.join(HERE, "data", config + ".json")))
    tr = json.load(open(os.path.join(ROOT, "benchmarks", "chip", "traffic", traffic + ".json")))
    return bench.Cell("tiny", 1, conf, dict(tr, seq_len=128), LIMITS[config], [])


def _numbers(cell, seed):
    prog, state, _, readings = bench.start(cell, seed, require_chip=False)
    del state
    return bench.compare(readings, bench.reference_run(prog, seed, bench.check_batches(cell, seed)))


def _passes(numbers, config="tiny-dense"):
    return all(numbers[k] <= lim for k, lim in LIMITS[config].items())


@pytest.mark.parametrize("config,traffic", [("tiny-dense", "train4k.exact"),
                                            ("tiny-dense", "train4k.l1b20"),
                                            ("tiny-moe", "train4k.l1b20")])
def test_sound_run_is_correct(config, traffic):
    # the MoE's limits are wider: at 8 experts and 128 tokens a top-2 near
    # tie that bfloat16 rounding flips moves a token's whole output
    assert _passes(_numbers(_cell(config, traffic), 2 ** 31 + 5), config)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    # the whole run, window and check, with the fault planted underneath
    monkeypatch.setattr(bench, "start", functools.partial(bench.start, fault=fault))
    r = bench.run(_cell("tiny-dense", "train4k.l1b20"), 5, 0.5, False, t_start=0.0,
                  require_chip=False, log=lambda *a: None)
    assert r["correct"] is False


def test_control_in_float8_is_not_correct():
    cell = _cell("tiny-dense", "train4k.l1b20")
    prog = bench.Program(cell, traced=False)
    batches = bench.check_batches(cell, 5)
    low = bench.reference_run(prog, 5, batches, precision="fp8")
    assert not _passes(bench.compare(low, bench.reference_run(prog, 5, batches)))


def test_full_run_prints_compared_numbers_last():
    cell = _cell("tiny-dense", "train4k.exact")
    r = bench.run(cell, 3, 0.5, False, t_start=0.0, require_chip=False, log=lambda *a: None)
    assert r["correct"] is True
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
