"""Trace reduction: busy time as the union of device op intervals, idle
share, time by kernel name, idle gaps labelled by the host span open in
them; on hand-made traces and on a small trace recorded on a TPU v5e."""
import os

import pytest

from benchmarks.chip import cost, xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tiny_tpu.xplane.pb")


def _trace():
    # device 0: [0,10) and [5,20) overlap; [30,40) is the kernel; idle
    # [20,30) inside a train_step span and [40,100) after it
    ops = {0: [("fusion.1", 0, 10), ("fusion.2", 5, 20),
               ("custom-call.3 [col_l1_scores]", 30, 40)]}
    spans = [("train_loop", 0, 100), ("train_step", 0, 35)]
    return xtrace.Trace(ops=ops, spans=spans, window=(0, 100))


def test_busy_is_the_union_of_op_intervals():
    t = _trace()
    assert t.busy_intervals(0) == [[0, 20], [30, 40]]
    assert t.busy_s() == pytest.approx(30e-9)
    assert t.window_s() == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.7)


def test_ops_outside_the_window_are_clipped():
    t = _trace()
    t.window = (10, 35)
    assert t.busy_intervals(0) == [[10, 20], [30, 35]]


def test_kernel_time_by_name():
    t = _trace()
    assert t.op_seconds(xtrace.kernel_match(["col_l1_scores"])) == pytest.approx(10e-9)
    assert t.op_seconds(xtrace.kernel_match(["block_gather_matmul"])) == 0.0
    assert t.top_ops(1) == [["fusion.2", pytest.approx(15e-9)]]


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = _trace().idle_gaps()
    assert gaps == [["train_loop", pytest.approx(60e-9)],
                    ["train_step", pytest.approx(10e-9)]]


def test_busy_is_averaged_over_devices():
    t = _trace()
    t.ops[1] = [("fusion.9", 0, 50)]
    assert t.busy_s() == pytest.approx(40e-9)


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData

    t = xtrace.from_profile(ProfileData.from_file(RECORDED), chips=1)
    # three steps of a 1024^3 bf16 matmul and the column-score kernel,
    # inside train_loop / train_step annotations
    assert [s[0] for s in t.spans].count("train_step") == 3
    assert 0 < t.busy_s() < t.window_s()
    assert 0.0 < t.idle_share() < 1.0
    kern = t.op_seconds(xtrace.kernel_match(["col_l1_scores"]))
    assert 0 < kern < t.busy_s()
    gaps = t.idle_gaps()
    assert gaps and {g[0] for g in gaps} <= {"train_step", "train_loop"}
    # the kernel's call keeps its HLO: [1024, 1024] bf16 in, f32 [1, 1024] out
    (name, hlo, seconds), = t.kernel_calls()
    assert name.startswith("col_l1_scores") and seconds > 0
    assert cost.kernel_work(name, hlo) == (2 * 1024 * 1024, 2 * 1024 * 1024 + 4 * 1024)
