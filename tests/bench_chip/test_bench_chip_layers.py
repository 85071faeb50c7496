"""The per-layer metrics that read the program's device scopes and host
spans: device time by layer through the op->layer table, the share of the
window idle in a fetch, and the trainer's data time; on a hand-made window,
with no traced program (they then read nothing), and on the recorded traces
(the metrics that were there before read as they did)."""
import os
import types

import pytest

from benchmarks.chip import bench, layers, xtrace
from repro import obs
from repro.obs import ObsConfig

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("attention_share", "ffn_share", "head_share", "sketch_spine_share",
       "optimizer_share", "unscoped_share", "fetch_idle_share", "trainer_data_ms")
OLD = ("device_idle_share", "data_wait_ms", "trainer_host_ms", "sketch_kernel_share",
       "sketch_kernel_roofline", "kernel_fallback_share", "step_mfu")
T0 = 5.0  # the tracer's clock at the trace's 0 ns


@pytest.fixture
def program():
    """A traced program's shared observability, cleared after the test."""
    obs._reset()
    ob = obs.observability(ObsConfig(trace=True, metrics=False, compile_ledger=False,
                                     memory_ledger=False, flight=False))
    yield ob
    obs._reset()


def _readings(trace, steps=2):
    span = lambda d: types.SimpleNamespace(duration_s=d)
    return bench.Readings(
        cell=bench.find_cell("yi6b-4l.train4k.l1b20"), trace=trace,
        counters={"kernels.fused.dispatch": 7, "kernels.fused.vmem_fallback": 5},
        steps=steps, data_waits=[0.017, 0.018, 0.019],
        host_spans=[span(0.001), span(0.002)], device_kind="TPU v5 lite")


def _window():
    # busy 700 ns of a 1000 ns window; while.3 spans ops of its own body
    ops = {0: [("fusion.1", 0, 100), ("fusion.2", 100, 300), ("while.3", 300, 600),
               ("fusion.4", 300, 400), ("fusion.5", 400, 450), ("custom-call.6", 450, 500),
               ("copy-done.7", 500, 550), ("fusion.8", 550, 600), ("fusion.9", 600, 650),
               ("fusion.10", 650, 700)]}
    spans = [("train_loop", 0, 1000), ("train_step", 0, 20), ("train_step", 690, 700)]
    return xtrace.Trace(ops=ops, spans=spans, window=(0, 1000))


TABLE = {"fusion.1": ("attn", "stack/attn"), "fusion.2": ("ffn", "stack/ffn"),
         "while.3": ("stack", "stack"), "fusion.4": ("sketch", "stack/attn/sketch/vjp"),
         "fusion.5": ("head", "head"), "custom-call.6": ("optim", "optim"),
         "copy-done.7": (None, ""), "fusion.9": ("embed", "embed"),
         "fusion.10": ("stack", "stack")}


def _record(ob):
    ob.record_op_layers("jit_step_fn", TABLE)
    tr = ob.tracer
    ns = lambda t: T0 + t * 1e-9
    tr.add_span("train_loop", ns(0), ns(1000))
    tr.add_span("train_data", ns(-500), ns(-400))        # an earlier call's
    tr.add_span("train_data", ns(1), ns(101))            # 100 ns
    tr.add_span("train_data", ns(600), ns(900))          # 300 ns
    tr.add_span("train_step", ns(0), ns(20))
    tr.add_span("train_step", ns(690), ns(700))
    tr.add_span("train_fetch", ns(650), ns(800))         # idle from 700 to 800


def test_layer_shares_of_busy_time(program):
    _record(program)
    r = _readings(_window())
    read = {m: bench.load_reader(m)(r) for m in NEW}
    busy = 700.0
    assert read["attention_share"] == pytest.approx(100 * 100 / busy)
    assert read["ffn_share"] == pytest.approx(100 * 200 / busy)
    assert read["sketch_spine_share"] == pytest.approx(100 * 100 / busy)
    assert read["head_share"] == pytest.approx(100 * 50 / busy)
    assert read["optimizer_share"] == pytest.approx(100 * 50 / busy)
    # copy-done.7 names no layer, fusion.8 is in no table
    assert read["unscoped_share"] == pytest.approx(100 * 100 / busy)
    # embed and stack, with no metric of their own, close the sum
    secs = layers.layer_seconds(r.trace, layers.op_tables())
    assert sum(secs.values()) == pytest.approx(busy * 1e-9)
    assert read["fetch_idle_share"] == pytest.approx(100 * 100 / 1000)
    assert read["trainer_data_ms"] == pytest.approx(200e-6)


def test_nothing_to_read_without_a_traced_program():
    obs._reset()
    r = _readings(_window())
    assert {m: bench.load_reader(m)(r) for m in NEW} == dict.fromkeys(NEW)


def test_spans_move_to_the_trace_clock(program):
    _record(program)
    r = _readings(_window())
    (s, e), = layers.to_trace_clock(r, layers.window_spans("train_fetch"))
    assert (s, e) == (pytest.approx(650, abs=1e-3), pytest.approx(800, abs=1e-3))
    assert layers.idle_intervals(r.trace) == [(700, 1000)]


def test_metrics_already_there_read_as_before_on_the_recorded_trace():
    from jax.profiler import ProfileData

    t = xtrace.from_profile(ProfileData.from_file(os.path.join(DATA, "tiny_tpu.xplane.pb")),
                            chips=1)
    r = _readings(t, steps=3)
    got = {m: bench.load_reader(m)(r) for m in OLD}
    # the readings of the program before the scopes, on these inputs
    assert got == {"device_idle_share": pytest.approx(99.20314339835151, rel=1e-12),
                   "data_wait_ms": pytest.approx(18.0, rel=1e-12),
                   "trainer_host_ms": pytest.approx(1.5, rel=1e-12),
                   "sketch_kernel_share": pytest.approx(59.942177963379386, rel=1e-12),
                   "sketch_kernel_roofline": pytest.approx(68.63633958337007, rel=1e-12),
                   "kernel_fallback_share": pytest.approx(71.42857142857143, rel=1e-12),
                   "step_mfu": pytest.approx(16307.29275473681, rel=1e-12)}
    assert t.breakdown()["device_ops"] == [
        ["convert_reduce_fusion", pytest.approx(1.1819e-05)],
        ["col_l1_scores.1", pytest.approx(3.738e-06)],
        ["copy-done", pytest.approx(3.108e-06)],
        ["copy-start", pytest.approx(1.3e-08)]]


def test_recorded_scoped_step_joins_by_module_and_op_name(program):
    """A tiny sketched step traced on a TPU v5e (``record_scoped_trace.py``):
    every op the step's module ran is an instruction of its table, the
    layers the table names cover the busy time, and the program's spans land
    on the trace's own spans."""
    import json

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(DATA, "tiny_scoped.xplane.pb"))
    rec = json.load(open(os.path.join(DATA, "tiny_scoped.json")))
    (module, table), = rec["op_layers"].items()
    ran = [name for mod, name, _, _ in layers.module_ops(pd) if mod == module]
    assert ran and all(name in table for name in ran)
    assert {"attn", "ffn", "head", "sketch", "optim"} <= {table[n][0] for n in ran}

    program.record_op_layers(module, {n: tuple(v) for n, v in table.items()})
    for name, t0, t1 in rec["spans"]:
        program.tracer.add_span(name, t0, t1)
    t = xtrace.from_profile(pd, chips=1)
    r = _readings(t)
    secs = layers.layer_seconds(t, layers.op_tables())
    assert sum(secs.values()) <= t.busy_s() * (1 + 1e-9)
    read = {m: bench.load_reader(m)(r) for m in NEW}
    assert all(read[m] is not None for m in NEW)
    assert read["sketch_spine_share"] > 0 and read["unscoped_share"] < 100
    mapped = layers.to_trace_clock(r, layers.window_spans("train_step"))
    traced = sorted((s, e) for n, s, e in t.spans if n == "train_step")
    assert len(mapped) == len(traced) == 2
    for (a, b), (c, d) in zip(mapped, traced):
        assert abs(a - c) < 50e3 and abs(b - d) < 50e3  # within 50 us
