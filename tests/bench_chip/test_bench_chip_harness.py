"""The benchmark as data: cells, configurations, traffic and metric readers
are files found by name, and a run without a TPU prints no result."""
import io
import json
import os
import shutil
from contextlib import redirect_stdout

import pytest

from benchmarks.chip import bench

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_by_name(w):
    cell = bench.find_cell(w["name"])
    assert cell.config["name"] == w["config"]
    assert cell.chips == w["chips"]
    one = {"losses": [1.0], "grad1": {"w": 1.0}, "delta": {"w": 1.0}}
    assert cell.limits and set(cell.limits) <= set(bench.compare(one, one))
    assert cell.traffic["kind"] == "train"
    for m in cell.per_layer:
        assert callable(bench.load_reader(m["name"]))
    # the program's configuration and the reference's model agree on sizes
    cfg = bench.arch_config(cell.config)
    ref = bench.load_reference(cell.config).Model.from_config(cell.config)
    assert (cfg.d_model, cfg.n_layers, cfg.vocab) == (ref.d, ref.n_layers, ref.vocab)


def test_configs_name_their_source_and_cuts():
    for c in SPEC["configs"]:
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert conf["assumed"]
        # a departure the program forces keeps the published value at the
        # top level, so that ``reduced`` names every key changed from the source
        for k, d in conf["departures"].items():
            assert k not in conf["reduced"] and conf.get(k, d["published"]) == d["published"]


def test_an_added_traffic_file_makes_a_new_cell(tmp_path):
    here = tmp_path / "chip"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), here)
    spec = json.loads(json.dumps(SPEC))
    traffic = json.load(open(here / "traffic" / "train4k.l1b20.json"))
    traffic["estimator"]["budget"] = 0.5
    (here / "traffic" / "train4k.l1b50.json").write_text(json.dumps(traffic))
    (here / "limits" / "yi6b-4l.train4k.l1b50.json").write_text(
        json.dumps({"loss_gap": 1e-3, "grad1_gap": 1e-2, "delta_gap": 1e-2}))
    spec["workloads"].append({"name": "yi6b-4l.train4k.l1b50", "config": "yi-6b-4l",
                              "traffic": "train4k.l1b50", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = bench.find_cell("yi6b-4l.train4k.l1b50", root=str(tmp_path), here=str(here))
    assert cell.traffic["estimator"]["budget"] == 0.5
    assert cell.limits["loss_gap"] == 1e-3
    # metrics that list no workloads reach the new cell too
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in spec["per_layer"] if "workloads" not in m}


def test_run_cell_without_a_tpu_exits_nonzero_with_no_result():
    import importlib.util

    path = os.path.join(ROOT, "benchmarks", "chip", "run_cell.py")
    s = importlib.util.spec_from_file_location("chip_run_cell", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = mod.main(["--workload", SPEC["workloads"][0]["name"], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert out.getvalue() == ""


def test_peaks_table_refuses_an_unknown_device():
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench.peaks("cpu")


def test_step_mfu_reads_the_traced_window():
    from benchmarks.chip import cost, xtrace

    cell = bench.find_cell("yi6b-4l.train4k.exact")
    trace = xtrace.Trace(ops={0: [("fusion.1", 0, 10**9)]}, spans=[], window=(0, 2 * 10**9))
    r = bench.Readings(cell=cell, trace=trace, counters={}, steps=4, data_waits=[],
                       host_spans=[], device_kind="TPU v5 lite")
    flops = 4 * 4096 * cost.model_flops_per_token(cell.config, 4096)
    assert bench.load_reader("step_mfu")(r) == pytest.approx(100 * flops / (2.0 * 197e12))
