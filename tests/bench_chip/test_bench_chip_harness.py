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


# where the program keeps each weight of a Llama-family model: the layer
# stack is segment 0, sub-block 0
LLAMA_PATHS = {
    "embed": ("embed",),
    "final_norm": ("final_norm", "g"),
    "lm_head": ("lm_head", "w"),
    "norm1": ("segments", 0, 0, "norm1", "g"),
    "norm2": ("segments", 0, 0, "norm2", "g"),
    "attn_q": ("segments", 0, 0, "attn", "q", "w"),
    "attn_k": ("segments", 0, 0, "attn", "k", "w"),
    "attn_v": ("segments", 0, 0, "attn", "v", "w"),
    "attn_o": ("segments", 0, 0, "attn", "o", "w"),
    "mlp_in": ("segments", 0, 0, "mlp", "in", "w"),
    "mlp_gate": ("segments", 0, 0, "mlp", "gate", "w"),
    "mlp_out": ("segments", 0, 0, "mlp", "out", "w"),
    "router": ("segments", 0, 0, "moe", "router", "w"),
    "expert_in": ("segments", 0, 0, "moe", "wi"),
    "expert_gate": ("segments", 0, 0, "moe", "wg"),
    "expert_out": ("segments", 0, 0, "moe", "wo"),
}


@pytest.mark.parametrize("config,ffn", [
    ("yi-6b-4l", ["mlp_in", "mlp_gate", "mlp_out"]),
    ("olmoe-1b-7b-2l", ["router", "expert_in", "expert_gate", "expert_out"]),
])
def test_reference_lm_names_the_programs_leaves(config, ffn):
    conf = json.load(open(os.path.join(ROOT, "benchmarks", "chip", "configs", config + ".json")))
    ref = bench.load_reference(conf)
    m = ref.Model.from_config(conf)
    names = ["embed", "final_norm", "lm_head", "norm1", "norm2",
             "attn_q", "attn_k", "attn_v", "attn_o"] + ffn
    assert ref.program_paths(m) == {n: LLAMA_PATHS[n] for n in names}
    assert set(ref.program_paths(m)) == set(ref.weight_specs(m))
    assert ref.FAULT_LEAF == "attn_o"


def test_arch_config_takes_head_dim_and_program_overrides():
    conf = bench.find_cell("yi6b-4l.train4k.exact").config
    assert bench.arch_config(conf).d_head is None
    wide = dict(conf, head_dim=256, program=dict(conf["program"], d_ff=4096, n_kv=8))
    cfg = bench.arch_config(wide)
    assert (cfg.head_dim, cfg.d_ff, cfg.n_kv) == (256, 4096, 8)
    assert (cfg.d_model, cfg.n_heads) == (4096, 32)
