"""Tiny-shape benchmark smoke (XLA paths only): every run.py entry point must
import, and the backward-fusion bench must run end-to-end in-process (the
conftest-forced 8 fake devices double as its mesh) and uphold the PR's
structural claim — the fused backward reads G at most twice."""
import importlib
import os

import jax
import pytest


@pytest.mark.parametrize("mod", [
    "benchmarks.run",
    "benchmarks.bench_fig1a_correlation",
    "benchmarks.bench_fig1b_mask_vs_sketch",
    "benchmarks.bench_fig2a_proxies",
    "benchmarks.bench_fig2b_spectral",
    "benchmarks.bench_fig3_larger_archs",
    "benchmarks.bench_fig4_location",
    "benchmarks.bench_variance",
    "benchmarks.bench_cost",
    "benchmarks.bench_block_granularity",
    "benchmarks.bench_distributed",
    "benchmarks.bench_backward_fusion",
    "benchmarks.bench_adaptive",
    "benchmarks.bench_resilience",
    "benchmarks.bench_serve",
    "benchmarks.bench_obs",
])
def test_bench_module_imports(mod):
    importlib.import_module(mod)


def test_adaptive_bench_tiny():
    """Closed-loop MLP training end-to-end: the controller only selects
    among pre-compiled buckets (every bucket step traced exactly once)."""
    from benchmarks import bench_adaptive as ba

    out = ba.run(tiny=True)
    for name in ("fixed", "warmup_exact", "adaptive"):
        r = out[name]
        # <= 1: jit traces lazily, so a never-selected bucket traces 0 times
        assert all(v <= 1 for v in r["traces"].values()), (name, r["traces"])
        assert r["total_bwd_flops"] > 0
    assert out["adaptive"]["total_bwd_flops"] <= out["fixed"]["total_bwd_flops"]
    # the realized trajectory stays inside the schedule's bucket set
    assert set(b for b in out["adaptive"]["budget_hist"]) <= {1.0, 0.5, 0.25}


def test_bench_summary_is_machine_readable(tmp_path):
    """benchmarks/run.py distills results/bench/*.json into a top-level
    JSONL summary: one line per benchmark with name, key metric and the
    delta vs the previous artifact."""
    import json
    import os

    from benchmarks import run as brun

    summary = tmp_path / "BENCH_summary.json"
    assert os.path.isdir(brun.RESULTS), "committed bench artifacts expected"
    recs = brun.write_summary(summary_path=str(summary))
    assert recs and {"name", "metric", "value", "prev", "delta"} <= set(recs[0])
    lines = [json.loads(l) for l in open(summary) if l.strip()]
    assert [l["name"] for l in lines] == [r["name"] for r in recs]
    by_name = {l["name"]: l for l in lines}
    assert "backward_fusion" in by_name
    # second write computes deltas against the first (tmp paths are outside
    # the repo, so the git-committed baseline does not apply)
    recs2 = brun.write_summary(summary_path=str(summary))
    assert all(r["delta"] == 0.0 for r in recs2 if r["value"] is not None)


def test_bench_summary_baseline_is_git_seeded():
    """Cross-PR trajectory: prev/delta for the canonical BENCH_summary.json
    come from the *committed* summary (the previous PR's values), so
    rewriting the summary twice in one session cannot zero the deltas; tmp
    paths keep the file-based fallback."""
    from benchmarks import run as brun

    committed = brun._committed_summary(brun.SUMMARY_PATH)
    if committed is None:
        pytest.skip("no git checkout (source export) — file-based fallback "
                    "is covered above")
    assert committed, "committed BENCH_summary.json must parse via git show"
    assert "distributed" in committed
    assert committed["distributed"]["value"] is not None
    # outside the repo: no git baseline (tests above rely on the fallback)
    assert brun._committed_summary("/tmp/nowhere/BENCH_summary.json") is None


def test_serve_bench_tiny():
    """The serving bench end-to-end at toy scale: all three engines emit the
    same tokens, the continuous engines waste at most what run-to-completion
    wastes, and the paged engine keeps its one-compile-per-bucket promise."""
    from benchmarks import bench_serve as bs

    out = bs.run(tiny=True)
    assert out["outputs_equal"]
    v = out["variants"]
    for name in ("legacy", "contiguous", "paged"):
        assert v[name]["tok_per_s"] > 0
    assert v["paged"]["wasted_decode_steps"] <= v["legacy"]["wasted_decode_steps"]
    tc = v["paged"]["trace_counts"]
    assert tc["decode"] == 1 and all(n == 1 for n in tc.values()), tc
    # per-request latency stamps only exist on the continuous engines
    assert v["paged"]["latency_p50_s"] is not None
    assert v["legacy"]["latency_p50_s"] is None


def test_obs_bench_tiny():
    """The obs-overhead bench end-to-end at toy scale: both paths produce a
    pairwise-ratio overhead estimate and the headline is their max. (The
    <2% claim itself is gated on the committed full-size artifact by
    run.py --check, not on this noisy tiny run.)"""
    from benchmarks import bench_obs as bo

    out = bo.run(tiny=True)
    assert out["reps"] == 3
    for path in ("serve", "train"):
        r = out[path]
        assert r["reps"] == 3
        assert r["off_s"] > 0 and r["on_s"] > 0
        assert r["overhead_frac"] is not None
    assert out["obs_overhead_frac"] == max(out["serve"]["overhead_frac"],
                                           out["train"]["overhead_frac"])


def test_check_regressions_units():
    """The --check gate's comparison logic: ceilings bind even without
    history, both directions flag past their tolerance band, and missing
    values/prevs/tolerances never flag."""
    from benchmarks.run import check_regressions

    tol = {"step_ms": {"direction": "lower", "rel_tol": 0.10, "abs_slack": 1.0},
           "tok_per_s": {"direction": "higher", "rel_tol": 0.10, "abs_slack": 0.0},
           "frac": {"direction": "lower", "rel_tol": 0.0, "abs_slack": 0.0,
                    "ceiling": 0.02}}

    def rec(metric, value, prev=None, name="b"):
        return {"name": name, "metric": metric, "value": value, "prev": prev}

    # within band: 10% rel + 1.0 abs slack on a prev of 100 allows 111
    assert check_regressions([rec("step_ms", 111.0, 100.0)], tol) == []
    [f] = check_regressions([rec("step_ms", 111.1, 100.0)], tol)
    assert "regressed" in f and "step_ms" in f
    # higher-is-better: 90 is allowed on prev 100, 89.9 is not
    assert check_regressions([rec("tok_per_s", 90.0, 100.0)], tol) == []
    assert len(check_regressions([rec("tok_per_s", 89.9, 100.0)], tol)) == 1
    # ceiling binds with no prev at all; under-ceiling first appearance is ok
    [f] = check_regressions([rec("frac", 0.03)], tol)
    assert "ceiling" in f
    assert check_regressions([rec("frac", 0.015)], tol) == []
    # ceiling + regression can both fire on one record
    assert len(check_regressions([rec("frac", 0.03, prev=0.01)], tol)) == 2
    # silent skips: no value, no tolerance entry
    assert check_regressions([rec("step_ms", None, 100.0),
                              rec("unknown_metric", 5.0, 1.0)], tol) == []


def test_check_gate_passes_on_committed_artifacts():
    """run.py --check against the repo's own committed artifacts + summary
    must pass — it is the regression gate this PR turns on."""
    import subprocess
    import sys

    r = subprocess.run([sys.executable, "-m", "benchmarks.run", "--check"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 regression(s)" in r.stdout


def test_backward_fusion_bench_tiny():
    from benchmarks import bench_backward_fusion as bf

    out = bf.run(tiny=True, budget=0.25)
    gp = out["g_passes"]
    # the fused backward streams G at most twice: score/plan + fused gather
    assert gp["g_passes_fused"] <= 2, gp
    assert gp["g_passes_fused"] <= gp["g_passes_unfused"], gp
    # the VMEM-overflow fallback now also streams G at most twice: score/plan
    # + ONE barriered gather feeding dX and the dW matmul with db folded into
    # its stream (was 3 readers when the dX kernel made its own pass, 4
    # before the shared dW/db gather)
    assert gp["g_passes_fallback"] <= 2, gp
    # the plan-carry estimators are the headline: ONE HBM pass over G —
    # the plan comes from carried scores (no score read), and the backward
    # kernel's single sweep produces the gradient and the score refresh.
    # Asserted against the per-estimator ceiling table the dryrun coverage
    # record and run.py --check consume.
    from repro.analysis.invariants import G_READER_CEILINGS

    assert gp["g_passes_onepass"] <= G_READER_CEILINGS["onepass"] == 1, gp
    assert gp["g_passes_stale"] <= G_READER_CEILINGS["stale"] == 1, gp
    assert gp["g_passes_fused"] <= G_READER_CEILINGS["pallas"], gp
    # stale-plan excess variance: probe-measured, finite, and >= ~1 (a stale
    # plan can only add variance relative to fresh scores, up to MC noise)
    sp = out["stale_plan"]
    assert sp["probe_var_stale"] > 0 and sp["probe_var_fresh"] > 0
    assert sp["excess_var_ratio"] > 0.5, sp
    ts_local = out["train_step_local"]
    assert {"block_twopass", "block_onepass", "block_stale"} <= set(ts_local)
    for rec in ts_local.values():
        assert rec["step_ms"] > 0
    if jax.device_count() >= 8:
        ts = out["train_step"]
        assert set(ts) >= {"exact", "compact_pre", "compact_fused"}
        for rec in ts.values():
            assert rec["step_ms"] > 0


def test_g_reader_ceiling_table():
    """The per-estimator HBM-accounting contract consumed by the smoke
    assertions above, the dryrun coverage record, and run.py --check: every
    builtin backend has a ceiling, the plan-carry estimators claim exactly
    one G reader, and unknown third-party backends get the conservative
    legacy bound."""
    from repro.analysis import G_READER_CEILINGS, g_reader_ceiling
    from repro.core.estimators import BUILTIN_BACKENDS

    assert set(G_READER_CEILINGS) == set(BUILTIN_BACKENDS)
    assert g_reader_ceiling("onepass") == g_reader_ceiling("stale") == 1
    assert g_reader_ceiling("pallas") == g_reader_ceiling("mask") == 2
    assert g_reader_ceiling("some_third_party_backend") == 2


def test_g_reader_counter_parses_hlo():
    import jax.numpy as jnp

    # canonical home since the analysis subsystem absorbed the helper;
    # the bench imports the same function
    from benchmarks.bench_backward_fusion import g_reader_passes

    f = jax.jit(lambda g: (jnp.sum(jnp.abs(g)), g @ g.T))
    txt = f.lower(jax.ShapeDtypeStruct((32, 48), jnp.float32)).compile().as_text()
    assert g_reader_passes(txt, 32, 48) >= 1


def test_run_parent_never_imports_jax():
    """benchmarks/run.py spawns one child per job, and an accelerator belongs
    to the first process that touches JAX: the parent must import neither
    JAX nor anything that does (repro, the bench modules)."""
    import ast

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "run.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    bad = [n for n in names
           if n.split(".")[0] in ("jax", "jaxlib", "repro", "benchmarks")]
    assert not bad, bad
