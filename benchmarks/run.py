"""Run every benchmark (quick mode by default; --full for paper-scale).

One benchmark per paper table/figure — see DESIGN.md §6 for the index.

After the sweep, :func:`write_summary` distills ``results/bench/*.json``
into a top-level ``BENCH_summary.json`` — one JSON line per benchmark with
its key metric and the delta vs the previous summary — so the benchmark
trajectory is machine-readable across PRs. ``--check`` turns that trajectory
into a gate: recompute the summary from the artifacts on disk, compare each
key metric to the git-committed value under the per-metric tolerances in
``_TOLERANCES``, and exit nonzero on any regression.
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback

ROOT = os.path.join(os.path.dirname(__file__), "..")
RESULTS = os.path.join(ROOT, "results", "bench")
SUMMARY_PATH = os.path.join(ROOT, "BENCH_summary.json")


def _get(d, *path):
    for p in path:
        if not isinstance(d, dict) or p not in d:
            return None
        d = d[p]
    return d if isinstance(d, (int, float)) else None


# artifact file -> (key metric name, extractor). One headline number per
# benchmark: step times for the perf benches, the FLOPs ratio for adaptive.
_KEY_METRICS = {
    "distributed": ("compact_step_ms",
                    lambda d: _get(d, "variants", "compact", "step_ms")),
    # value is null when the artifact was produced without the 8-fake-device
    # mesh timing (never substitute a different quantity under this label —
    # deltas across PRs must compare like with like)
    "backward_fusion": ("block_fused_step_ms",
                        lambda d: _get(d, "train_step", "block_fused", "step_ms")),
    "adaptive": ("adaptive_vs_fixed_flops",
                 lambda d: ((_get(d, "adaptive", "total_bwd_flops")
                             / _get(d, "fixed", "total_bwd_flops"))
                            if _get(d, "fixed", "total_bwd_flops") else None)),
    # worst-case escaped-FLOP fraction across the swept archs; ratchets
    # DOWN as the MoE/SSM baseline.json waivers get retired
    "coverage": ("escaped_flop_frac",
                 lambda d: _get(d, "escaped_flop_frac")),
    # recompute tax of the recovery ladder under the canned fault drill
    "resilience": ("wasted_work_frac",
                   lambda d: _get(d, "wasted_work_frac")),
    # continuous-batching throughput over the run-to-completion baseline on
    # the same mixed-max_new workload (>1 = continuous batching wins)
    "serve": ("continuous_vs_legacy_tok_per_s",
              lambda d: _get(d, "continuous_vs_legacy_tok_per_s")),
    # worst-case obs-on/obs-off wall-time overhead across serve + train
    # (negative = within noise); held under 2% by the --check ceiling
    "obs": ("obs_overhead_frac", lambda d: _get(d, "obs_overhead_frac")),
}

# Additional per-artifact metrics (emitted as "<artifact>:<metric>" records
# after the headline record, so by-name lookups of the headline still work).
# backward_fusion grew the one-pass accounting in the plan-carry PR: the
# HLO G-reader counts for the onepass/stale estimators are ABSOLUTE claims
# (ceiling 1 — the single HBM pass over G), the stale step time tracks the
# carry path's wall trajectory, and the probe-measured excess variance keeps
# the staleness cost honest (see docs/perf.md).
_EXTRA_METRICS = {
    "backward_fusion": [
        ("g_passes_onepass", lambda d: _get(d, "g_passes", "g_passes_onepass")),
        ("g_passes_stale", lambda d: _get(d, "g_passes", "g_passes_stale")),
        ("stale_step_ms",
         lambda d: _get(d, "train_step_local", "block_stale", "step_ms")),
        ("stale_excess_var",
         lambda d: _get(d, "stale_plan", "excess_var_ratio")),
    ],
}


# --check gate: per-metric tolerance for value-vs-prev regressions.
# direction: which way is WORSE. rel_tol / abs_slack: a regression is flagged
# only past prev*(1±rel_tol) shifted by abs_slack — wall-time metrics get
# generous slack (shared CI boxes), ratio metrics get tight ones. ceiling
# (optional): an absolute bound enforced even when prev is missing.
_TOLERANCES = {
    "compact_step_ms": {"direction": "lower", "rel_tol": 0.25, "abs_slack": 10.0},
    "block_fused_step_ms": {"direction": "lower", "rel_tol": 0.25, "abs_slack": 10.0},
    "adaptive_vs_fixed_flops": {"direction": "lower", "rel_tol": 0.05, "abs_slack": 0.0},
    "escaped_flop_frac": {"direction": "lower", "rel_tol": 0.0, "abs_slack": 0.005},
    "wasted_work_frac": {"direction": "lower", "rel_tol": 0.25, "abs_slack": 0.02},
    "continuous_vs_legacy_tok_per_s": {"direction": "higher", "rel_tol": 0.15,
                                       "abs_slack": 0.0},
    "obs_overhead_frac": {"direction": "lower", "rel_tol": 0.0,
                          "abs_slack": 0.01, "ceiling": 0.02},
    # the one-pass contract is absolute: the compiled plan-carry backward
    # reads G exactly once — zero tolerance, enforced even without history
    "g_passes_onepass": {"direction": "lower", "rel_tol": 0.0,
                         "abs_slack": 0.0, "ceiling": 1},
    "g_passes_stale": {"direction": "lower", "rel_tol": 0.0,
                       "abs_slack": 0.0, "ceiling": 1},
    "stale_step_ms": {"direction": "lower", "rel_tol": 0.25, "abs_slack": 10.0},
    # probe-measured variance ratio of carrying the plan one step (AR rho=0.9
    # gradients); stochastic, so a wide band + an absolute sanity ceiling
    "stale_excess_var": {"direction": "lower", "rel_tol": 0.5,
                         "abs_slack": 0.25, "ceiling": 3.0},
}


def check_regressions(records, tolerances=None) -> list:
    """Flag per-metric regressions in ``write_summary`` records.

    Returns human-readable failure strings (empty = gate passes). A record
    participates only when its metric has a tolerance entry; ``value=None``
    (artifact missing the number) and ``prev=None`` (first appearance) are
    never regressions — except a metric with a ``ceiling``, which is an
    absolute bound on ``value`` regardless of history."""
    tolerances = _TOLERANCES if tolerances is None else tolerances
    failures = []
    for rec in records:
        tol = tolerances.get(rec.get("metric"))
        value = rec.get("value")
        if tol is None or value is None:
            continue
        name, metric = rec.get("name"), rec.get("metric")
        ceiling = tol.get("ceiling")
        if ceiling is not None and value > ceiling:
            failures.append(
                f"{name}: {metric}={value:.6g} exceeds ceiling {ceiling:g}")
        prev = rec.get("prev")
        if prev is None:
            continue
        if tol["direction"] == "lower":
            bound = prev * (1.0 + tol["rel_tol"]) + tol["abs_slack"]
            if value > bound:
                failures.append(
                    f"{name}: {metric} regressed {prev:.6g} -> {value:.6g} "
                    f"(allowed <= {bound:.6g})")
        else:
            bound = prev * (1.0 - tol["rel_tol"]) - tol["abs_slack"]
            if value < bound:
                failures.append(
                    f"{name}: {metric} regressed {prev:.6g} -> {value:.6g} "
                    f"(allowed >= {bound:.6g})")
    return failures


def _parse_summary(text: str) -> dict:
    recs = {}
    for line in text.splitlines():
        line = line.strip()
        if line:
            try:
                r = json.loads(line)
                recs[r["name"]] = r
            except (ValueError, KeyError):
                pass
    return recs


def _committed_summary(summary_path: str):
    """The git-committed BENCH_summary.json (the previous PR's values), or
    None when unavailable. Seeding prev/delta from the *checked-in* summary
    — rather than whatever the file on disk currently holds — makes the
    cross-PR trajectory robust to multiple write_summary calls in one
    session (a second call would otherwise diff against its own output and
    report delta 0 forever)."""
    import subprocess

    rel = os.path.relpath(summary_path, ROOT)
    if rel.startswith(".."):
        return None  # outside the repo (tests writing to tmp dirs)
    try:
        r = subprocess.run(["git", "show", f"HEAD:{rel.replace(os.sep, '/')}"],
                           capture_output=True, text=True, cwd=ROOT)
    except OSError:
        return None
    return _parse_summary(r.stdout) if r.returncode == 0 else None


def write_summary(results_dir: str = RESULTS,
                  summary_path: str = SUMMARY_PATH) -> list:
    """Write ``BENCH_summary.json``: one JSON object per line with
    ``{name, metric, value, prev, delta}`` for every artifact in
    ``results_dir``. ``prev``/``delta`` are seeded from the git-committed
    summary (the previous PR's headline values), falling back to the file
    being replaced when git is unavailable. Returns the records."""
    prev = _committed_summary(summary_path)
    if prev is None:
        prev = {}
        if os.path.exists(summary_path):
            with open(summary_path) as f:
                prev = _parse_summary(f.read())
    records = []
    for fname in sorted(os.listdir(results_dir) if os.path.isdir(results_dir) else []):
        if not fname.endswith(".json"):
            continue
        name = fname[:-5]
        try:
            with open(os.path.join(results_dir, fname)) as f:
                data = json.load(f)
        except ValueError:
            continue
        metric, extract = _KEY_METRICS.get(
            name, ("n_entries", lambda d: float(len(d)) if isinstance(d, dict) else None))

        def _rec(rec_name, metric, value):
            p = prev.get(rec_name, {})
            prev_value = p.get("value") if p.get("metric") == metric else None
            return {"name": rec_name, "metric": metric,
                    "value": None if value is None else float(value),
                    "prev": prev_value,
                    "delta": (float(value) - prev_value
                              if value is not None and prev_value is not None
                              else None)}

        records.append(_rec(name, metric, extract(data)))
        for metric2, extract2 in _EXTRA_METRICS.get(name, ()):
            # satellite metrics ride as "<artifact>:<metric>" records so the
            # headline record keeps its by-name identity
            records.append(_rec(f"{name}:{metric2}", metric2, extract2(data)))
    with open(summary_path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return records


# job name -> benchmarks module with a ``run(quick=...)`` entry point. Every
# job runs in its own child interpreter and this parent never imports JAX:
# an accelerator belongs to the one process that touched JAX first, so a
# parent holding it would leave its children without a device. The child
# boundary also lets the distributed benches force their 8 fake host devices
# before backend init without resizing any other job's backend.
_JOBS = {
    "fig1a_correlation": "bench_fig1a_correlation",
    "fig1b_mask_vs_sketch": "bench_fig1b_mask_vs_sketch",
    "fig2a_proxies": "bench_fig2a_proxies",
    "fig2b_spectral": "bench_fig2b_spectral",
    "fig3_larger_archs": "bench_fig3_larger_archs",
    "fig4_location": "bench_fig4_location",
    "variance_eq6": "bench_variance",
    "cost_backends": "bench_cost",
    "block_granularity": "bench_block_granularity",
    "adaptive": "bench_adaptive",
    "coverage": "bench_coverage",
    "resilience": "bench_resilience",
    "serve": "bench_serve",
    "obs": "bench_obs",
    "distributed": "bench_distributed",
    "backward_fusion": "bench_backward_fusion",
}


def _run_job(module: str, quick: bool) -> None:
    code = f"from benchmarks import {module} as m; m.run(quick={quick})"
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"{module} exited {r.returncode}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--check", action="store_true",
                    help="skip the sweep: recompute BENCH_summary.json from "
                         "the artifacts on disk and exit nonzero on any "
                         "per-metric regression vs the git-committed summary")
    args = ap.parse_args()
    quick = not args.full

    if args.check:
        records = write_summary()
        failures = check_regressions(records)
        for f in failures:
            print(f"REGRESSION: {f}")
        print(f"--check: {len(records)} metric(s), "
              f"{len(failures)} regression(s)")
        raise SystemExit(1 if failures else 0)

    failures = 0
    for name, module in _JOBS.items():
        if args.only and args.only != name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            _run_job(module, quick)
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except RuntimeError:
            failures += 1
            traceback.print_exc()
            print(f"[{name}] FAILED")
    records = write_summary()
    print(f"\nBENCH_summary.json: "
          + ", ".join(f"{r['name']}={r['value']}" for r in records))
    print(f"benchmarks complete, failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
