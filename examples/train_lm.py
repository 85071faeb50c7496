"""End-to-end driver: train a ~100M-parameter LM with sketched backprop.

    PYTHONPATH=src python examples/train_lm.py --steps 300          # full run
    PYTHONPATH=src python examples/train_lm.py --steps 20 --tiny    # smoke

Uses the production stack end to end through the :class:`repro.api.Runtime`
front door: ArchConfig (a scaled llama-style dense config), synthetic bigram
LM data with host prefetch, AdamW + cosine schedule, sketch policy (ℓ1 @ 0.2
by default), async checkpointing + auto-resume, and a budget schedule
(reactive straggler buckets via ``--straggler``, a warmup-exact schedule via
``--warmup-exact N``, or the closed-loop SNR-adaptive schedule via
``--adaptive-budget SNR`` — telemetry probes included; add
``--telemetry-jsonl PATH`` for per-step records).
"""
import argparse

from repro import compat
from repro.api import (BudgetSchedule, ExecutionConfig, Runtime, SketchConfig,
                       SketchPolicy, TelemetryConfig)
from repro.configs.base import ArchConfig
from repro.data.pipeline import prefetch
from repro.data.synthetic import LMStream
from repro.optim import adamw, cosine_warmup
from repro.train.trainer import TrainerConfig


def arch_100m(tiny: bool) -> ArchConfig:
    if tiny:
        return ArchConfig(name="lm-tiny", family="dense", n_layers=2, d_model=128,
                          n_heads=4, n_kv=2, d_ff=512, vocab=512,
                          q_chunk=64, kv_chunk=64)
    # ~100M params: 12L, d=768, ff=2048, vocab 32k
    return ArchConfig(name="lm-100m", family="dense", n_layers=12, d_model=768,
                      n_heads=12, n_kv=12, d_ff=2048, vocab=32000,
                      q_chunk=128, kv_chunk=256)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--budget", type=float, default=0.2)
    ap.add_argument("--method", default="l1")
    ap.add_argument("--exact", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--straggler", action="store_true")
    ap.add_argument("--warmup-exact", type=int, default=0,
                    help="run exact backprop for N steps, then sketched")
    ap.add_argument("--adaptive-budget", type=float, default=0.0, metavar="SNR",
                    help="closed-loop budget control: run the cheapest "
                         "pre-compiled bucket whose probe-predicted gradient "
                         "SNR stays above this target (docs/telemetry.md)")
    ap.add_argument("--telemetry-jsonl", default=None,
                    help="write per-step telemetry records to this JSONL file")
    args = ap.parse_args()
    print(f"compile cache: {compat.enable_compilation_cache()}")

    cfg = arch_100m(args.tiny)
    policy = None if args.exact else SketchPolicy(
        base=SketchConfig(method=args.method, budget=args.budget))
    if args.straggler and policy is not None:
        schedule = BudgetSchedule.straggler((1.0, 0.5, 0.2))
    elif args.warmup_exact and policy is not None:
        schedule = BudgetSchedule.warmup_exact(args.warmup_exact)
    elif args.adaptive_budget > 0 and policy is not None:
        schedule = BudgetSchedule.adaptive(target_snr=args.adaptive_budget,
                                           budgets=(1.0, 0.5, 0.2, 0.1))
    else:
        schedule = BudgetSchedule()
    execution = ExecutionConfig()
    if args.telemetry_jsonl or (args.adaptive_budget > 0 and policy is not None):
        execution = ExecutionConfig(
            telemetry=TelemetryConfig(jsonl=args.telemetry_jsonl))
    runtime = Runtime(policy=policy, schedule=schedule, execution=execution)
    opt = adamw(cosine_warmup(3e-4, max(10, args.steps // 20), args.steps),
                weight_decay=0.1, clip=1.0)
    stream = LMStream(vocab=cfg.vocab, seed=0)
    data = prefetch(stream.batches(args.batch, args.seq), size=2)
    tcfg = TrainerConfig(steps=args.steps, log_every=max(1, args.steps // 30),
                         ckpt_dir=args.ckpt, ckpt_every=max(10, args.steps // 5))
    state, history = runtime.train(cfg, opt, data, tcfg)
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"\nloss: {first:.4f} -> {last:.4f} over {args.steps} steps "
          f"({'exact' if args.exact else f'{args.method}@{args.budget}'})")


if __name__ == "__main__":
    main()
