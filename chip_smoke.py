#!/usr/bin/env python3
"""Bring-up check: the sketched training step of Gemma3-1B on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on a 2x2 mesh

One chip: Gemma3-1B at its published widths (26 layers, d_model 1152, d_ff
6912, 4 heads MQA, d_head 256, vocab 262144, bf16) trains a few steps
through ``Runtime.train`` under four estimators: exact backprop, then the
l1 block sketch at budget 0.2 on the ``pallas``, ``onepass`` and ``stale``
backends. Before that, the fused and streaming Pallas kernels are checked
against their XLA oracles at the full-width MLP up/gate site. Every cut
from the published setting is printed on a line of its own.

Four chips: the TP-local compact sketch with compressed DP gradient
collectives (``ExecutionConfig(mesh=..., tp_sketch=True,
compact_grads=True)``, the dry-run's ``compact_sharded`` policy) against
the exact step on the same ``(2, 2)`` ``("data", "model")`` mesh.

The script fails (non-zero exit, no result line) when JAX finds no TPU, when
a loss is not finite, when the phases' step-0 losses disagree (sketching
touches only the backward), when the Pallas kernels were never dispatched,
or when a kernel disagrees with its oracle. Its last line is then the
result: ``{"ok": true, "device": {...}}``.

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache``. No TPU flags are set:
``LIBTPU_INIT_ARGS`` is left as the machine has it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compat  # noqa: E402
from repro.api import (ExecutionConfig, ObsConfig, Runtime,  # noqa: E402
                       SketchConfig, SketchPolicy)
from repro.configs import gemma3_1b  # noqa: E402
from repro.data.synthetic import LMStream  # noqa: E402
from repro.kernels import ops, ref, sketch_matmul  # noqa: E402
from repro.obs import clock  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.train.trainer import TrainerConfig  # noqa: E402

STEPS = 4
# One 4096-token sequence per chip and step: Gemma3-1B's context is 32k
# tokens, and the [tokens, 262144] f32 logits and their gradient bound the
# tokens one 16 GB chip holds next to the weights and optimizer state.
BATCH, SEQ = 1, 4096
CUTS = (
    f"cut: sequences of {SEQ} tokens, one per chip per step (Gemma3-1B's "
    "context is 32k tokens; the [tokens, 262144] f32 logits bound what fits "
    "in 16 GB)",
    f"cut: {STEPS} steps per phase, random weights from seed 0, synthetic "
    "bigram data (LMStream, seed 0)",
)
BUDGET = 0.2
BLOCK = 128
STEP0_RTOL = 1e-3
KERNEL_TOL = 1e-2   # bf16 operands: max |kernel - oracle| over max |oracle|
COUNTERS = ("kernels.fused.dispatch", "kernels.fused.vmem_fallback",
            "kernels.stream.dispatch", "kernels.stream.vmem_fallback")
OBS = ObsConfig(trace=False, compile_ledger=False, memory_ledger=False,
                flight=False)
# with the compile ledger on, the step keeps the executable it runs, whose
# HLO the four-chip phases read
OBS_LEDGER = dataclasses.replace(OBS, compile_ledger=True)


class SmokeFailure(RuntimeError):
    pass


def phase_policies(block: int = BLOCK, budget: float = BUDGET):
    """The four one-chip phases: (name, policy)."""
    def sketch(backend):
        return SketchPolicy(base=SketchConfig(method="l1", budget=budget,
                                              backend=backend, block=block))
    return [("exact", None), ("pallas", sketch("pallas")),
            ("onepass", sketch("onepass")), ("stale", sketch("stale"))]


def make_optimizer():
    # float32 moments: with bfloat16 ones the steps peaked at 7.0 GB on a
    # v5e, so float32 (4 GB more) fits the chip's 16 GB uncut
    return adamw(1e-4, weight_decay=0.1, clip=1.0)


def _counts(runtime) -> dict:
    reg = runtime.observability().metrics
    return {name: int(reg.counter(name).value) for name in COUNTERS}


def run_phase(cfg, policy, *, steps: int = STEPS, batch: int = BATCH,
              seq: int = SEQ, execution: ExecutionConfig = None,
              data_sharding=None, hlo: bool = False) -> dict:
    """Train ``steps`` steps of ``cfg`` under ``policy`` through
    ``Runtime.train`` from seed 0 and return the per-step losses and the
    kernel dispatch counts of this phase, and with ``hlo`` the compiled HLO
    text of the executable the steps ran. The final state is dropped before
    returning, so phases never hold two models at once."""
    execution = (execution or ExecutionConfig()).replace(
        obs=OBS_LEDGER if hlo else OBS)
    runtime = Runtime(policy=policy, execution=execution)
    opt = make_optimizer()
    state = None
    if execution.mesh is not None:
        from repro.train import elastic

        state = runtime.init_state(jax.random.fold_in(compat.prng_key(0), 0),
                                   cfg, opt)
        state = jax.device_put(
            state, elastic.state_shardings(state, execution.mesh))
    data = LMStream(vocab=cfg.vocab, seed=0).batches(batch, seq)
    if data_sharding is not None:
        data = (jax.device_put(b, data_sharding) for b in data)
    before = _counts(runtime)
    # the loop fetches every step's loss (log_every=1), so the host clock
    # between callbacks spans one whole step; step 0 includes compilation
    stamps = [clock.now()]
    state, history = runtime.train(
        cfg, opt, data, TrainerConfig(steps=steps, log_every=1, seed=0),
        state=state, on_metrics=lambda m: stamps.append(clock.now()))
    jax.block_until_ready(state)
    del state
    gc.collect()
    after = _counts(runtime)
    out = {"losses": [h["loss"] for h in history],
           "counts": {k: after[k] - before[k] for k in COUNTERS},
           "step_seconds": [b - a for a, b in zip(stamps, stamps[1:])]}
    if hlo:
        # the cached step the loop ran (same runtime, cfg, opt and budget)
        compiled = runtime.train_step(cfg, opt).compiled()
        if compiled is None:
            raise SmokeFailure("the train step kept no compiled executable")
        out["hlo"] = compiled.as_text()
    return out


def _rel_err(got, want) -> float:
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    return float(jnp.max(jnp.abs(got - want))) / max(scale, 1e-30)


def kernel_check(N: int, n: int, d: int, rb: int, *, block: int = BLOCK,
                 seed: int = 0) -> dict:
    """The fused and streaming Pallas kernels against their XLA oracles on
    one site (G [N, n], W [n, d], X [N, d], bf16) with the same plan:
    ``rb`` kept blocks with 1/p scales. Returns the normwise relative error
    of every output. Interpret mode off a TPU."""
    kg, kw, kx, ki, ks = jax.random.split(jax.random.key(seed), 5)
    G = jax.random.normal(kg, (N, n), jnp.bfloat16)
    W = jax.random.normal(kw, (n, d), jnp.bfloat16)
    X = jax.random.normal(kx, (N, d), jnp.bfloat16)
    nb = n // block
    idx = jnp.sort(jax.random.choice(ki, nb, (rb,), replace=False)).astype(jnp.int32)
    scales = 1.0 / jax.random.uniform(ks, (rb,), minval=0.2, maxval=1.0)
    interpret = not ops.on_tpu()

    got = sketch_matmul.block_gather_matmul_fused(
        G, idx, scales, W, X, block=block, interpret=interpret)
    want = ref.block_gather_matmul_fused_ref(G, idx, scales, W, X, block=block)
    errs = {f"fused.{k}": _rel_err(a, b)
            for k, a, b in zip(("dX", "dW", "db"), got, want)}

    gates = jnp.zeros((nb,), jnp.float32).at[idx].set(scales)
    slots = jnp.zeros((nb,), jnp.int32).at[idx].set(jnp.arange(rb, dtype=jnp.int32))
    got = sketch_matmul.block_stream_matmul_fused(
        G, gates, slots, W, X, rb=rb, block=block, interpret=interpret)
    want = ref.block_stream_matmul_onepass_ref(G, idx, scales, W, X, block=block)
    errs.update({f"stream.{k}": _rel_err(a, b)
                 for k, a, b in zip(("dX", "dW", "db", "scores"), got, want)})
    return errs


def check_phases(results: dict, *, need_fused: bool = True) -> None:
    """Raise SmokeFailure unless every loss is finite, the step-0 losses of
    all phases agree, and the Pallas kernels were dispatched where their
    phase routes through them."""
    for name, r in results.items():
        if not all(math.isfinite(x) for x in r["losses"]):
            raise SmokeFailure(f"{name}: non-finite loss {r['losses']}")
    first = [r["losses"][0] for r in results.values()]
    if max(first) - min(first) > STEP0_RTOL * abs(first[0]):
        raise SmokeFailure(f"step-0 losses disagree: "
                           f"{dict(zip(results, first))}")
    if need_fused:
        if results["pallas"]["counts"]["kernels.fused.dispatch"] == 0:
            raise SmokeFailure("pallas phase never dispatched the fused kernel")
        if results["onepass"]["counts"]["kernels.stream.dispatch"] == 0:
            raise SmokeFailure("onepass phase never dispatched the stream kernel")


def _peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _print_config(cfg) -> None:
    print(f"config: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} n_heads={cfg.n_heads} n_kv={cfg.n_kv} "
          f"d_head={cfg.d_head} vocab={cfg.vocab} dtype={cfg.dtype}")
    for line in CUTS:
        print(line)


def _print_phase(name: str, r: dict) -> None:
    print(f"phase {name}: losses {r['losses']}")
    print(f"phase {name}: step wall seconds (host clock; the first includes "
          f"compilation) {r['step_seconds']}")
    print(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in r["counts"].items()))


def one_chip() -> None:
    cfg = gemma3_1b.CONFIG
    _print_config(cfg)
    dev = jax.devices()[0]

    # full-width Gemma3-1B MLP up/gate site: G [4096, 6912] -> 1152, 11 of
    # 54 blocks kept (budget 0.2)
    errs = kernel_check(4096, cfg.d_ff, cfg.d_model, 11)
    for k, v in errs.items():
        print(f"kernel check {k}: max|kernel-oracle|/max|oracle| = {v:.3e} "
              f"(tolerance {KERNEL_TOL})")
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    if bad:
        raise SmokeFailure(f"kernels disagree with their oracles: {bad}")

    results = {}
    for name, policy in phase_policies():
        results[name] = run_phase(cfg, policy)
        _print_phase(name, results[name])
        print(f"phase {name}: peak_bytes_in_use {_peak_bytes(dev)}")
    check_phases(results)


def four_chips(cfg=gemma3_1b.CONFIG, *, seq: int = SEQ) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    _print_config(cfg)
    mesh = compat.make_mesh((2, 2), ("data", "model"),
                            devices=jax.devices()[:4])
    act = NamedSharding(mesh, P("data", None, None))
    bsh = NamedSharding(mesh, P("data", None))
    # the dry-run's compact_sharded policy (launch/dryrun.py)
    policy = SketchPolicy(base=SketchConfig(method="l1", budget=0.1,
                                            backend="compact", block=BLOCK))
    runs = {
        "exact": (None, ExecutionConfig(mesh=mesh, act_sharding=act)),
        "compact_sharded": (policy, ExecutionConfig(
            mesh=mesh, act_sharding=act, tp_sketch=True, compact_grads=True)),
    }
    results = {}
    for name, (pol, ex) in runs.items():
        results[name] = run_phase(cfg, pol, batch=2, seq=seq, execution=ex,
                                  data_sharding=bsh, hlo=True)
        _print_phase(name, results[name])
        print(f"phase {name}: peak_bytes_in_use per device "
              f"{[_peak_bytes(d) for d in mesh.devices.flat]}")
        hlo = results[name].pop("hlo")
        spanned = sorted(collective_devices(hlo))
        print(f"phase {name}: collectives in the compiled step "
              f"{collective_counts(hlo)} over devices {spanned}")
        if spanned != [0, 1, 2, 3]:
            raise SmokeFailure(f"{name}: the compiled step's collectives span "
                               f"devices {spanned}, not all 4")
    check_phases(results, need_fused=False)
    if ops.on_tpu():  # the CPU backend keeps no per-device memory stats
        peaks = [_peak_bytes(d) for d in mesh.devices.flat]
        if min(peaks) < 0.5 * max(peaks):
            raise SmokeFailure(f"device memory is lopsided: {peaks}")


_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")


def _collective_lines(hlo: str):
    for line in hlo.splitlines():
        for k in _COLLECTIVES:
            if f" {k}(" in line or f" {k}-start(" in line:
                yield k, line


def collective_counts(hlo: str) -> dict:
    out = {}
    for k, _ in _collective_lines(hlo):
        out[k] = out.get(k, 0) + 1
    return out


def collective_devices(hlo: str) -> set:
    """Device ids the compiled step's collectives span: explicit
    ``replica_groups={{0,1},...}`` / ``source_target_pairs``, or the iota
    form ``replica_groups=[2,2]<=[4]`` (all of its 4 devices)."""
    seen = set()
    for _, line in _collective_lines(hlo):
        m = re.search(r"(?:replica_groups|source_target_pairs)=\{([\d,{} ]*)\}",
                      line)
        if m:
            seen.update(int(t) for t in re.findall(r"\d+", m.group(1)))
            continue
        m = re.search(r"replica_groups=\[([\d,]+)\]<=", line)
        if m:
            n = 1
            for t in m.group(1).split(","):
                n *= int(t)
            seen.update(range(n))
    return seen


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    print(f"compile cache: {compat.enable_compilation_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}")
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
