"""The training benchmark: one cell, one run, one result line.

A cell (an entry of ``BENCHMARK.json`` ``workloads``) names a configuration
and a traffic mix; each lives in a file of its own, found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``, and the limits of
the correctness check in ``limits/<workload>.json``. Per-layer metrics are
read by ``metrics/<metric>.py``. Adding a cell or a metric adds files.

A configuration names its plain reference, ``<reference>.py`` beside the
configurations' directory, which is the one place that knows the
architecture. Adding a configuration adds its file and, for a new
architecture, a reference module that exports:

- ``Model.from_config(conf)``: the model's sizes, from the file's keys;
- ``weight_specs(model)``: {name: (shape, dtype, std)} of every weight;
- ``weight_key(seed)``, ``make_leaf(key, name, spec)``,
  ``make_weights(key, specs)``: the weights, drawn on the device;
- ``program_paths(model)``: {name: path} of each weight's leaf in the
  program's parameter tree, for exactly the names of ``weight_specs``; a
  path may lead anywhere in the tree (any segment or sub-layer, or an
  unstacked leaf such as ``("shared", "attn", "q", "w")``);
- ``FAULT_LEAF``: the weight whose gradient the planted ``answer`` fault
  doubles;
- ``model_flops_per_token(conf, seq)``: the exact model's training FLOPs
  per token, the yardstick of ``mfu`` and ``step_mfu``;
- ``Sketch.from_traffic(estimator)`` and ``Reference(model, sketch,
  optimizer, precision).train(weights, batches, step_keys)``: the plain
  training steps that ``correct`` compares with.

A run drives the program's front door: one ``Runtime`` and one compiled
train step, fed by the seeded token stream. Set-up makes the weights on the
device from the seed, takes the first ``check_steps`` steps through
``Runtime.train`` (which compiles), and a few more to size the window. The
window is one ``Runtime.train`` call of as many steps as fill ``--seconds``,
ended by ``block_until_ready``. After the window the program's state is
freed and the plain reference trains the first steps again from the same
weights and batches; ``correct`` compares the two.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list      # the BENCHMARK.json per-layer metric entries of this cell
    here: str = HERE     # the directory its files were found in


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(workload: str, root: str = ROOT, here: str = HERE) -> Cell:
    """The cell ``workload`` of ``root``/BENCHMARK.json, its files found by
    name under ``here``."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = _load_json(os.path.join(here, "configs", w["config"] + ".json"))
    traffic = _load_json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(here, "limits", workload + ".json"))
    per_layer = [m for m in spec["per_layer"] if workload in m.get("workloads", [workload])]
    return Cell(workload, int(w["chips"]), conf, traffic, limits, per_layer, here)


def load_reader(metric: str) -> Callable:
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"chip_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(conf: dict, here: str = HERE):
    """The reference module the configuration names, from ``here``."""
    path = os.path.join(here, conf["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(f"chip_ref_{conf['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file: sizes from its
    Hugging Face keys, then its ``program`` entries, which override them."""
    from repro.configs.base import ArchConfig

    heads = conf["num_attention_heads"]
    fields = dict(
        name=conf["name"], n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=heads,
        n_kv=conf.get("num_key_value_heads", heads), d_head=conf.get("head_dim"),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=conf["rope_theta"], tie_embeddings=conf["tie_word_embeddings"],
        n_experts=conf.get("num_experts", 0), top_k=conf.get("num_experts_per_tok", 0))
    fields.update(conf["program"])
    return ArchConfig(**fields)


def policy(traffic: dict):
    from repro.api import SketchConfig, SketchPolicy

    est = traffic["estimator"]
    if est is None:
        return None
    return SketchPolicy(base=SketchConfig(**est))


def optimizer(traffic: dict):
    import jax.numpy as jnp
    from repro.optim import adamw

    o = traffic["optimizer"]
    return adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], clip=o["clip"],
                 moment_dtype=jnp.dtype(o["moment_dtype"]))


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _set(tree, path, value):
    """Copy of nested dicts/lists ``tree`` with the leaf at ``path`` replaced."""
    if not path:
        return value
    head, rest = path[0], path[1:]
    if isinstance(tree, list):
        out = list(tree)
    else:
        out = dict(tree)
    out[head] = _set(tree[head], rest, value)
    return out


class Program:
    """One Runtime, optimizer and compiled step: the system under test."""

    def __init__(self, cell: Cell, *, traced: bool, fault: Optional[str] = None):
        import jax
        from repro.api import ExecutionConfig, ObsConfig, Runtime

        self.cell = cell
        self.cfg = arch_config(cell.config)
        self.ref = load_reference(cell.config, cell.here)
        self.model = self.ref.Model.from_config(cell.config)
        self.specs = self.ref.weight_specs(self.model)
        self.names = [n for n in self.specs]
        self.paths = self.ref.program_paths(self.model)
        if set(self.paths) != set(self.specs):
            raise ValueError(f"program_paths names {sorted(set(self.paths) ^ set(self.specs))} "
                             f"that weight_specs does not, or the reverse")
        self.opt = (optimizer(cell.traffic) if fault is None else
                    faulty_optimizer(cell.traffic, fault, self.paths[self.ref.FAULT_LEAF]))
        obs = ObsConfig(trace=traced, annotate=traced, metrics=True,
                        compile_ledger=False, memory_ledger=False, flight=False)
        self.runtime = Runtime(policy=policy(cell.traffic),
                               execution=ExecutionConfig(obs=obs))
        self._norm = jax.jit(jnp_norm)
        self._delta = {}

    def make_state(self, seed: int):
        """The train state: the program's own state layout, with every
        weight drawn by the benchmark from ``seed`` on the device."""
        import jax
        import jax.numpy as jnp
        from repro import compat
        from repro.train.train_step import TrainState

        def build(wkey):
            st = self.runtime.init_state(compat.prng_key(0), self.cfg, self.opt)
            params = st.params
            covered = set()
            for n in self.names:
                path = self.paths[n]
                leaf = _get(params, path)
                w = self.ref.make_leaf(wkey, n, self.specs[n])
                if leaf.shape != w.shape or leaf.dtype != w.dtype:
                    raise ValueError(f"{n}: program leaf {leaf.shape} {leaf.dtype}, "
                                     f"benchmark weight {w.shape} {w.dtype}")
                params = _set(params, path, w)
                covered.add(path)
            left = [p for p, x in _leaf_paths(params)
                    if p not in covered and jnp.issubdtype(x.dtype, jnp.floating)
                    and "sslot" not in p]
            if left:
                raise ValueError(f"program weights the reference does not know: {left}")
            return TrainState(params=params, opt_state=self.opt.init(params),
                              step=jnp.zeros((), jnp.int32))

        return jax.jit(build)(self.ref.weight_key(seed))

    def counters(self) -> dict:
        reg = self.runtime.observability().metrics
        return {n: int(reg.counter(n).value) for n in
                ("kernels.fused.dispatch", "kernels.fused.vmem_fallback",
                 "kernels.stream.dispatch", "kernels.stream.vmem_fallback")}

    def spans(self, name: str) -> list:
        return self.runtime.observability().tracer.spans(name)

    def train(self, state, feed, steps: int, seed: int):
        """``steps`` more steps through ``Runtime.train``; (state, history)."""
        from repro.train.trainer import TrainerConfig

        import jax

        start = int(jax.device_get(state.step))
        hist = []
        state, _ = self.runtime.train(
            self.cfg, self.opt, feed,
            TrainerConfig(steps=start + steps, log_every=10, seed=trainer_seed(seed)),
            state=state, on_metrics=lambda m: hist.append(dict(m, host_t=time.perf_counter())))
        return state, hist

    def grad1_norms(self, state) -> dict:
        b1 = self.cell.traffic["optimizer"]["b1"]
        return {n: float(self._norm(_get(state.opt_state["m"], self.paths[n]))) / (1 - b1)
                for n in self.names}

    def params(self, state) -> dict:
        return {n: _get(state.params, self.paths[n]) for n in self.names}

    def delta_norms(self, seed: int, params: dict) -> dict:
        """Per leaf ||params - initial weights||, the initial weights drawn
        again from the seed one leaf at a time."""
        import jax

        wkey = self.ref.weight_key(seed)
        out = {}
        for n, a in params.items():
            if n not in self._delta:
                self._delta[n] = jax.jit(lambda x, k, n=n: jnp_norm(
                    x.astype("float32") - self.ref.make_leaf(k, n, self.specs[n]).astype("float32")))
            out[n] = float(self._delta[n](a, wkey))
        return out


def jnp_norm(a):
    import jax.numpy as jnp

    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def _leaf_paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _leaf_paths(v, prefix + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaf_paths(v, prefix + (i,))
    elif tree is not None:
        yield prefix, tree


def trainer_seed(seed: int) -> int:
    """The trainer's PRNG seed (its step keys draw the sketches)."""
    return seed % (2 ** 31)


class Feed:
    """The trainer's batch iterator: step s of the run gets batch s of the
    seeded stream. Times every ``next`` on the host clock (data wait)."""

    def __init__(self, stream, batch: int, seq: int, mask_half: bool = False):
        self.stream, self.batch, self.seq = stream, batch, seq
        self.step = 0
        self.waits: list = []
        self.mask_half = mask_half  # a planted fault: the loss over half the rows

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        b = self.stream.batch(self.step, self.batch, self.seq)
        if self.mask_half:
            mask = np.ones(b["tokens"].shape, np.float32)
            mask[..., self.seq // 2:] = 0.0
            b["mask"] = mask
        self.step += 1
        self.waits.append(time.perf_counter() - t0)
        return b


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def leaf_gaps(prog: dict, ref: dict) -> list:
    """Per leaf |norm_prog - norm_ref| / max(norm_ref, median leaf norm)."""
    med = float(np.median(list(ref.values())))
    return [abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in ref]


def compare(run: dict, ref: dict) -> dict:
    """The numbers of one run compared with the reference's: the worst
    step's relative loss gap and the first step's, and the worst and the
    median leaf's gap of the first gradient's norm and of the change's."""
    steps = [abs(a - b) / abs(b) for a, b in zip(run["losses"], ref["losses"])]
    if not all(math.isfinite(x) for x in run["losses"]):
        steps = [float("inf")] * len(steps)
    g1, dl = leaf_gaps(run["grad1"], ref["grad1"]), leaf_gaps(run["delta"], ref["delta"])
    return {"loss_gap": max(steps), "loss1_gap": steps[0],
            "grad1_gap": max(g1), "grad1_median_gap": float(np.median(g1)),
            "delta_gap": max(dl), "delta_median_gap": float(np.median(dl))}


def reference_run(prog: Program, seed: int, batches: list, precision: str = "f32") -> dict:
    """The reference's losses, first-gradient and change norms."""
    import jax

    ref = prog.ref
    sk = ref.Sketch.from_traffic(prog.cell.traffic["estimator"])
    r = ref.Reference(prog.model, sk, prog.cell.traffic["optimizer"], precision)
    key = jax.random.key(trainer_seed(seed))
    keys = [jax.random.fold_in(key, s + 1) for s in range(len(batches))]
    weights = jax.jit(lambda k: ref.make_weights(k, prog.specs))(ref.weight_key(seed))
    out = r.train(weights, batches, keys)
    out["delta"] = prog.delta_norms(seed, out.pop("params"))
    return out


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs[:chips])}


def check_devices(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")


def host_clock_losses() -> dict:
    """Counters of time the machine kept from this process: CPU seconds
    stolen by the hypervisor and waiting on I/O (all CPUs, /proc/stat), and
    this process's involuntary context switches."""
    import resource

    out = {"preempted": float(resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw)}
    if os.path.exists("/proc/stat"):
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        tick = os.sysconf("SC_CLK_TCK")
        out.update(steal_s=int(cpu[8]) / tick, iowait_s=int(cpu[5]) / tick)
    return out


def peak_bytes(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in jax.devices()[:chips])


def start(cell: Cell, seed: int, *, trace: bool = False, fault: Optional[str] = None,
          require_chip: bool = True):
    """Set-up of a run up to the window: the program with its weights from
    ``seed``, driven through the first ``check_steps`` steps by the
    window's own call and feed. Returns (program, state, feed, readings)
    where the readings are what ``correct`` compares.

    ``fault`` plants a fault in the timed path (tests of the check):
    "unchanged" (the step returns its state unchanged), "half_batch" (the
    loss is the mean over half the rows), "answer" (the gradient of one
    leaf is doubled where it is produced)."""
    from benchmarks.chip import tokens
    from repro import compat

    if require_chip:
        check_devices(cell.chips)
        compat.enable_compilation_cache()
        import jax

        # every program of a run, small ones too, is found in the cache next time
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    tr = cell.traffic
    prog = Program(cell, traced=trace, fault=fault)
    stream = tokens.TokenStream(cell.config["vocab_size"], seed, **tr["tokens"])
    feed = Feed(stream, int(tr["batch"]), int(tr["seq_len"]),
                mask_half=(fault == "half_batch"))
    state = prog.make_state(seed)
    readings = {"losses": []}
    for s in range(int(tr["check_steps"])):
        state, hist = prog.train(state, feed, 1, seed)
        readings["losses"].append(float(hist[-1]["loss"]))
        if s == 0:
            readings["grad1"] = prog.grad1_norms(state)
    readings["delta"] = prog.delta_norms(seed, prog.params(state))
    return prog, state, feed, readings


def check_batches(cell: Cell, seed: int) -> list:
    """The batches of the first ``check_steps`` steps, drawn again."""
    from benchmarks.chip import tokens

    tr = cell.traffic
    stream = tokens.TokenStream(cell.config["vocab_size"], seed, **tr["tokens"])
    return [stream.batch(s, int(tr["batch"]), int(tr["seq_len"]))
            for s in range(int(tr["check_steps"]))]


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_chip: bool = True,
        log=lambda *a: print(*a, file=sys.stderr, flush=True)) -> dict:
    """One run of ``cell``; returns the result object (printed by the CLI)."""
    import jax

    from benchmarks.chip import xtrace

    tr = cell.traffic
    B, S = int(tr["batch"]), int(tr["seq_len"])
    prog, state, feed, run_out = start(cell, seed, trace=trace, require_chip=require_chip)
    log(f"check steps: losses {run_out['losses']}")
    # size the window from warm steps: the first call after the checked
    # steps runs slower than steady state on some cells, so time the second
    for k in (2, 4):
        t0 = time.perf_counter()
        state, _ = prog.train(state, feed, k, seed)
        jax.block_until_ready(state)
    step_s = (time.perf_counter() - t0) / 4
    window = seconds if not trace else min(seconds, 5.0)
    n = max(3, int(round(window / step_s)))
    counters = prog.counters()
    waits0 = len(feed.waits)
    trace_dir = tempfile.mkdtemp(prefix="chip_trace_") if trace else None

    gc_pauses = []
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        else:
            gc_pauses.append(time.perf_counter() - gc_t0[0])

    setup_s = time.perf_counter() - t_start
    gc.callbacks.append(on_gc)
    cpu0, host0 = time.process_time(), host_clock_losses()
    t_win = time.perf_counter()
    if trace:
        jax.profiler.start_trace(trace_dir)
    state, hist = prog.train(state, feed, n, seed)
    jax.block_until_ready(state)
    win_s = time.perf_counter() - t_win
    cpu_s = time.process_time() - cpu0
    host = {k: v - host0[k] for k, v in host_clock_losses().items()}
    gc.callbacks.remove(on_gc)
    if trace:
        jax.profiler.stop_trace()
    # where a slow window lost its time: seconds between the trainer's log
    # fetches (every 10 steps), this process's CPU seconds and garbage
    # collections, and what the machine took from it
    marks = [t_win] + [h["host_t"] for h in hist]
    log(f"window intervals (to step, s): "
        f"{[(h['step'], round(b - a, 4)) for h, a, b in zip(hist, marks, marks[1:])]}")
    log(f"window host: cpu {cpu_s:.3f} s, {len(gc_pauses)} gc pauses, "
        f"{1e3 * sum(gc_pauses):.2f} ms in all, longest {1e3 * max(gc_pauses, default=0):.2f} ms; "
        + ", ".join(f"{k} {v:.3f}" for k, v in host.items()))
    mem = peak_bytes(cell.chips)
    losses = [h["loss"] for h in hist]
    failed = sum(1 for x in losses if not math.isfinite(x))
    del state
    gc.collect()

    result = {"correct": None, "attempted": n, "failed": failed}
    device = dict(device_info(cell.chips), memory_peak_bytes=mem)
    if trace:
        tdata = xtrace.load(trace_dir, chips=cell.chips)
        xtrace.remove(trace_dir)
        device["busy_s"] = tdata.busy_s()
        device["window_s"] = tdata.window_s()
        ctx = Readings(cell=cell, trace=tdata, counters=counters, steps=n,
                       data_waits=feed.waits[waits0:],
                       host_spans=prog.spans("train_step")[-n:],
                       device_kind=device["kind"])
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tdata.breakdown()
    else:
        from benchmarks.chip import cost

        tokens_per_s = n * B * S / win_s
        metrics = {"tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"}}
        if require_chip:  # off the chip (tests) there is no peak to share
            flops = cost.model_flops_per_token(cell.config, S, cell.here)
            mfu = 100.0 * flops * tokens_per_s / (
                cell.chips * peaks(device["kind"])["bf16_flops_per_s"])
            metrics["mfu"] = {"value": mfu, "unit": "%"}
        metrics.update(peak_hbm_gb={"value": mem / 1e9, "unit": "GB"},
                       setup_s={"value": setup_s, "unit": "s"})
    log(f"window: {n} steps in {win_s:.3f} s ({step_s:.4f} s/step warm)")

    t_ref = time.perf_counter()
    ref_out = reference_run(prog, seed, check_batches(cell, seed))
    log(f"reference: {time.perf_counter() - t_ref:.1f} s, losses {ref_out['losses']}")
    numbers = compare(run_out, ref_out)
    log(f"program: {run_out}\nreference: {ref_out}")
    result["correct"] = all(numbers[k] <= lim for k, lim in cell.limits.items())
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = {k: {"value": numbers[k], "limit": lim} for k, lim in cell.limits.items()}
    log(f"not compared: { {k: v for k, v in numbers.items() if k not in cell.limits} }")
    return result


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader may read."""
    cell: Cell
    trace: object          # xtrace.Trace of the traced window
    counters: dict         # obs kernel counters after the step compiled
    steps: int             # steps in the traced window
    data_waits: list       # host seconds in the feed, per window step
    host_spans: list       # the trainer's train_step spans of the window
    device_kind: str


def faulty_optimizer(traffic: dict, fault: str, path: tuple):
    """The cell's optimizer with a planted fault (tests of the check);
    ``answer`` doubles the gradient of the leaf at ``path``."""
    import jax
    from repro.optim import Optimizer

    opt = optimizer(traffic)
    if fault == "unchanged":
        return Optimizer(opt.init, lambda g, s, p, step: (p, s))
    if fault == "answer":
        def update(grads, st, params, step):
            return opt.update(_set(grads, path, 2 * _get(grads, path)), st, params, step)
        return Optimizer(opt.init, update)
    if fault == "half_batch":
        return opt
    raise ValueError(f"unknown fault {fault!r}")
