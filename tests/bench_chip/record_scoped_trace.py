#!/usr/bin/env python3
"""Record the scoped-step fixture on a TPU (the tests only read it):

    python3 tests/bench_chip/record_scoped_trace.py [--out tests/bench_chip/data]

Trains a tiny sketched dense model (``data/tiny-dense.json`` cut to one
layer, the l1 block sketch of ``train4k.l1b20`` at 256 tokens) through ``Runtime.train`` with
tracing on, and writes a profiler trace of two steps
(``tiny_scoped.xplane.pb``) and, in ``tiny_scoped.json``, the op->layer
table the program recorded for the step's executable (``op_layers``) and
the program tracer's spans of the traced call (``spans``: name, start and
end in seconds on the tracer's clock).
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

SEQ = 256


def _varint(b: bytes, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, wire type, raw bytes of the whole field) of a protobuf
    message, in order."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            _, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            value = b[i:i + n]
            i += n
            yield key >> 3, value, b[start:i]
            continue
        else:
            i += {1: 8, 5: 4}[kind]
        yield key >> 3, None, b[start:i]


def without_hlo_copies(xspace: bytes) -> bytes:
    """The profile (an XSpace) without the ``/host:metadata`` plane's event
    metadata: the profiler's copies of each module's HLO, megabytes that the
    op table replaces. Everything else is kept byte for byte."""
    out = []
    for num, value, raw in _fields(xspace):
        if num == 1:  # XSpace.planes
            parts = list(_fields(value))
            if any(n == 2 and v == b"/host:metadata" for n, v, _ in parts):
                body = b"".join(r for n, _, r in parts if n != 4)  # XPlane.event_metadata
                n, size = len(body), bytearray()
                while True:
                    size.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
                    n >>= 7
                    if not n:
                        break
                raw = raw[:1] + bytes(size) + body
        out.append(raw)
    return b"".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "data"))
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import bench, tokens
    from repro.api import ExecutionConfig, ObsConfig, Runtime
    from repro.train.trainer import TrainerConfig

    conf = dict(json.load(open(os.path.join(HERE, "data", "tiny-dense.json"))),
                num_hidden_layers=1)
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "chip", "traffic",
                                          "train4k.l1b20.json")))
    cfg, opt = bench.arch_config(conf), bench.optimizer(traffic)
    obs = ObsConfig(trace=True, annotate=True, metrics=True, compile_ledger=False,
                    memory_ledger=False, flight=False)
    rt = Runtime(policy=bench.policy(traffic), execution=ExecutionConfig(obs=obs))
    feed = bench.Feed(tokens.TokenStream(conf["vocab_size"], 1, **traffic["tokens"]), 1, SEQ)
    state = rt.init_state(jax.random.key(0), cfg, opt)
    state, _ = rt.train(cfg, opt, feed, TrainerConfig(steps=2, log_every=1), state=state)
    jax.block_until_ready(state)
    trace_dir = tempfile.mkdtemp(prefix="scoped_trace_")
    # the host's own annotations only (no Python frames, no runtime
    # internals) and no copy of the HLO: the table carries what is needed
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    state, _ = rt.train(cfg, opt, feed, TrainerConfig(steps=4, log_every=1), state=state)
    jax.block_until_ready(state)
    jax.profiler.stop_trace()

    pb, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(args.out, exist_ok=True)
    with open(pb, "rb") as f, open(os.path.join(args.out, "tiny_scoped.xplane.pb"), "wb") as g:
        g.write(without_hlo_copies(f.read()))
    tracer = rt.observability().tracer
    loop = tracer.spans("train_loop")[-1]
    spans = [[s.name, s.t0, s.t1] for s in tracer.spans()
             if s.t0 >= loop.t0 and s.t1 <= loop.t1]
    with open(os.path.join(args.out, "tiny_scoped.json"), "w") as f:
        json.dump({"op_layers": rt.observability().op_layers(), "spans": spans}, f,
                  sort_keys=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    for name in ("tiny_scoped.xplane.pb", "tiny_scoped.json"):
        print(name, os.path.getsize(os.path.join(args.out, name)), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
