"""Device scopes (`repro.obs.scopes`): the compiled train step names every
layer in its HLO metadata, the Runtime records the op->layer table once per
step executable when tracing is on (and a persistent compile cache hands it
back unchanged), and the trainer's `train_data` / `train_fetch` spans — with
tracing off nothing is wrapped, recorded or set."""
import re

import jax
import pytest

from repro import obs
from repro.api import ExecutionConfig, ObsConfig, Runtime, SketchConfig, SketchPolicy
from repro.configs.base import ArchConfig
from repro.obs import scopes
from repro.optim import adamw, constant
from repro.train.trainer import TrainerConfig, train_loop

METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"

DENSE = ArchConfig(name="scopes-dense", family="dense", n_layers=2, d_model=256,
                   n_heads=4, n_kv=2, d_ff=512, vocab=512, q_chunk=64, kv_chunk=64,
                   dtype="bfloat16", param_dtype="bfloat16")
MOE = ArchConfig(name="scopes-moe", family="moe", n_layers=2, d_model=256, n_heads=2,
                 n_kv=2, d_ff=128, vocab=512, n_experts=8, top_k=2, q_chunk=64,
                 kv_chunk=64, dtype="bfloat16", param_dtype="bfloat16")
SKETCH = SketchPolicy(base=SketchConfig(method="l1", budget=0.5, block=128,
                                        backend="pallas"))
SEQ = 128


@pytest.fixture(autouse=True)
def _isolated():
    """Shared observability state and the cache-key flag the traced Runtime
    sets are process-wide: each test starts from none and leaves none."""
    from repro.api import runtime

    key = getattr(jax.config, METADATA_KEY)
    obs._reset()
    runtime._cache_clear()
    yield
    obs._reset()
    runtime._cache_clear()
    jax.config.update(METADATA_KEY, key)


def _obs(trace: bool, **kw):
    return ObsConfig(trace=trace, metrics=False, compile_ledger=False,
                     memory_ledger=False, flight=False, **kw)


def _batches(vocab=512, seed=0):
    key = jax.random.key(seed)
    while True:
        key, k = jax.random.split(key)
        toks = jax.random.randint(k, (1, SEQ + 1), 0, vocab)
        yield {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _first_step(rt, cfg):
    opt = adamw(constant(1e-3), clip=1.0)
    state = rt.init_state(jax.random.key(0), cfg, opt)
    step = rt.train_step(cfg, opt)
    state, _ = step(state, next(_batches()), jax.random.key(1))
    jax.block_until_ready(state)
    return step


def _opcodes(hlo: str) -> dict:
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^\s+(?:ROOT\s+)?%?([^\s=%]+)\s*=\s*.*?\s([\w\-]+)\(", hlo, re.M)}


@pytest.mark.parametrize("cfg", [DENSE, MOE], ids=["dense", "moe"])
def test_op_table_names_every_layer(cfg):
    rt = Runtime(policy=SKETCH, execution=ExecutionConfig(obs=_obs(True)))
    step = _first_step(rt, cfg)
    tables = rt.observability().op_layers()
    (module, table), = tables.items()
    assert module.startswith("jit_")
    layers = {layer for layer, _ in table.values()}
    assert {"attn", "ffn", "head", "optim", "sketch"} <= layers
    # a sketched site inside attention counts as the sketch, not attention
    assert any(layer == "sketch" and "attn/sketch" in path
               for layer, path in table.values())
    opcodes = _opcodes(step.compiled().as_text())
    dots = [n for n in table if opcodes.get(n) in ("dot", "convolution")]
    assert dots and all(table[n][0] is not None for n in dots)
    assert "op_layers" in rt.observability().report()


def test_layer_is_the_innermost_name_through_transforms():
    op = ("jit(step_fn)/transpose(jvp(stack))/while/body/closed_call/checkpoint/"
          "attn/sketch/vjp/plan/jit(searchsorted)/while/body")
    assert scopes.layer_of(op) == ("sketch", "stack/attn/sketch/vjp/plan")
    assert scopes.layer_of("jit(step_fn)/optim/mul") == ("optim", "optim")
    # a function called like a scope is not one
    assert scopes.layer_of("jit(step_fn)/jit(stack)/concatenate") == (None, "")


def test_table_reads_an_instruction_that_spans_lines():
    """A kernel's attributes may hold line breaks (the splash attention
    kernels' ``kernel_metadata``): the ``op_name`` on a later line still
    names the instruction's layer, and the next instruction keeps its own."""
    hlo = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->()}",
        "",
        "ENTRY %main.1 (p0: bf16[8,128]) -> bf16[8,128] {",
        "  %p0 = bf16[8,128]{1,0} parameter(0)",
        "  %splash_mqa_dq_no_residuals.1 = bf16[8,128]{1,0} custom-call(%p0), "
        "custom_call_target=\"tpu_custom_call\", frontend_attributes={kernel_metadata={",
        '"xprof_metadata":"{\\"block_q_dq\\": 512}"',
        '}}, metadata={op_name="jit(step_fn)/transpose(jvp(stack))/checkpoint/attn/'
        'vmap(jit(_splash_attention))/splash_mqa_dq_no_residuals/pallas_call"}',
        '  ROOT %add.2 = bf16[8,128]{1,0} add(%splash_mqa_dq_no_residuals.1, %p0), '
        'metadata={op_name="jit(step_fn)/optim/add"}',
        "}",
        "",
    ])
    module, table = scopes.op_layer_table(hlo)
    assert module == "jit_step"
    assert table["splash_mqa_dq_no_residuals.1"] == ("attn", "stack/attn")
    assert table["add.2"] == ("optim", "optim")
    assert table["p0"] == (None, "")


def test_table_survives_a_persistent_cache_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        from repro.api import runtime

        rt = Runtime(policy=SKETCH, execution=ExecutionConfig(obs=_obs(True)))
        tables, entries = [], []
        # the same build twice from one call site (the stack frames in the
        # metadata are part of the key): the second loads the first's
        # executable from the persistent cache, where XLA compiles nothing
        for _ in range(2):
            runtime._cache_clear()
            obs._reset()
            jax.clear_caches()
            _first_step(rt, DENSE)
            tables.append(rt.observability().op_layers())
            entries.append(sorted(tmp_path.glob("jit_step_fn-*")))
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert len(entries[0]) == 1 and entries[1] == entries[0]
        assert tables[1] == tables[0]
        assert any(layer for t in tables[1].values() for layer, _ in t.values())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_tracing_off_wraps_records_and_sets_nothing():
    jax.config.update(METADATA_KEY, False)
    rt = Runtime(policy=SKETCH, execution=ExecutionConfig(obs=_obs(False)))
    step = _first_step(rt, DENSE)
    assert not hasattr(step, "compiled")          # the plain jitted step
    assert rt.observability().op_layers() == {}
    assert "op_layers" not in rt.observability().report()
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    train_loop(rt, DENSE, adamw(constant(1e-3)), _batches(),
               TrainerConfig(steps=3, log_every=2))
    assert rt.observability().tracer.spans() == []


def test_trainer_spans_data_and_every_fetch():
    rt = Runtime(policy=SKETCH, execution=ExecutionConfig(obs=_obs(True)))
    _, hist = train_loop(rt, DENSE, adamw(constant(1e-3)), _batches(),
                         TrainerConfig(steps=5, log_every=2))
    tracer = rt.observability().tracer
    data, fetch = tracer.spans("train_data"), tracer.spans("train_fetch")
    assert [s.attrs["step"] for s in data] == [0, 1, 2, 3, 4]
    # the log cadence fetches steps 0, 2 and 4: one span each
    assert [s.attrs["step"] for s in fetch] == [h["step"] for h in hist] == [0, 2, 4]
    (loop,) = tracer.spans("train_loop")
    assert all(s.parent == loop.sid for s in data + fetch)
