"""Attention: chunked XLA attention vs naive reference (GQA, window, ragged,
offsets), and the dispatch between the flash kernel and the chunked path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import flash_attention_ref
from repro.nn.attention import AttnCfg, decode_attention, multi_head_attention


@pytest.mark.parametrize("B,Sq,Skv,H,Kv,dh,causal,window", [
    (2, 64, 64, 8, 2, 32, True, None),
    (1, 96, 96, 4, 1, 16, True, 24),
    (2, 50, 50, 4, 4, 16, True, None),     # ragged vs chunks
    (1, 64, 64, 6, 3, 16, False, None),    # bidirectional
    (1, 33, 77, 4, 2, 16, False, None),    # cross-attention shapes
])
def test_chunked_matches_reference(B, Sq, Skv, H, Kv, dh, causal, window):
    ks = jax.random.split(jax.random.key(B * Sq + H), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh))
    k = jax.random.normal(ks[1], (B, Skv, Kv, dh))
    v = jax.random.normal(ks[2], (B, Skv, Kv, dh))
    cfg = AttnCfg(n_heads=H, n_kv=Kv, d_head=dh, causal=causal, window=window,
                  q_chunk=16, kv_chunk=16)
    got = multi_head_attention(q, k, v, cfg)
    want = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_cost_mode_matches_rolled():
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    cfg = AttnCfg(n_heads=4, n_kv=2, d_head=16, q_chunk=16, kv_chunk=16)
    a = multi_head_attention(q, k, v, cfg, cost_mode=False)
    b = multi_head_attention(q, k, v, cfg, cost_mode=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_chunked_backward_finite():
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    cfg = AttnCfg(n_heads=4, n_kv=2, d_head=16, q_chunk=8, kv_chunk=8)
    g = jax.grad(lambda q_: jnp.sum(multi_head_attention(q_, k, v, cfg)))(q)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_decode_matches_full_last_position():
    """decode_attention(pos) == reference attention at the last query row."""
    ks = jax.random.split(jax.random.key(7), 3)
    S, H, Kv, dh = 40, 4, 2, 16
    q_full = jax.random.normal(ks[0], (2, S, H, dh))
    k = jax.random.normal(ks[1], (2, S, Kv, dh))
    v = jax.random.normal(ks[2], (2, S, Kv, dh))
    want = flash_attention_ref(q_full, k, v, causal=True)[:, -1:]
    cfg = AttnCfg(n_heads=H, n_kv=Kv, d_head=dh)
    got = decode_attention(q_full[:, -1:], k, v, S - 1, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_gqa_repeat_gets_sharding_annotation():
    """ROADMAP item: the GQA k/v head repeat must be pinned on BOTH sides
    under a mesh ctx — the pre-repeat [B, S, Kv, dh] tensors arrive
    seq-sharded from the sequence-parallel projections while the repeated
    output is head-sharded, and without the operand annotation SPMD logs an
    `[spmd] Involuntary full rematerialization` in the forward and the
    remat'd backward of production train cells (4 warnings at
    nn/attention.py; the dryrun stderr check lives in test_distributed's
    slow subprocess test)."""
    from repro.launch.mesh import make_mesh
    from repro.nn.attention import AttnCfg, multi_head_attention
    from repro.nn.common import Ctx

    if jax.device_count() < 8:
        pytest.skip("needs the 8 fake host devices forced by conftest")
    mesh = make_mesh((2, 4), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    ctx = Ctx(mesh=mesh, data_axes=("data",), model_axes=("model",),
              act_sharding=NamedSharding(mesh, P(("data",), None, None)))
    cfg = AttnCfg(n_heads=4, n_kv=2, d_head=8, q_chunk=8, kv_chunk=8)
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 8))
    k = jax.random.normal(ks[1], (2, 16, 2, 8))
    v = jax.random.normal(ks[2], (2, 16, 2, 8))

    def f(constrain):
        return lambda q, k, v: multi_head_attention(q, k, v, cfg,
                                                    constrain=constrain)

    jaxpr = str(jax.make_jaxpr(f(ctx.constrain_heads))(q, k, v))
    # pre-repeat k and v pins + post-repeat q/k/v pins (and per-chunk pins)
    assert jaxpr.count("sharding_constraint") >= 5
    # no ctx -> no constraint (single-device paths unchanged)
    jaxpr0 = str(jax.make_jaxpr(f(None))(q, k, v))
    assert "sharding_constraint" not in jaxpr0
    # annotated and unannotated paths compute the same thing
    np.testing.assert_allclose(
        np.asarray(f(ctx.constrain_heads)(q, k, v)),
        np.asarray(f(None)(q, k, v)), rtol=1e-5, atol=1e-5)


def test_rope_broadcast_gets_sharding_annotation():
    """ROADMAP item: RoPE's [B, S, 1, d/2] cos/sin broadcast must carry a
    sharding annotation under a mesh ctx so SPMD stops involuntarily
    rematerializing it in the backward of production train cells (the
    dryrun stderr check lives in test_distributed's slow subprocess test)."""
    from repro.launch.mesh import make_mesh
    from repro.nn.common import Ctx
    from repro.nn.rope import apply_rope

    if jax.device_count() < 8:
        pytest.skip("needs the 8 fake host devices forced by conftest")
    mesh = make_mesh((2, 4), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    ctx = Ctx(mesh=mesh, data_axes=("data",), model_axes=("model",),
              act_sharding=NamedSharding(mesh, P(("data",), None, None)))
    x = jnp.ones((4, 8, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (4, 8))
    jaxpr = str(jax.make_jaxpr(lambda xx, pp: apply_rope(xx, pp, 1e4, ctx=ctx))(x, pos))
    assert "sharding_constraint" in jaxpr
    # no ctx -> no constraint (decode / single-device paths unchanged)
    jaxpr0 = str(jax.make_jaxpr(lambda xx, pp: apply_rope(xx, pp, 1e4))(x, pos))
    assert "sharding_constraint" not in jaxpr0
    # annotated and unannotated paths compute the same thing
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, pos, 1e4, ctx=ctx)),
        np.asarray(apply_rope(x, pos, 1e4)), rtol=1e-6)


def _flash_counters(monkeypatch, tpu: bool):
    """A fresh metrics registry bound to the kernel dispatch, and the
    dispatch told it runs on a TPU (or not)."""
    from repro.kernels import ops
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(ops, "_METRICS", reg)
    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    return lambda name: reg.counter(f"kernels.flash.{name}").value


@pytest.mark.parametrize("case", ["cpu", "segs", "cost_mode", "sharded", "cross",
                                  "d_head_64"])
def test_flash_dispatch_falls_back_to_chunked(monkeypatch, case):
    """Calls the flash kernel does not take run the chunked path — exactly
    what ``impl="chunked"`` computes, never the S x S reference — and count
    as ``kernels.flash.fallback``."""
    count = _flash_counters(monkeypatch, tpu=case != "cpu")
    B, S, H, Kv = 2, 64, 4, 2
    dh = 64 if case == "d_head_64" else 128
    Skv = 96 if case == "cross" else S
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, Skv, Kv, dh))
    v = jax.random.normal(ks[2], (B, Skv, Kv, dh))
    kw = {}
    if case == "segs":
        kw["segs"] = jnp.concatenate([jnp.ones((B, 40), jnp.int32),
                                      2 * jnp.ones((B, S - 40), jnp.int32)], axis=1)
    if case in ("cost_mode", "sharded"):
        kw[case] = True
    cfg = AttnCfg(n_heads=H, n_kv=Kv, d_head=dh, causal=case != "cross",
                  q_chunk=16, kv_chunk=16)
    assert cfg.impl == "pallas"
    got = multi_head_attention(q, k, v, cfg, **kw)
    want = multi_head_attention(q, k, v, dataclasses.replace(cfg, impl="chunked"), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (count("dispatch"), count("fallback")) == (0, 1)


def test_flash_dispatch_counts_one_call_per_attention_layer(monkeypatch):
    """On a TPU every self-attention layer traced takes the flash kernel:
    one ``kernels.flash.dispatch`` per traced call, no fallback. Traced
    only (``eval_shape``): the kernel itself runs only on a chip."""
    from repro.configs.base import ArchConfig
    from repro.models import lm
    from repro.nn import attention as attn_mod
    from repro.nn.common import Ctx

    count = _flash_counters(monkeypatch, tpu=True)
    calls = []
    inner = attn_mod.multi_head_attention

    def spy(*a, **kw):
        calls.append(1)
        return inner(*a, **kw)

    monkeypatch.setattr(attn_mod, "multi_head_attention", spy)
    cfg = ArchConfig(name="flash-dispatch", family="dense", n_layers=3, d_model=256,
                     n_heads=4, n_kv=2, d_head=128, d_ff=512, vocab=128)
    assert cfg.attn_impl == "pallas"
    params = jax.eval_shape(lambda key: lm.init_params(key, cfg), jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 256), jnp.int32), "labels": jnp.zeros((2, 256), jnp.int32)}
    jax.eval_shape(jax.grad(lambda p: lm.lm_loss(p, batch, Ctx(), cfg)[0]), params)
    assert calls and count("dispatch") == len(calls)
    assert count("fallback") == 0
