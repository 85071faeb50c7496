"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.col_scores import col_l1_scores
from repro.kernels.flash_attention import flash_attention
from repro.kernels.sketch_matmul import (block_gather_matmul, block_gather_matmul_dw,
                                         block_gather_matmul_fused,
                                         block_stream_matmul_fused)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("N,n,d,rb,bs,dt", [
    (64, 512, 384, 2, 128, jnp.float32),
    (100, 256, 130, 1, 128, jnp.float32),
    (256, 1024, 512, 4, 128, jnp.bfloat16),
    (32, 256, 96, 2, 64, jnp.float32),
    (8, 128, 64, 1, 128, jnp.float32),
])
def test_block_gather_matmul(N, n, d, rb, bs, dt):
    ks = jax.random.split(jax.random.key(N * n + d), 4)
    G = jax.random.normal(ks[0], (N, n), dt)
    W = jax.random.normal(ks[1], (n, d), dt)
    X = jax.random.normal(ks[2], (N, d), dt)
    nb = n // bs
    idx = jnp.sort(jax.random.choice(ks[3], nb, (rb,), replace=False)).astype(jnp.int32)
    sc = jax.random.uniform(ks[3], (rb,), minval=0.5, maxval=2.0)
    got = block_gather_matmul(G, idx, sc, W, block=bs, interpret=True)
    want = ref.block_gather_matmul_ref(G, idx, sc, W, block=bs)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=_tol(dt), atol=_tol(dt))
    got2 = block_gather_matmul_dw(G, idx, sc, X, block=bs, interpret=True)
    want2 = ref.block_gather_matmul_dw_ref(G, idx, sc, X, block=bs)
    np.testing.assert_allclose(np.asarray(got2, np.float32), np.asarray(want2, np.float32),
                               rtol=_tol(dt), atol=_tol(dt))


@pytest.mark.parametrize("N,n,d,rb,bs,dt", [
    (64, 512, 384, 2, 128, jnp.float32),
    (100, 256, 130, 1, 128, jnp.float32),
    (256, 1024, 512, 4, 128, jnp.bfloat16),
    (32, 256, 96, 2, 64, jnp.float32),
    (8, 128, 64, 1, 128, jnp.float32),
])
def test_block_gather_matmul_fused(N, n, d, rb, bs, dt):
    """Fused one-pass kernel: BIT-identical to the unfused pair for the same
    plan (same tiles, same accumulation order), allclose to the jnp oracle."""
    ks = jax.random.split(jax.random.key(N * n + d), 4)
    G = jax.random.normal(ks[0], (N, n), dt)
    W = jax.random.normal(ks[1], (n, d), dt)
    X = jax.random.normal(ks[2], (N, d), dt)
    nb = n // bs
    idx = jnp.sort(jax.random.choice(ks[3], nb, (rb,), replace=False)).astype(jnp.int32)
    sc = jax.random.uniform(ks[3], (rb,), minval=0.5, maxval=2.0)

    dX, dWc, db = block_gather_matmul_fused(G, idx, sc, W, X, block=bs, interpret=True)
    dX_u = block_gather_matmul(G, idx, sc, W, block=bs, interpret=True)
    dW_u = block_gather_matmul_dw(G, idx, sc, X, block=bs, interpret=True)
    np.testing.assert_array_equal(np.asarray(dX, np.float32), np.asarray(dX_u, np.float32))
    np.testing.assert_array_equal(np.asarray(dWc, np.float32), np.asarray(dW_u, np.float32))

    rdX, rdW, rdb = ref.block_gather_matmul_fused_ref(G, idx, sc, W, X, block=bs)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    # The kernel sums block by block and the oracle in one dot, so the two
    # round differently. In float32 that rounding, not the 2e-5 tolerance,
    # sets the absolute error of a K-term sum of unit-scale products, and it
    # grows like sqrt(K) (K = rb*bs for dX, N for dW); bfloat16's tolerance
    # already covers it.
    f32 = dt == jnp.float32
    atol_dx = tol * np.sqrt(rb * bs) if f32 else tol
    atol_dw = tol * np.sqrt(N) if f32 else tol
    np.testing.assert_allclose(np.asarray(dX, np.float32), np.asarray(rdX, np.float32),
                               rtol=tol, atol=atol_dx)
    np.testing.assert_allclose(np.asarray(dWc, np.float32), np.asarray(rdW, np.float32),
                               rtol=tol, atol=atol_dw)
    np.testing.assert_allclose(np.asarray(db), np.asarray(rdb), rtol=tol, atol=tol * 10)


def test_fused_ref_matches_manual():
    """The fused oracle's three outputs equal the independent formulas."""
    ks = jax.random.split(jax.random.key(7), 4)
    N, n, d, bs = 24, 64, 40, 16
    G = jax.random.normal(ks[0], (N, n))
    W = jax.random.normal(ks[1], (n, d))
    X = jax.random.normal(ks[2], (N, d))
    idx = jnp.asarray([0, 2], jnp.int32)
    sc = jnp.asarray([1.5, 0.5], jnp.float32)
    dX, dWc, db = ref.block_gather_matmul_fused_ref(G, idx, sc, W, X, block=bs)
    np.testing.assert_allclose(
        np.asarray(dX), np.asarray(ref.block_gather_matmul_ref(G, idx, sc, W, block=bs)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dWc), np.asarray(ref.block_gather_matmul_dw_ref(G, idx, sc, X, block=bs)),
        rtol=1e-5, atol=1e-5)
    cols = (idx[:, None] * bs + jnp.arange(bs)).reshape(-1)
    want_db = (jnp.take(G, cols, axis=1) * jnp.repeat(sc, bs)[None, :]).sum(0)
    np.testing.assert_allclose(np.asarray(db).reshape(-1), np.asarray(want_db),
                               rtol=1e-5, atol=1e-5)


def test_dw_db_ref_matches_fused_halves():
    """The VMEM-fallback's shared-gather dW/db oracle equals the dW/db halves
    of the fused oracle (ops.block_gather_matmul_fused composes it with the
    dX kernel when the fused accumulators overflow VMEM on TPU)."""
    ks = jax.random.split(jax.random.key(11), 3)
    N, n, d, bs = 32, 96, 24, 16
    G = jax.random.normal(ks[0], (N, n))
    W = jax.random.normal(ks[1], (n, d))
    X = jax.random.normal(ks[2], (N, d))
    idx = jnp.asarray([1, 4, 5], jnp.int32)
    sc = jnp.asarray([2.0, 0.5, 1.25], jnp.float32)
    dWc, db = ref.block_gather_matmul_dw_db_ref(G, idx, sc, X, block=bs)
    _, want_dw, want_db = ref.block_gather_matmul_fused_ref(G, idx, sc, W, X,
                                                            block=bs)
    np.testing.assert_allclose(np.asarray(dWc), np.asarray(want_dw),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(want_db),
                               rtol=1e-5, atol=1e-5)
    assert dWc.shape == (3, bs, d) and db.shape == (3, bs)


@pytest.mark.parametrize("N,n,d,rb,bs,dt", [
    (64, 512, 384, 2, 128, jnp.float32),
    (32, 256, 96, 2, 64, jnp.float32),
    (256, 1024, 512, 4, 128, jnp.bfloat16),
])
def test_stream_kernel_bit_identical_to_fused(N, n, d, rb, bs, dt):
    """Streaming selection (one pass over ALL of G) is BIT-identical to the
    kept-only fused kernel on dX/dWc/db for the same keep decisions: kept
    blocks accumulate in the same order with the same operands, and dropped
    blocks only touch the score reduction. Fresh scores match numpy."""
    ks = jax.random.split(jax.random.key(N * n + d + 1), 4)
    G = jax.random.normal(ks[0], (N, n), dt)
    W = jax.random.normal(ks[1], (n, d), dt)
    X = jax.random.normal(ks[2], (N, d), dt)
    nb = n // bs
    idx = jnp.sort(jax.random.choice(ks[3], nb, (rb,), replace=False)).astype(jnp.int32)
    sc = jax.random.uniform(ks[3], (rb,), minval=0.5, maxval=2.0)
    gates = jnp.zeros((nb,), jnp.float32).at[idx].set(sc.astype(jnp.float32))
    slot_map = jnp.zeros((nb,), jnp.int32).at[idx].set(jnp.arange(rb, dtype=jnp.int32))

    dX_s, dWc_s, db_s, scores = block_stream_matmul_fused(
        G, gates, slot_map, W, X, rb=rb, block=bs, interpret=True)
    dX_f, dWc_f, db_f = block_gather_matmul_fused(G, idx, sc, W, X, block=bs,
                                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(dX_s, np.float32), np.asarray(dX_f, np.float32))
    np.testing.assert_array_equal(np.asarray(dWc_s, np.float32), np.asarray(dWc_f, np.float32))
    np.testing.assert_array_equal(np.asarray(db_s), np.asarray(db_f))

    want_s = np.abs(np.asarray(G, np.float32)).sum(0)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(scores), want_s, rtol=tol, atol=tol)


def test_fused_with_scores_outputs_unchanged():
    """with_scores=True is a free rider: the three gradient outputs are
    byte-identical with the flag on or off (Pallas kernel AND oracle), and
    the appended kept-block scores equal the raw column reduction."""
    ks = jax.random.split(jax.random.key(23), 4)
    N, n, d, bs, rb = 32, 256, 96, 64, 2
    G = jax.random.normal(ks[0], (N, n))
    W = jax.random.normal(ks[1], (n, d))
    X = jax.random.normal(ks[2], (N, d))
    idx = jnp.asarray([1, 3], jnp.int32)
    sc = jnp.asarray([1.5, 0.75], jnp.float32)
    base = block_gather_matmul_fused(G, idx, sc, W, X, block=bs, interpret=True)
    plus = block_gather_matmul_fused(G, idx, sc, W, X, block=bs, interpret=True,
                                     with_scores=True)
    for a, b in zip(base, plus[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cols = (np.asarray(idx)[:, None] * bs + np.arange(bs)).reshape(-1)
    want = np.abs(np.asarray(G, np.float32))[:, cols].sum(0).reshape(rb, bs)
    np.testing.assert_allclose(np.asarray(plus[3]), want, rtol=1e-4, atol=1e-4)

    rbase = ref.block_gather_matmul_fused_ref(G, idx, sc, W, X, block=bs)
    rplus = ref.block_gather_matmul_fused_ref(G, idx, sc, W, X, block=bs,
                                              with_scores=True)
    for a, b in zip(rbase, rplus[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(rplus[3]), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["l1", "l2"])
def test_onepass_ref_matches_fused_ref(mode):
    """The streaming one-pass XLA oracle produces the same gradients as the
    kept-only fused oracle for the same plan, plus full fresh scores equal to
    the direct column reduction."""
    ks = jax.random.split(jax.random.key(31), 4)
    N, n, d, bs = 24, 128, 40, 32
    G = jax.random.normal(ks[0], (N, n))
    W = jax.random.normal(ks[1], (n, d))
    X = jax.random.normal(ks[2], (N, d))
    idx = jnp.asarray([0, 3], jnp.int32)
    sc = jnp.asarray([2.0, 0.5], jnp.float32)
    dX, dWc, db, scores = ref.block_stream_matmul_onepass_ref(
        G, idx, sc, W, X, block=bs, score_mode=mode)
    rdX, rdW, rdb = ref.block_gather_matmul_fused_ref(G, idx, sc, W, X, block=bs)
    np.testing.assert_allclose(np.asarray(dX), np.asarray(rdX), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dWc), np.asarray(rdW), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(rdb), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(scores),
                               np.asarray(ref.col_scores_ref(G, mode=mode)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["l1", "l2"])
def test_col_scores_fp32_accumulation_property(mode):
    """The fp32-accumulation promise in col_scores.py as a tested property:
    at N = 10^5 rows the fp32 tree reduction of |G| / G² matches a float64
    reference to ~1e-6 relative — naive fp16/bf16 accumulation would be off
    by orders of magnitude more."""
    rng = np.random.default_rng(0)
    N, n = 100_000, 8
    G64 = rng.standard_normal((N, n))
    G = jnp.asarray(G64, jnp.float32)
    got = np.asarray(col_l1_scores(G, mode=mode, interpret=True), np.float64)
    red = np.abs if mode == "l1" else np.square
    want = red(np.asarray(G, np.float64)).sum(0)  # f64 over the f32 values
    # fp32 sequential tile accumulation: ~sqrt(steps)*eps relative; bf16
    # accumulation would sit at ~1e-2 and fail this by three decades.
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_ops_fused_vmem_limit_resolution(monkeypatch):
    """fused_vmem_limit(): configure() override > REPRO_FUSED_VMEM_LIMIT env
    > built-in default; invalid values raise; dispatch decisions land in the
    bound metrics registry."""
    from repro.kernels import ops
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(ops, "_VMEM_LIMIT_OVERRIDE", None)
    monkeypatch.setattr(ops, "_METRICS", None)
    monkeypatch.delenv("REPRO_FUSED_VMEM_LIMIT", raising=False)
    assert ops.fused_vmem_limit() == ops._FUSED_VMEM_LIMIT

    monkeypatch.setenv("REPRO_FUSED_VMEM_LIMIT", str(7 * 2 ** 20))
    assert ops.fused_vmem_limit() == 7 * 2 ** 20
    monkeypatch.setenv("REPRO_FUSED_VMEM_LIMIT", "not-a-number")
    with pytest.raises(ValueError):
        ops.fused_vmem_limit()
    monkeypatch.setenv("REPRO_FUSED_VMEM_LIMIT", str(7 * 2 ** 20))

    reg = MetricsRegistry()
    ops.configure(vmem_limit=5 * 2 ** 20, metrics=reg)
    assert ops.fused_vmem_limit() == 5 * 2 ** 20  # override beats env
    assert reg.gauge("kernels.fused_vmem_limit").value == 5 * 2 ** 20
    with pytest.raises(ValueError):
        ops.configure(vmem_limit=0)

    ks = jax.random.split(jax.random.key(3), 3)
    N, n, d, bs = 16, 128, 32, 64
    G = jax.random.normal(ks[0], (N, n))
    W = jax.random.normal(ks[1], (n, d))
    X = jax.random.normal(ks[2], (N, d))
    idx = jnp.asarray([1], jnp.int32)
    sc = jnp.asarray([2.0], jnp.float32)
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    ops.block_gather_matmul_fused(G, idx, sc, W, X, block=bs)
    ops.block_stream_matmul_fused(G, idx, sc, W, X, block=bs)
    assert reg.counter("kernels.fused.dispatch").value == 1
    assert reg.counter("kernels.stream.dispatch").value == 1


@pytest.mark.parametrize("N,n,dt,mode", [
    (300, 700, jnp.float32, "l1"), (64, 128, jnp.bfloat16, "l1"),
    (128, 384, jnp.float32, "l2"), (17, 130, jnp.float32, "l1"),
])
def test_col_scores(N, n, dt, mode):
    G = jax.random.normal(jax.random.key(N + n), (N, n), dt)
    got = col_l1_scores(G, mode=mode, interpret=True)
    if mode == "l1":
        want = ref.col_l1_scores_ref(G)
    else:
        want = jnp.sum(jnp.square(G.astype(jnp.float32)), axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2 if dt == jnp.bfloat16 else 1e-5)


def _all_128_blocks(seq, d_head):
    """Tiles of 128 (the least a TPU tile takes): several q and kv blocks at
    test sizes, so dead causal / window blocks are skipped."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes
    return BlockSizes(block_q=128, block_kv=128, block_kv_compute=128,
                      block_q_dkv=128, block_kv_dkv=128, block_kv_dkv_compute=128,
                      block_q_dq=128, block_kv_dq=128)


def _flash_case(*vals, tiles=None):
    """One case, its id spelled from its values (``-t128`` where the test
    sets 128-row tiles)."""
    name = "-".join(getattr(v, "__name__", str(v)) for v in vals)
    return pytest.param(*vals, tiles, id=name + ("" if tiles is None else f"-t{tiles}"))


@pytest.mark.parametrize("B,Sq,Skv,H,Kv,dh,causal,window,dt,tiles", [
    _flash_case(2, 128, 128, 4, 2, 64, True, None, jnp.float32),
    _flash_case(1, 96, 96, 4, 4, 64, True, 32, jnp.float32),
    _flash_case(2, 64, 192, 4, 1, 128, True, None, jnp.float32),  # Sq != Skv: dispatch
    _flash_case(1, 128, 128, 2, 2, 64, False, None, jnp.float32),
    _flash_case(1, 128, 128, 4, 2, 64, True, None, jnp.bfloat16),
    _flash_case(1, 100, 100, 2, 2, 64, True, None, jnp.float32),  # ragged
    _flash_case(1, 384, 384, 8, 2, 128, True, None, jnp.float32, tiles=128),  # GQA, skipping
    _flash_case(1, 256, 256, 4, 4, 128, True, None, jnp.float32),  # MHA
    _flash_case(2, 256, 256, 4, 1, 128, True, None, jnp.bfloat16),  # MQA, bf16
    _flash_case(1, 384, 384, 4, 1, 256, True, 100, jnp.float32, tiles=128),  # window skipping
    _flash_case(1, 300, 300, 4, 2, 128, True, None, jnp.bfloat16, tiles=128),  # S % block != 0
    _flash_case(1, 200, 200, 2, 2, 128, False, None, jnp.float32, tiles=128),  # padded keys
])
def test_flash_attention(monkeypatch, B, Sq, Skv, H, Kv, dh, causal, window, dt, tiles):
    """The flash kernel (interpret mode) against the float32 reference:
    the forward and dQ/dK/dV. A call it does not take (Sq != Skv) is sent
    to the chunked path by the dispatch, and matches the reference there."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import ops
    from repro.nn.attention import AttnCfg, multi_head_attention
    from repro.obs.metrics import MetricsRegistry

    ks = jax.random.split(jax.random.key(B * Sq + H), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dt)
    k = jax.random.normal(ks[1], (B, Skv, Kv, dh), dt)
    v = jax.random.normal(ks[2], (B, Skv, Kv, dh), dt)
    ct = jax.random.normal(ks[3], (B, Sq, H, dh), jnp.float32)
    if tiles is not None:
        monkeypatch.setattr(fa, "block_sizes", _all_128_blocks)
    if Sq == Skv:
        attend = functools.partial(flash_attention, causal=causal, window=window,
                                   interpret=True)
    else:
        reg = MetricsRegistry()
        monkeypatch.setattr(ops, "_METRICS", reg)
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        cfg = AttnCfg(n_heads=H, n_kv=Kv, d_head=dh, causal=causal, window=window,
                      q_chunk=32, kv_chunk=32)
        assert cfg.impl == "pallas"
        # right-aligned queries, as the reference places them
        attend = functools.partial(multi_head_attention, cfg=cfg, q_offset=Skv - Sq)
    f32 = lambda x: x.astype(jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(f32(fn(q, k, v)) * ct)

    ref_fn = lambda q, k, v: ref.flash_attention_ref(f32(q), f32(k), f32(v),
                                                      causal=causal, window=window)
    got = jax.jit(attend)(q, k, v)
    want = ref_fn(q, k, v)
    dgot = jax.jit(jax.grad(loss(attend), argnums=(0, 1, 2)))(q, k, v)
    dwant = jax.grad(loss(ref_fn), argnums=(0, 1, 2))(q, k, v)
    if Sq != Skv:
        assert reg.counter("kernels.flash.fallback").value >= 1
        assert reg.counter("kernels.flash.dispatch").value == 0
    assert got.dtype == dt
    tol = 3e-2 if dt == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)
    for a, b in zip(dgot, dwant):
        assert a.dtype == dt
        err = float(jnp.linalg.norm(f32(a) - b) / jnp.linalg.norm(b))
        assert err < (1e-2 if dt == jnp.bfloat16 else 1e-5), err
