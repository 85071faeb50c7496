"""The Pallas kernels compiled for a described TPU v5e at real widths.

Nothing runs: each test lowers a kernel against shapes placed on one chip of
a described ``v5e:2x2`` topology and compiles it with the TPU compiler that
ships with jaxlib. That catches what interpret mode cannot — tiling the
Mosaic compiler refuses, and kernels that need more scoped VMEM than its
default 16 MiB limit. The dispatcher-agreement tests pin
``fused_vmem_bytes`` / ``stream_vmem_bytes`` to that limit from both sides.

Site shapes (Gemma3-1B, N = 4096 tokens, bf16, block 128, budget 0.2):
MLP up/gate G [N, 6912] -> dX width 1152 (54 blocks, 11 kept); attention q
G [N, 1024] -> 1152 (2 kept); attention o G [N, 1152] -> 1024 (2 kept).
Flash attention, forward and backward, at S = N: Yi-6B (32 heads, 4 KV,
d_head 128), OLMoE-1B-7B (16 heads MHA) and a Gemma3-1B local layer.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.col_scores import col_l1_scores
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import fused_vmem_limit
from repro.kernels.sketch_matmul import (block_gather_matmul_fused,
                                         block_stream_matmul_fused,
                                         fused_vmem_bytes, stream_vmem_bytes)

N = 4096
BLOCK = 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # jaxlib ships the TPU compiler: failing to describe the topology is a
    # regression to report, not a reason to skip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described-device compile is written to the persistent cache but can
    # never be read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _fused_args(sh, n, d, rb):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    return (S((N, n), BF16), S((rb,), jnp.int32), S((rb,), jnp.float32),
            S((n, d), BF16), S((N, d), BF16))


def _stream_args(sh, n, d):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    nb = n // BLOCK
    return (S((N, n), BF16), S((nb,), jnp.float32), S((nb,), jnp.int32),
            S((n, d), BF16), S((N, d), BF16))


def _fused(rb, with_scores=False):
    def fn(G, idx, sc, W, X):
        return block_gather_matmul_fused(G, idx, sc, W, X, block=BLOCK,
                                         with_scores=with_scores)
    return fn


def _stream(rb):
    def fn(G, gates, slots, W, X):
        return block_stream_matmul_fused(G, gates, slots, W, X, rb=rb,
                                         block=BLOCK)
    return fn


@pytest.mark.parametrize("n,d,rb,with_scores", [
    (6912, 1152, 11, False),   # MLP up / gate
    (6912, 1152, 11, True),    # same site under the stale-plan estimator
    (1024, 1152, 2, False),    # attention q
    (1152, 1024, 2, False),    # attention o
])
def test_fused_kernel_compiles_at_gemma_sites(one_chip, n, d, rb, with_scores):
    assert fused_vmem_bytes(N, d, rb, BLOCK, 2,
                            with_scores=with_scores) <= fused_vmem_limit()
    hlo = _compile(_fused(rb, with_scores),
                   *_fused_args(one_chip, n, d, rb)).as_text()
    assert "tpu_custom_call" in hlo


def test_stream_kernel_compiles_at_gemma_mlp_site(one_chip):
    n, d, rb = 6912, 1152, 11
    assert stream_vmem_bytes(N, d, rb, n // BLOCK, BLOCK, 2) <= fused_vmem_limit()
    hlo = _compile(_stream(rb), *_stream_args(one_chip, n, d)).as_text()
    assert "tpu_custom_call" in hlo


def test_col_scores_compiles_at_gemma_mlp_site(one_chip):
    G = jax.ShapeDtypeStruct((N, 6912), BF16, sharding=one_chip)
    hlo = _compile(lambda g: col_l1_scores(g, mode="l1"), G).as_text()
    assert "tpu_custom_call" in hlo


def _attention_grad_hlo(one_chip, H, Kv, dh, window=None):
    """HLO of the flash kernel's forward and backward (the gradient of a
    loss of its output) for one row of N tokens, compiled for one described
    v5e, inside the ``attn`` scope as ``nn.attention.attention`` calls it."""
    from repro.obs import scopes

    @scopes.scoped(scopes.ATTN)
    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    sds = lambda shape: jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    return _compile(jax.grad(loss, argnums=(0, 1, 2)), sds((1, N, H, dh)),
                    sds((1, N, Kv, dh)), sds((1, N, Kv, dh))).as_text()


def _f32_score_tiles(hlo: str) -> list:
    """Float32 buffers of the HLO with two axes of 512 or more: a tile of
    scores or probabilities, query rows by key columns. (A count of
    elements cannot tell one: at Yi's widths H * d_head = S, so a q-shaped
    float32 buffer such as the lane-broadcast logsumexp holds S x S.)"""
    return [dims for dims in re.findall(r"\bf32\[([\d,]*)\]", hlo)
            if sum(int(d) >= 512 for d in dims.split(",") if d) >= 2]


def _check_flash_compile(hlo: str) -> None:
    """Forward, dQ and dKV kernels are in the compiled program, no score
    tile reaches HBM, and the op table labels every kernel ``attn``."""
    from repro.obs.scopes import op_layer_table

    assert "tpu_custom_call" in hlo
    kernels = set(re.findall(r"%(splash_mqa_(?:fwd|dq|dkv)[\w.]*)\s*=", hlo))
    for phase in ("fwd", "dq", "dkv"):
        assert any(name.startswith(f"splash_mqa_{phase}") for name in kernels), kernels
    assert not _f32_score_tiles(hlo)
    _, table = op_layer_table(hlo)
    assert all(table[name][0] == "attn" for name in kernels)


@pytest.mark.parametrize("H,Kv", [(32, 4), (16, 16)], ids=["yi-6b", "olmoe"])
def test_flash_attention_fwd_bwd_compiles(one_chip, H, Kv):
    """At S 4096, d_head 128: Yi-6B (GQA, 8 query heads a KV head) and
    OLMoE-1B-7B (MHA)."""
    _check_flash_compile(_attention_grad_hlo(one_chip, H, Kv, 128))


def test_flash_attention_compiles_at_gemma_local_layer(one_chip):
    # 4 query heads sharing 1 kv head (MQA), d_head 256, sliding window 512
    _check_flash_compile(_attention_grad_hlo(one_chip, 4, 1, 256, window=512))


# Dispatcher agreement. d = 1280 needs no padding, so the kernel's outputs
# are the program's outputs and leave through HBM — the case the estimate
# budgets for. 13 kept blocks is the last that fits there, 14 the first that
# does not; the MLP down projection (G [N, 1152] -> 6912) fits with none.
@pytest.mark.parametrize("n,d,rb", [(6912, 1280, 13), (6912, 1280, 14),
                                    (1152, 6912, 1)])
def test_fused_dispatch_agrees_with_compiler(one_chip, n, d, rb):
    args = _fused_args(one_chip, n, d, rb)
    if fused_vmem_bytes(N, d, rb, BLOCK, 2) <= fused_vmem_limit():
        _compile(_fused(rb), *args)
    else:
        with pytest.raises(Exception, match="vmem"):
            _compile(_fused(rb), *args)


@pytest.mark.parametrize("rb", [13, 14])
def test_stream_dispatch_agrees_with_compiler(one_chip, rb):
    n, d = 6912, 1280
    args = _stream_args(one_chip, n, d)
    if stream_vmem_bytes(N, d, rb, n // BLOCK, BLOCK, 2) <= fused_vmem_limit():
        _compile(_stream(rb), *args)
    else:
        with pytest.raises(Exception, match="vmem"):
            _compile(_stream(rb), *args)


def test_agreement_cases_straddle_the_limit():
    """The agreement cases above exercise both branches: one admitted and one
    refused shape on each side of the limit."""
    lim = fused_vmem_limit()
    assert fused_vmem_bytes(N, 1280, 13, BLOCK, 2) <= lim
    assert fused_vmem_bytes(N, 1280, 14, BLOCK, 2) > lim
    assert fused_vmem_bytes(N, 6912, 1, BLOCK, 2) > lim
    assert stream_vmem_bytes(N, 1280, 13, 54, BLOCK, 2) <= lim
    assert stream_vmem_bytes(N, 1280, 14, 54, BLOCK, 2) > lim
