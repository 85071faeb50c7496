"""The Pallas kernels compiled for a described TPU v5e at Gemma3-1B widths.

Nothing runs: each test lowers a kernel against shapes placed on one chip of
a described ``v5e:2x2`` topology and compiles it with the TPU compiler that
ships with jaxlib. That catches what interpret mode cannot — tiling the
Mosaic compiler refuses, and kernels that need more scoped VMEM than its
default 16 MiB limit. The dispatcher-agreement tests pin
``fused_vmem_bytes`` / ``stream_vmem_bytes`` to that limit from both sides.

Site shapes (Gemma3-1B, N = 4096 tokens, bf16, block 128, budget 0.2):
MLP up/gate G [N, 6912] -> dX width 1152 (54 blocks, 11 kept); attention q
G [N, 1024] -> 1152 (2 kept); attention o G [N, 1152] -> 1024 (2 kept).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

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


def test_flash_attention_compiles_at_gemma_local_layer(one_chip):
    # 4 query heads sharing 1 kv head (MQA), d_head 256, sliding window 512
    S = lambda shape: jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    hlo = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                   window=512),
                   S((1, N, 4, 256)), S((1, N, 1, 256)),
                   S((1, N, 1, 256))).as_text()
    assert "tpu_custom_call" in hlo


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
