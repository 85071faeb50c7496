"""Pallas TPU flash attention for training and prefill: forward and backward.

A thin wrapper over the splash attention kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward that saves
the logsumexp, and separate dQ and dKV kernels under their ``custom_vjp``.
Scores and probabilities live in VMEM only. The QKᵀ, dP, dQ, dK and dV dots
take bf16 operands with float32 accumulation; the forward's PV dot takes
float32 probabilities and values (the splash kernel upcasts V). The mask is
block-sparse: fully masked (causal, or outside the sliding window) blocks are
skipped, with no MXU work and no DMA.

Layout: each KV head is one MQA problem over its G query heads, so K/V are
never repeated to H heads; the kernel is vmapped over batch × KV heads. The
``d_head ** -0.5`` scale is applied to q before the call. S is padded up to
the block size; causal masks keep real queries off padded keys, and a
non-causal call with padding masks them with segment ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

__all__ = ["flash_attention", "block_sizes"]

_LANES = 128


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def block_sizes(seq: int, d_head: int) -> splash.BlockSizes:
    """Tile sizes from the (padded) sequence length and the head size.

    Larger tiles feed the MXU longer runs and cut per-step overhead; the
    VMEM a tile needs grows with ``d_head``, so tiles shrink as it grows:
    1024 rows at d_head 128 (the fastest of a sweep on a v5e at S 4096,
    PERF.md §5), 512 at 256. Every size is a power of two of at least 128
    lanes and divides the padded length (see :func:`_padded`)."""
    unit = _pow2_at_least(max(seq, _LANES))
    blk = min(max(_LANES, 1024 * _LANES // max(d_head, _LANES)), unit)
    sub = min(blk, 512)
    return splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=sub,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=sub,
        block_q_dq=blk, block_kv_dq=blk)


def _padded(seq: int, bs: splash.BlockSizes) -> int:
    return -(-seq // bs.block_q) * bs.block_q


@functools.lru_cache(maxsize=64)
def _kernel(seq: int, groups: int, causal: bool, window, bs: splash.BlockSizes,
            interpret: bool):
    """The splash MQA kernel for one KV head and its ``groups`` query heads,
    built once per shape, mask and tiling."""
    shape = (seq, seq)
    if not causal:
        mask = splash.FullMask(shape)
    elif window is None:
        mask = splash.CausalMask(shape)
    else:
        mask = splash.LocalMask(shape, window_size=(window - 1, 0), offset=0)
    # the mask tables are constants of every program that calls the kernel:
    # make them concrete even when the first call comes inside a trace
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([mask] * groups), block_sizes=bs,
            interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    interpret: bool = False):
    """Self-attention. q: [B, S, H, dh]; k/v: [B, S, Kv, dh] (GQA, MQA or
    MHA) -> [B, S, H, dh] in q's dtype. ``window`` (causal only) keeps the
    keys of the last ``window`` positions, the query's own included."""
    B, S, H, dh = q.shape
    Kv = k.shape[2]
    if k.shape[1] != S:
        raise ValueError(f"self-attention only: {S} queries against {k.shape[1]} keys")
    G = H // Kv
    bs = block_sizes(S, dh)
    Sp = _padded(S, bs)
    q = (q.astype(jnp.float32) * dh ** -0.5).astype(q.dtype)
    # [B, S, Kv, G, dh] -> [B*Kv, G, S, dh]; k/v [B, S, Kv, dh] -> [B*Kv, S, dh]
    qh = jnp.moveaxis(q.reshape(B, S, Kv, G, dh), 1, 3).reshape(B * Kv, G, S, dh)
    kh = jnp.moveaxis(k, 1, 2).reshape(B * Kv, S, dh)
    vh = jnp.moveaxis(v, 1, 2).reshape(B * Kv, S, dh)
    segs = None
    if Sp != S:
        pad = Sp - S
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kh = jnp.pad(kh, ((0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad), (0, 0)))
        if not causal:
            ids = (jnp.arange(Sp) >= S).astype(jnp.int32)
            segs = splash.SegmentIds(q=ids, kv=ids)
    kernel = _kernel(Sp, G, causal, window if causal else None, bs, interpret)
    o = jax.vmap(kernel, in_axes=(0, 0, 0, None))(qh, kh, vh, segs)
    o = o[:, :, :S].reshape(B, Kv, G, S, dh)
    return jnp.moveaxis(o, 3, 1).reshape(B, S, H, dh)
