"""Jitted wrappers around the Pallas kernels with backend selection.

On TPU the real kernels run; on CPU (this container) they run in
``interpret=True`` mode — the kernel bodies execute in Python per grid step,
which validates correctness but is slow, so wrappers fall back to the jnp
oracle unless ``REPRO_FORCE_INTERPRET=1`` (tests set it or pass explicitly).

The fused kernels keep f32 accumulators resident in VMEM; when the estimate
(``fused_vmem_bytes`` / ``stream_vmem_bytes``) exceeds the VMEM limit the
dispatch drops to the one-gather XLA fallback. The limit is configurable —
``ExecutionConfig(fused_vmem_limit=...)`` or ``REPRO_FUSED_VMEM_LIMIT`` —
and every resolution + fallback decision is recorded through the bound
``repro.obs`` metrics registry (see :func:`configure`).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.col_scores import col_l1_scores as _col_l1_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.sketch_matmul import (block_gather_matmul as _bgm_pallas,
                                         block_gather_matmul_dw as _bgm_dw_pallas,
                                         block_gather_matmul_fused as _bgm_fused_pallas,
                                         block_stream_matmul_fused as _bgm_stream_pallas,
                                         fused_vmem_bytes, stream_vmem_bytes)

__all__ = ["on_tpu", "block_gather_matmul", "block_gather_matmul_dw",
           "block_gather_matmul_fused", "block_stream_matmul_fused",
           "gather_cols_matmul", "gather_cols_matmul_dw", "col_l1_scores",
           "use_flash", "flash_attention", "fused_vmem_limit", "configure"]

# The TPU compiler's default scoped-VMEM limit on a v5e: it refuses a kernel
# whose scoped allocation exceeds it ("limit 16.00M"), and
# fused_vmem_bytes / stream_vmem_bytes count that allocation the way the
# compiler does, so a kernel is dispatched exactly when it compiles
# (tests/test_tpu_compile.py). Override without code edits:
# configure(vmem_limit=...) — plumbed from ExecutionConfig.fused_vmem_limit —
# wins, then REPRO_FUSED_VMEM_LIMIT.
_FUSED_VMEM_LIMIT = 16 * 2 ** 20

# process-wide overrides/bindings installed by configure()
_VMEM_LIMIT_OVERRIDE = None
_METRICS = None


def configure(*, vmem_limit=None, metrics=None) -> None:
    """Install process-wide kernel-dispatch bindings.

    ``vmem_limit``: override the fused-kernel VMEM budget (bytes; None keeps
    the current override). ``metrics``: a ``repro.obs`` MetricsRegistry that
    dispatch decisions are recorded into (``kernels.fused_vmem_limit`` gauge,
    ``kernels.fused_dispatch`` / ``kernels.fused_fallback`` counters).
    Runtime wires both from its ExecutionConfig; the env var
    ``REPRO_FUSED_VMEM_LIMIT`` covers scripts that never build a Runtime."""
    global _VMEM_LIMIT_OVERRIDE, _METRICS
    if vmem_limit is not None:
        if vmem_limit <= 0:
            raise ValueError(f"vmem_limit must be > 0, got {vmem_limit}")
        _VMEM_LIMIT_OVERRIDE = int(vmem_limit)
    if metrics is not None:
        _METRICS = metrics
    if _METRICS is not None:
        _METRICS.gauge("kernels.fused_vmem_limit").set(fused_vmem_limit())


def fused_vmem_limit() -> int:
    """The effective VMEM budget for the fused backward kernels (bytes):
    configure()/ExecutionConfig override > REPRO_FUSED_VMEM_LIMIT env >
    the built-in default (the v5e compiler's scoped-VMEM limit)."""
    if _VMEM_LIMIT_OVERRIDE is not None:
        return _VMEM_LIMIT_OVERRIDE
    env = os.environ.get("REPRO_FUSED_VMEM_LIMIT")
    if env:
        try:
            v = int(env)
        except ValueError as e:
            raise ValueError(
                f"REPRO_FUSED_VMEM_LIMIT must be an int (bytes), got {env!r}"
            ) from e
        if v > 0:
            return v
    return _FUSED_VMEM_LIMIT


def _record_dispatch(kernel: str, fits: bool) -> None:
    if _METRICS is None:
        return
    _METRICS.counter(f"kernels.{kernel}.dispatch").inc()
    if not fits:
        _METRICS.counter(f"kernels.{kernel}.vmem_fallback").inc()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas() -> bool:
    """On a TPU the Pallas kernels always run; the fused/streaming wrappers
    leave them only through their counted VMEM decision. Off a TPU (tests)
    the XLA oracles run, or the kernels in interpret mode when
    ``REPRO_FORCE_INTERPRET=1``."""
    return on_tpu() or os.environ.get("REPRO_FORCE_INTERPRET") == "1"


def block_gather_matmul(G, block_idx, scales, W, *, block: int = 128):
    if _use_pallas():
        return _bgm_pallas(G, block_idx, scales, W, block=block, interpret=not on_tpu())
    return kref.block_gather_matmul_ref(G, block_idx, scales, W, block=block)


def block_gather_matmul_dw(G, block_idx, scales, X, *, block: int = 128):
    if _use_pallas():
        return _bgm_dw_pallas(G, block_idx, scales, X, block=block, interpret=not on_tpu())
    return kref.block_gather_matmul_dw_ref(G, block_idx, scales, X, block=block)


def block_gather_matmul_fused(G, block_idx, scales, W, X, *, block: int = 128,
                              with_scores: bool = False,
                              score_mode: str = "l1"):
    """One-pass fused backward (dX, compact dW, compact db); see
    ``sketch_matmul.block_gather_matmul_fused``. When the fused accumulators
    would not fit VMEM (on TPU), falls back to
    ``ref.block_gather_matmul_fallback_ref``: ONE barriered XLA gather of
    kept G feeds the dX matmul and a single dW matmul with the db
    row-reduction folded into its stream (ones column on X) — still one pass
    over kept G, just without the Pallas kernel's resident accumulators.
    Off-TPU the single-gather fused XLA oracle runs directly.

    ``with_scores=True`` appends the kept blocks' raw column score reduction
    ([rb, block] f32) on every path — the stale-plan estimator's free
    partial refresh."""
    if _use_pallas():
        rb = block_idx.shape[0]
        fits = fused_vmem_bytes(G.shape[0], W.shape[1], rb, block,
                                jnp.dtype(G.dtype).itemsize,
                                with_scores=with_scores) <= fused_vmem_limit()
        _record_dispatch("fused", fits)
        if fits or not on_tpu():
            return _bgm_fused_pallas(G, block_idx, scales, W, X, block=block,
                                     interpret=not on_tpu(),
                                     with_scores=with_scores,
                                     score_mode=score_mode)
        return kref.block_gather_matmul_fallback_ref(
            G, block_idx, scales, W, X, block=block,
            with_scores=with_scores, score_mode=score_mode)
    return kref.block_gather_matmul_fused_ref(
        G, block_idx, scales, W, X, block=block,
        with_scores=with_scores, score_mode=score_mode)


def block_stream_matmul_fused(G, block_idx, scales, W, X, *, block: int = 128,
                              score_mode: str = "l1"):
    """Streaming one-pass backward over ALL of G: (dX, compact dW, compact
    db, fresh scores [n]) — score/selection/matmuls in one sweep; see
    ``sketch_matmul.block_stream_matmul_fused``. The plan (kept block ids +
    1/p scales, sampled OUTSIDE from carried scores — no G read) arrives as
    ``block_idx``/``scales`` and is expanded to per-block gates here. When
    the streaming accumulators would not fit VMEM (on TPU), or off-TPU,
    falls back to ``ref.block_stream_matmul_onepass_ref``: ONE barriered
    permuted gather of ALL of G (kept blocks first) feeds the same outputs
    with a single G reader."""
    rb = block_idx.shape[0]
    nb = G.shape[1] // block
    if _use_pallas():
        fits = stream_vmem_bytes(G.shape[0], W.shape[1], rb, nb, block,
                                 jnp.dtype(G.dtype).itemsize) <= fused_vmem_limit()
        _record_dispatch("stream", fits)
        if fits or not on_tpu():
            gates = jnp.zeros((nb,), jnp.float32).at[block_idx].set(
                scales.astype(jnp.float32))
            slot_map = jnp.zeros((nb,), jnp.int32).at[block_idx].set(
                jnp.arange(rb, dtype=jnp.int32))
            return _bgm_stream_pallas(G, gates, slot_map, W, X, rb=rb,
                                      block=block, score_mode=score_mode,
                                      interpret=not on_tpu())
    return kref.block_stream_matmul_onepass_ref(G, block_idx, scales, W, X,
                                                block=block,
                                                score_mode=score_mode)


def gather_cols_matmul(G, idx, scales, W):
    """Per-column compact dX. Arbitrary (unblocked) column gathers do not map
    onto BlockSpec index maps, so this stays an XLA gather + matmul; the
    Pallas fast path is the block-granular variant (SketchConfig.block=128)."""
    return kref.gather_cols_matmul_ref(G, idx, scales, W)


def gather_cols_matmul_dw(G, idx, scales, X):
    return kref.gather_cols_matmul_dw_ref(G, idx, scales, X)


def col_l1_scores(G, *, mode: str = "l1"):
    if mode not in kref.COL_SCORE_MODES:
        raise ValueError(f"unknown score mode {mode!r}; "
                         f"expected one of {sorted(kref.COL_SCORE_MODES)}")
    if _use_pallas():
        return _col_l1_pallas(G, mode=mode, interpret=not on_tpu())
    return kref.col_scores_ref(G, mode=mode)


def use_flash(q, k, *, cost_mode: bool, segmented: bool, sharded: bool) -> bool:
    """Whether attention of q [B, Sq, H, dh] over k [B, Skv, Kv, dh] takes
    the Pallas flash kernel: on a TPU, outside cost mode, without segment
    ids, outside a device mesh (XLA cannot partition a Mosaic kernel), for
    self-attention (Sq == Skv) with d_head a multiple of the 128 lanes.
    Every other call takes the chunked XLA path. Records the choice at trace
    time as ``kernels.flash.dispatch`` or ``kernels.flash.fallback``."""
    take = (on_tpu() and not cost_mode and not segmented and not sharded
            and q.shape[1] == k.shape[1] and q.shape[-1] % 128 == 0)
    if _METRICS is not None:
        _METRICS.counter("kernels.flash." + ("dispatch" if take else "fallback")).inc()
    return take


def flash_attention(q, k, v, *, causal: bool = True, window=None):
    """The Pallas flash kernel (forward and backward) for self-attention;
    callers check :func:`use_flash` first."""
    return _flash_pallas(q, k, v, causal=causal, window=window, interpret=not on_tpu())
