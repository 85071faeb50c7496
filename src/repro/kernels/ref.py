"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["block_gather_matmul_ref", "block_gather_matmul_dw_ref",
           "block_gather_matmul_fused_ref", "block_gather_matmul_dw_db_ref",
           "block_gather_matmul_fallback_ref",
           "block_stream_matmul_onepass_ref", "gather_cols_onepass_ref",
           "gather_cols_fused_scores_ref",
           "gather_cols_matmul_ref", "gather_cols_matmul_dw_ref",
           "COL_SCORE_MODES", "col_scores_ref", "col_l1_scores_ref",
           "flash_attention_ref"]

# The ONE table mapping a score mode to its elementwise column reduction —
# shared by the Pallas kernels (col_scores, sketch_matmul), the XLA oracles
# below, and the ops dispatcher, so the mode sets cannot drift apart.
COL_SCORE_MODES = {"l1": jnp.abs, "l2": jnp.square}


def block_gather_matmul_ref(G, block_idx, scales, W, *, block: int):
    """dX = Σ_k scale_k · G[:, blk_k] @ W[blk_k, :].

    G: [N, n]; block_idx: [rb] (block ids); scales: [rb]; W: [n, d].
    """
    N, n = G.shape
    nb = n // block
    Gb = G.reshape(N, nb, block)
    Wb = W.reshape(nb, block, -1)
    Gc = jnp.take(Gb, block_idx, axis=1).astype(jnp.float32) * scales[None, :, None]
    Wc = jnp.take(Wb, block_idx, axis=0)  # [rb, bs, d]
    return jnp.einsum("nrb,rbd->nd", Gc, Wc.astype(jnp.float32)).astype(G.dtype)


def block_gather_matmul_dw_ref(G, block_idx, scales, X, *, block: int):
    """dWc[k] = scale_k · G[:, blk_k]ᵀ @ X  -> [rb, block, d_in]."""
    N, n = G.shape
    nb = n // block
    Gb = G.reshape(N, nb, block)
    Gc = jnp.take(Gb, block_idx, axis=1).astype(jnp.float32) * scales[None, :, None]
    return jnp.einsum("nrb,nd->rbd", Gc, X.astype(jnp.float32)).astype(G.dtype)


def block_gather_matmul_fused_ref(G, block_idx, scales, W, X, *, block: int,
                                  with_scores: bool = False,
                                  score_mode: str = "l1"):
    """Fused backward oracle: (dX, dWc, db_c) from ONE gather of G.

    The scaled compact ``Gc`` is materialised once (flat column gather — the
    layout XLA lowers with no extra copies; kept blocks are contiguous column
    runs, so this reads exactly the kept slabs) and feeds all three outputs.
    The optimization barrier stops XLA from re-fusing the gather into each
    consumer, which would read G three times — exactly the multi-pass
    backward this path exists to avoid. Shapes as in the Pallas kernel:
    dX [N, d], dWc [rb, block, d], db_c [rb, block] f32.

    ``with_scores=True`` appends the kept blocks' raw (pre-scale) column
    score reduction [rb, block] f32, computed from the already-materialised
    gather — no extra pass over G (the stale-plan partial refresh).
    """
    rb = block_idx.shape[0]
    Gc, cols, kept_s = _gather_scaled_blocks(
        G, block_idx, scales, block,
        score_mode=score_mode if with_scores else None)
    Wc = jnp.take(W, cols, axis=0).astype(jnp.float32)  # [rb*bs, d]
    dX = (Gc @ Wc).astype(G.dtype)
    dWc = jax.lax.dot_general(Gc, X.astype(jnp.float32), (((0,), (0,)), ((), ())))
    db = jnp.sum(Gc, axis=0)  # [rb*bs] f32
    out = (dX, dWc.astype(G.dtype).reshape(rb, block, -1), db.reshape(rb, block))
    if with_scores:
        return out + (kept_s.reshape(rb, block),)
    return out


def _gather_scaled_blocks(G, block_idx, scales, block: int, *,
                          score_mode=None):
    """ONE barriered gather of G's kept column-blocks, scaled, in f32.
    Returns ``(Gc, cols, kept_scores)`` — the per-column index vector is
    shared with any sibling gather (W rows) so the layouts cannot
    desynchronize; ``kept_scores`` ([rb*block] f32, or None when
    ``score_mode`` is None) is the raw pre-scale column reduction of the
    gathered slab, so a score refresh costs no extra read of G.

    The optimization barrier pins the raw gather as a materialised buffer:
    without it XLA re-fuses the gather into every consumer, turning one HBM
    pass over kept G into one pass per consumer."""

    cols = (block_idx[:, None] * block
            + jnp.arange(block, dtype=block_idx.dtype)[None, :]).reshape(-1)
    col_scales = jnp.repeat(scales, block)
    Gc0 = jnp.take(G, cols, axis=1).astype(jnp.float32)
    (Gc0,) = jax.lax.optimization_barrier((Gc0,))
    kept_scores = None
    if score_mode is not None:
        kept_scores = jnp.sum(COL_SCORE_MODES[score_mode](Gc0), axis=0)
    Gc = Gc0 * col_scales[None, :]
    return Gc, cols, kept_scores


def _dw_db_from_gc(Gc, X, rb: int, block: int, out_dtype):
    """Compact dW with db FOLDED INTO ITS MATMUL STREAM: X is augmented with
    a trailing ones column, so ``Gcᵀ @ [X | 1]`` emits the weight gradient
    and the bias gradient from a single dot over a single read of ``Gc`` —
    the db row-reduction no longer exists as a separate consumer."""
    XA = jnp.concatenate(
        [X.astype(jnp.float32), jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
    out = jax.lax.dot_general(Gc, XA, (((0,), (0,)), ((), ())))  # [rb*bs, d+1]
    dWc = out[:, :-1].astype(out_dtype).reshape(rb, block, -1)
    db = out[:, -1].reshape(rb, block)  # f32
    return dWc, db


def block_gather_matmul_dw_db_ref(G, block_idx, scales, X, *, block: int):
    """(dWc, db_c) from ONE gather of G's kept blocks, db folded into the dW
    matmul (trailing ones column on X) — the dW/db side is literally one dot
    over one pass of kept G. Shapes: dWc [rb, block, d_in], db_c [rb, block]
    f32. See :func:`block_gather_matmul_fallback_ref` for the full fallback
    backward that shares the same gather with dX."""
    rb = block_idx.shape[0]
    Gc, _, _ = _gather_scaled_blocks(G, block_idx, scales, block)
    return _dw_db_from_gc(Gc, X, rb, block, G.dtype)


def block_gather_matmul_fallback_ref(G, block_idx, scales, W, X, *, block: int,
                                     with_scores: bool = False,
                                     score_mode: str = "l1"):
    """VMEM-overflow fallback backward: (dX, dWc, db_c) in **one pass over
    kept G**. ONE barriered gather materialises the scaled compact ``Gc``;
    the dX matmul reads ``Gc`` (not G), and the dW/db side is the single
    folded dot of :func:`block_gather_matmul_dw_db_ref`. Unlike the fused
    Pallas kernel this keeps no [r, d] accumulator resident in VMEM — XLA
    tiles the two dots freely — so it is the shape
    ``ops.block_gather_matmul_fused`` drops to when ``fused_vmem_bytes``
    overflows. Shapes as the fused oracle: dX [N, d], dWc [rb, block, d],
    db_c [rb, block] f32 (+ kept raw scores [rb, block] f32 when
    ``with_scores``)."""
    rb = block_idx.shape[0]
    Gc, cols, kept_s = _gather_scaled_blocks(
        G, block_idx, scales, block,
        score_mode=score_mode if with_scores else None)
    Wc = jnp.take(W, cols, axis=0).astype(jnp.float32)  # [rb*bs, d]
    dX = (Gc @ Wc).astype(G.dtype)
    dWc, db = _dw_db_from_gc(Gc, X, rb, block, G.dtype)
    if with_scores:
        return dX, dWc, db, kept_s.reshape(rb, block)
    return dX, dWc, db


def _onepass_perm(sel, total, r):
    """Permutation putting the ``r`` selected ids first (in selection order)
    and the rest after (ascending). ``sel``: [r] ascending unique ids."""
    keyv = jnp.full((total,), total, jnp.int32).at[sel].set(
        jnp.arange(r, dtype=jnp.int32))
    keyv = jnp.where(keyv < r, keyv,
                     r + jnp.arange(total, dtype=jnp.int32))
    return jnp.argsort(keyv)


def block_stream_matmul_onepass_ref(G, block_idx, scales, W, X, *, block: int,
                                    score_mode: str = "l1"):
    """XLA oracle for the streaming one-pass backward: (dX, dWc, db_c,
    scores) with ONE reader of G.

    A single permuted gather materialises ALL of G (kept blocks first, in
    slot order, then dropped blocks); the barrier pins it as one buffer.
    Fresh column scores for every block come from that copy (scattered back
    through the permutation), and the kept prefix — scaled — feeds the same
    dX / folded dW+db dots as the fallback oracle. The price vs the kept-only
    gather is materialising the dropped part of G too (it must be read for
    the scores anyway); vs the two-pass path the separate score read of G is
    gone. Shapes: dX [N, d], dWc [rb, block, d], db_c [rb, block] f32,
    scores [n] f32 (raw Σ|G| or ΣG² per column)."""

    N, n = G.shape
    nb = n // block
    rb = block_idx.shape[0]
    perm = _onepass_perm(block_idx, nb, rb)
    cols = (perm[:, None] * block
            + jnp.arange(block, dtype=jnp.int32)[None, :]).reshape(-1)
    Gall = jnp.take(G, cols, axis=1).astype(jnp.float32)
    (Gall,) = jax.lax.optimization_barrier((Gall,))
    red = jnp.sum(COL_SCORE_MODES[score_mode](Gall), axis=0)  # [n] permuted
    scores = jnp.zeros((n,), jnp.float32).at[cols].set(red)
    kept = rb * block
    Gc = Gall[:, :kept] * jnp.repeat(scales, block)[None, :]
    Wc = jnp.take(W, cols[:kept], axis=0).astype(jnp.float32)
    dX = (Gc @ Wc).astype(G.dtype)
    dWc, db = _dw_db_from_gc(Gc, X, rb, block, G.dtype)
    return dX, dWc, db, scores


def gather_cols_onepass_ref(G, idx, scales, W, X, *, score_mode: str = "l1"):
    """Per-column one-pass backward oracle: (dX, dW_rows, db_rows, scores)
    with ONE reader of G — the unblocked counterpart of
    :func:`block_stream_matmul_onepass_ref`. dW_rows: [r, d_in]; db_rows:
    [r] f32; scores: [n] f32 raw per-column reduction."""

    n = G.shape[1]
    r = idx.shape[0]
    perm = _onepass_perm(idx.astype(jnp.int32), n, r)
    Gall = jnp.take(G, perm, axis=1).astype(jnp.float32)
    (Gall,) = jax.lax.optimization_barrier((Gall,))
    red = jnp.sum(COL_SCORE_MODES[score_mode](Gall), axis=0)
    scores = jnp.zeros((n,), jnp.float32).at[perm].set(red)
    Gc = Gall[:, :r] * scales[None, :].astype(jnp.float32)
    Wc = jnp.take(W, perm[:r], axis=0).astype(jnp.float32)
    dX = (Gc @ Wc).astype(G.dtype)
    XA = jnp.concatenate(
        [X.astype(jnp.float32), jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
    out = jax.lax.dot_general(Gc, XA, (((0,), (0,)), ((), ())))  # [r, d+1]
    return dX, out[:, :-1].astype(G.dtype), out[:, -1], scores


def gather_cols_fused_scores_ref(G, idx, scales, W, X, *,
                                 score_mode: str = "l1"):
    """Per-column compact backward with a kept-column score refresh from ONE
    barriered gather of G: (dX, dW_rows, db_rows, kept_scores). The stale
    estimator's unblocked path — like the per-column compact pair but the
    gather is shared and the raw reduction rides along for free."""

    r = idx.shape[0]
    Gc0 = jnp.take(G, idx, axis=1).astype(jnp.float32)
    (Gc0,) = jax.lax.optimization_barrier((Gc0,))
    kept_s = jnp.sum(COL_SCORE_MODES[score_mode](Gc0), axis=0)  # [r]
    Gc = Gc0 * scales[None, :].astype(jnp.float32)
    Wc = jnp.take(W, idx, axis=0).astype(jnp.float32)
    dX = (Gc @ Wc).astype(G.dtype)
    XA = jnp.concatenate(
        [X.astype(jnp.float32), jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
    out = jax.lax.dot_general(Gc, XA, (((0,), (0,)), ((), ())))  # [r, d+1]
    return dX, out[:, :-1].astype(G.dtype), out[:, -1], kept_s


def gather_cols_matmul_ref(G, idx, scales, W):
    """Per-column compact backward dX (XLA reference used by backend="compact")."""
    Gc = jnp.take(G, idx, axis=1) * scales[None, :].astype(G.dtype)
    Wc = jnp.take(W, idx, axis=0)
    return (Gc.astype(jnp.float32) @ Wc.astype(jnp.float32)).astype(G.dtype)


def gather_cols_matmul_dw_ref(G, idx, scales, X):
    Gc = jnp.take(G, idx, axis=1) * scales[None, :].astype(G.dtype)
    return (Gc.astype(jnp.float32).T @ X.astype(jnp.float32)).astype(G.dtype)


def col_scores_ref(G, *, mode: str = "l1"):
    """fp32 column score reduction over G per :data:`COL_SCORE_MODES`:
    s_j = Σ_i |G[i, j]| (``"l1"``) or Σ_i G[i, j]² (``"l2"``)."""
    return jnp.sum(COL_SCORE_MODES[mode](G.astype(jnp.float32)), axis=0)


def col_l1_scores_ref(G):
    """ℓ1 column scores in fp32: s_j = Σ_i |G[i, j]|."""
    return col_scores_ref(G, mode="l1")


def flash_attention_ref(q, k, v, *, causal: bool = True, window=None, scale=None):
    """q: [B, Sq, H, dh]; k/v: [B, Skv, Kv, dh] (GQA) -> [B, Sq, H, dh]."""
    B, Sq, H, dh = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, dh).astype(jnp.float32)
    sc = scale if scale is not None else dh ** -0.5
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg, k.astype(jnp.float32)) * sc
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        off = k.shape[1] - Sq  # right-aligned when Skv > Sq
        mask &= (qpos + off) >= kpos
        if window is not None:
            mask &= (qpos + off - kpos) < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, dh).astype(q.dtype)
