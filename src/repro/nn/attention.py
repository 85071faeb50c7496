"""Attention: GQA/MQA with RoPE / M-RoPE, causal, bidirectional, sliding-window.

``AttnCfg.impl`` picks how the attention core (scores, softmax, PV) runs:
  * pallas   — the default. On a TPU, self-attention in training and prefill
               runs the Pallas flash kernel with its own backward
               (``kernels/flash_attention.py``): dead causal or window blocks
               skipped, bf16 MXU operands (but in the forward's PV), no
               score tile in HBM, K/V never repeated to H heads. ``kernels.ops.use_flash`` decides from
               what the call shows (TPU, not cost mode, no segment ids, no
               device mesh, Sq == Skv, d_head a multiple of 128); every other
               call — CPU, dry-run cost artifacts, segment-masked prefill,
               sharded steps, cross-attention — falls through to the chunked
               path.
  * chunked  — memory-bounded double-chunked online-softmax attention in XLA
               (never materialises S×S). Sliding-window layers statically
               slice only ``window + Cq`` keys per query chunk, so locality
               is a *shape-level* FLOP saving.
  * einsum   — naive reference (tests, tiny shapes).
Decode (one query against a cache) uses a dedicated masked-einsum path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn.common import Ctx, dense, dense_init
from repro.nn.rope import apply_mrope, apply_rope
from repro.obs import scopes

__all__ = ["AttnCfg", "attn_init", "attention", "decode_attention", "init_kv_cache"]


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    n_heads: int
    n_kv: int
    d_head: int
    causal: bool = True
    window: Optional[int] = None  # sliding window (None = full)
    rope: str = "default"  # default | mrope | none
    theta: float = 10000.0
    q_chunk: int = 512
    kv_chunk: int = 512
    impl: str = "pallas"  # pallas | chunked | einsum
    cross: bool = False  # cross-attention (no rope on kv side, bidir)

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv


def attn_init(key, d_model: int, cfg: AttnCfg, dtype=jnp.float32, kv_d_model: int | None = None):
    ks = jax.random.split(key, 4)
    dh, H, Kv = cfg.d_head, cfg.n_heads, cfg.n_kv
    kvd = kv_d_model or d_model
    return {
        "q": dense_init(ks[0], d_model, H * dh, dtype),
        "k": dense_init(ks[1], kvd, Kv * dh, dtype),
        "v": dense_init(ks[2], kvd, Kv * dh, dtype),
        "o": dense_init(ks[3], H * dh, d_model, dtype, scale=(H * dh) ** -0.5),
    }


def _split_heads(x, n, dh):
    return x.reshape(x.shape[:-1] + (n, dh))


def _tile(q, k, v, scale, mask):
    """One attention tile, flat-head layout (k/v pre-repeated to H heads —
    TP-shardable on H even when n_kv < model-axis size, see DESIGN.md).

    q:[B,Cq,H,dh] k/v:[B,Ck,H,dh] mask:[Cq,Ck]|[B,Cq,Ck]|None (the batched
    form carries per-row segment/packing masks — serving prefill).
    Returns (m, l, acc): running max/denom [B,H,Cq], acc [B,Cq,H,dh].
    """
    # bf16 operands feed the MXU directly; fp32 accumulation via
    # preferred_element_type (avoids materialising fp32 copies of K/V).
    s = jnp.einsum("bqhd,bchd->bhqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        m_b = mask[None, None] if mask.ndim == 2 else mask[:, None]
        s = jnp.where(m_b, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqc,bchd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _merge(m1, l1, a1, m2, l2, a2):
    m = jnp.maximum(m1, m2)
    e1 = jnp.exp(m1 - m)
    e2 = jnp.exp(m2 - m)
    l = l1 * e1 + l2 * e2
    # acc layout [B,Cq,H,dh]; coefficients are [B,H,Cq]
    c1 = jnp.swapaxes(e1, 1, 2)[..., None]
    c2 = jnp.swapaxes(e2, 1, 2)[..., None]
    return m, l, a1 * c1 + a2 * c2


def _q_chunk_full(qi, k, v, scale, causal, qpos, kpos, kv_chunk, cost_mode,
                  kv_valid_len=None, window=None, seg_qi=None, seg_k=None):
    """All-kv attention for one query chunk via online softmax over kv tiles.

    ``seg_qi`` [B, Cq] / ``seg_k`` [B, Skv] are per-row segment ids (packed
    serving prefill): queries only attend within their own segment, and
    segment id 0 marks padding keys. When given, the tile masks become
    batched [B, Cq, Ck].
    """
    B, Cq, H, dh = qi.shape
    Skv = k.shape[1]
    ck = min(kv_chunk, Skv)
    nk = Skv // ck
    assert nk * ck == Skv

    def tile_j(j):
        kj = jax.lax.dynamic_slice_in_dim(k, j * ck, ck, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * ck, ck, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(kpos, j * ck, ck, axis=0)
        mask = None
        if causal:
            d = qpos[:, None] - kp[None, :]
            mask = d >= 0
            if window is not None:
                mask &= d < window
        if kv_valid_len is not None:
            vmask = (kp < kv_valid_len)[None, :]
            mask = vmask if mask is None else (mask & vmask)
        if seg_qi is not None:
            sk = jax.lax.dynamic_slice_in_dim(seg_k, j * ck, ck, axis=1)
            smask = (seg_qi[:, :, None] == sk[:, None, :]) & (sk[:, None, :] > 0)
            mask = smask if mask is None else (mask[None] & smask)
        return _tile(qi, kj, vj, scale, mask)

    if cost_mode:
        m, l, acc = tile_j(0)
        for j in range(1, nk):
            m, l, acc = _merge(m, l, acc, *tile_j(j))
        return m, l, acc

    def body(carry, j):
        m, l, acc = carry
        mj, lj, aj = tile_j(j)
        return _merge(m, l, acc, mj, lj, aj), None

    init = (jnp.full((B, H, Cq), -1e30, jnp.float32),
            jnp.zeros((B, H, Cq), jnp.float32),
            jnp.zeros((B, Cq, H, dh), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nk))
    return m, l, acc


def _q_chunk_window(qi, k_pad, v_pad, scale, window, i, q_chunk, qpos, cost_mode,
                    kv_valid_len=None, seg_qi=None, seg_k_pad=None):
    """Sliding-window attention for one query chunk.

    k_pad/v_pad are left-padded by ``window`` so the relevant keys for query
    chunk i live at padded offsets [i*Cq, i*Cq + window + Cq).
    ``seg_k_pad`` carries segment ids padded to the same layout (0 = pad).
    """
    Cq = qi.shape[1]
    span = window + Cq
    start = i * q_chunk
    kj = jax.lax.dynamic_slice_in_dim(k_pad, start, span, axis=1)
    vj = jax.lax.dynamic_slice_in_dim(v_pad, start, span, axis=1)
    kp = start - window + jnp.arange(span)  # original coordinates
    valid = kp >= 0
    if kv_valid_len is not None:
        valid &= kp < kv_valid_len
    d = qpos[:, None] - kp[None, :]
    mask = (d >= 0) & (d < window) & valid[None, :]
    if seg_qi is not None:
        sk = jax.lax.dynamic_slice_in_dim(seg_k_pad, start, span, axis=1)
        mask = (mask[None] & (seg_qi[:, :, None] == sk[:, None, :])
                & (sk[:, None, :] > 0))
    return _tile(qi, kj, vj, scale, mask)


def multi_head_attention(q, k, v, cfg: AttnCfg, *, cost_mode: bool = False,
                         q_offset=0, constrain=None, segs=None, sharded: bool = False):
    """q:[B,Sq,H,dh] k,v:[B,Skv,Kv,dh] -> [B,Sq,H,dh] (fp32 accum).

    GQA k/v are repeated to H heads up front (flat-head layout): the repeat is
    free per TP shard (each shard repeats only its local groups) and keeps
    every attention tensor shardable on H even when n_kv < model-axis size.
    ``constrain`` (from Ctx.constrain_heads) re-pins [B, S, H, dh] tensors to
    (dp, None, model, None).

    ``segs`` (int32 [B, Sq], self-attention only) are packed-prefill segment
    ids: tokens attend only within their own segment and id 0 marks padding
    (docs/serving.md). ``sharded``: the call runs under a device mesh
    (``Ctx.mesh``). Under ``impl="pallas"`` a call the flash kernel does not
    take (``kernels.ops.use_flash``: off a TPU, cost mode, ``segs``,
    ``sharded``, cross-attention, d_head not a multiple of 128) runs the
    chunked path.
    """
    B, Sq, H, dh = q.shape
    Kv = k.shape[2]
    G = H // Kv
    scale = dh ** -0.5

    if cfg.impl == "pallas":
        from repro.kernels import ops as kops
        if kops.use_flash(q, k, cost_mode=cost_mode, segmented=segs is not None,
                          sharded=sharded):
            o = kops.flash_attention(q, k, v, causal=cfg.causal, window=cfg.window)
            return o.astype(q.dtype)

    if G > 1:
        # Pin the GQA k/v layout on BOTH sides of the head repeat. The repeat
        # output is head-sharded (below), so SPMD wants its operand
        # head-partial too — but the operand arrives seq-sharded from the
        # sequence-parallel projections, and with n_kv < model-axis size the
        # un-annotated transition logs an `[spmd] Involuntary full
        # rematerialization` in the forward AND the remat'd backward of
        # production train cells (same failure mode as the rope.py position
        # broadcast, see ROADMAP). constrain_heads picks (dp, None,
        # model-if-divisible, None), so the small pre-repeat tensor reshards
        # voluntarily once and both directions reuse the layout.
        if constrain is not None:
            k, v = constrain(k), constrain(v)
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    if constrain is not None:
        q, k, v = constrain(q), constrain(k), constrain(v)

    if cfg.impl == "einsum":
        s = jnp.einsum("bqhd,bchd->bhqc", q, k,
                       preferred_element_type=jnp.float32) * scale
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(k.shape[1])
        mask = None
        if cfg.causal:
            mask = qpos[:, None] >= kpos[None, :]
            if cfg.window:
                mask &= (qpos[:, None] - kpos[None, :]) < cfg.window
        if segs is not None:
            smask = (segs[:, :, None] == segs[:, None, :]) & (segs[:, None, :] > 0)
            mask = smask if mask is None else (mask[None] & smask)
        if mask is not None:
            m_b = mask[None, None] if mask.ndim == 2 else mask[:, None]
            s = jnp.where(m_b, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqc,bchd->bqhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    # chunked (pads ragged Sq / Skv internally; padded queries are sliced off,
    # padded keys masked via kv_valid_len)
    if cost_mode and not (cfg.window is not None and cfg.causal):
        # HLO cost artifacts: enlarge tiles to bound unrolled-HLO size. FLOPs
        # are identical (the full path computes every masked tile at any tile
        # size); window layers keep their production chunking — the window
        # FLOP saving is shape-level and must stay visible in the artifact.
        cfg = dataclasses.replace(cfg, q_chunk=max(cfg.q_chunk, 4096),
                                  kv_chunk=max(cfg.kv_chunk, 8192))
    Cq = min(cfg.q_chunk, Sq)
    Sq_pad = ((Sq + Cq - 1) // Cq) * Cq
    Skv = k.shape[1]
    ck = min(cfg.kv_chunk, Skv)
    Skv_pad = ((Skv + ck - 1) // ck) * ck
    qg_p = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    nq = Sq_pad // Cq
    qpos_all = q_offset + jnp.arange(Sq_pad)
    kpos = jnp.arange(Skv_pad)
    kv_valid = Skv if Skv_pad != Skv else None
    use_window = cfg.window is not None and cfg.causal and Skv > (cfg.window + Cq)
    seg_q_all = seg_k_in = None
    if segs is not None:
        # 0-pad: padded queries/keys belong to no segment
        seg_q_all = jnp.pad(segs, ((0, 0), (0, Sq_pad - Sq)))
    if use_window:
        # left-pad by window; right-pad to cover padded query chunks
        right = max(0, (Sq_pad - Skv))
        k_in = jnp.pad(k, ((0, 0), (cfg.window, right), (0, 0), (0, 0)))
        v_in = jnp.pad(v, ((0, 0), (cfg.window, right), (0, 0), (0, 0)))
        if segs is not None:
            seg_k_in = jnp.pad(segs, ((0, 0), (cfg.window, right)))
    else:
        k_in = jnp.pad(k, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
        v_in = jnp.pad(v, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
        if segs is not None:
            seg_k_in = jnp.pad(segs, ((0, 0), (0, Skv_pad - Skv)))

    def one_chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(qg_p, i * Cq, Cq, axis=1)
        qpos = jax.lax.dynamic_slice_in_dim(qpos_all, i * Cq, Cq, axis=0)
        seg_qi = None
        if segs is not None:
            seg_qi = jax.lax.dynamic_slice_in_dim(seg_q_all, i * Cq, Cq, axis=1)
        if constrain is not None:
            qi = constrain(qi)
        if use_window:
            m, l, acc = _q_chunk_window(qi, k_in, v_in, scale, cfg.window, i, Cq, qpos,
                                        cost_mode, kv_valid_len=Skv,
                                        seg_qi=seg_qi, seg_k_pad=seg_k_in)
        else:
            m, l, acc = _q_chunk_full(qi, k_in, v_in, scale, cfg.causal, qpos, kpos,
                                      cfg.kv_chunk, cost_mode, kv_valid_len=kv_valid,
                                      window=cfg.window if cfg.causal else None,
                                      seg_qi=seg_qi, seg_k=seg_k_in)
        lr = jnp.swapaxes(l, 1, 2)[..., None]  # [B,Cq,H,1]
        out = (acc / jnp.maximum(lr, 1e-30)).astype(q.dtype)
        return constrain(out) if constrain is not None else out

    chunk_fn = jax.checkpoint(one_chunk)
    if cost_mode:
        outs = [chunk_fn(i) for i in range(nq)]
        o = jnp.concatenate(outs, axis=1)
    else:
        o = jax.lax.map(chunk_fn, jnp.arange(nq))  # [nq,B,Cq,H,dh]
        o = jnp.moveaxis(o, 0, 1).reshape(B, Sq_pad, H, dh)
    o = o[:, :Sq]
    return o.reshape(B, Sq, H, dh)


def decode_attention(q, k_cache, v_cache, pos, cfg: AttnCfg):
    """q:[B,1,H,dh]; caches [B,Smax,Kv,dh]; pos: index of the new token —
    a scalar (whole batch at one timestep) or an int32 [B] vector (per-slot
    positions, the continuous-batching serving path; see docs/serving.md).

    GQA via grouped einsum on the *unrepeated* cache (repeating a 32k-entry
    cache would multiply HBM reads by G — decode is memory-bound, so the
    cache is read once per kv head). Caches may be sequence-sharded; softmax
    partials combine via XLA-inserted all-reduce (flash-decoding pattern).
    """
    B, _, H, dh = q.shape
    Kv = k_cache.shape[2]
    G = H // Kv
    qg = q.reshape(B, 1, Kv, G, dh)
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    idx = jnp.arange(k_cache.shape[1])
    posv = jnp.asarray(pos)
    if posv.ndim == 0:
        posv = posv[None]  # [1] broadcasts over B
    rolling = cfg.window is not None and k_cache.shape[1] <= cfg.window
    # warm ring buffer: everything valid once pos >= size; during warmup only
    # slots <= pos have been written.
    mask = idx[None, :] <= posv[:, None]
    if cfg.window is not None and not rolling:
        mask &= idx[None, :] > posv[:, None] - cfg.window
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckh->bqkgh", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, dh).astype(q.dtype)


def init_kv_cache(batch: int, max_len: int, cfg: AttnCfg, dtype):
    size = min(max_len, cfg.window) if cfg.window is not None else max_len
    shape = (batch, size, cfg.n_kv, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@scopes.scoped(scopes.ATTN)
def attention(params, x, ctx: Ctx, cfg: AttnCfg, positions, cache=None, pos=None,
              memory=None, role_prefix: str = "attn", segs=None):
    """Full attention sublayer: projections (sketched) + core + out-proj.

    * train/prefill: ``cache=None`` (or a cache dict to fill when prefilling).
    * decode: ``cache`` + ``pos`` (scalar, or int32 [B] per-slot positions)
      -> returns (out, updated_cache).
    * cross-attention: ``memory`` = encoder output (keys/values from memory).
    * packed prefill: ``segs`` = int32 [B, S] segment ids (0 = padding);
      self-attention is segment-masked (docs/serving.md).
    """
    B, S, _ = x.shape
    rq = f"{role_prefix}_q"
    q = _split_heads(dense(params["q"], x, ctx, rq), cfg.n_heads, cfg.d_head)
    kv_src = memory if memory is not None else x
    k = _split_heads(dense(params["k"], kv_src, ctx, f"{role_prefix}_k"), cfg.n_kv, cfg.d_head)
    v = _split_heads(dense(params["v"], kv_src, ctx, f"{role_prefix}_v"), cfg.n_kv, cfg.d_head)

    if cfg.rope == "default":
        q = apply_rope(q, positions, cfg.theta, ctx=ctx)
        if memory is None:
            k = apply_rope(k, positions, cfg.theta, ctx=ctx)
    elif cfg.rope == "mrope":
        q = apply_mrope(q, positions, cfg.theta, ctx=ctx)
        if memory is None:
            k = apply_mrope(k, positions, cfg.theta, ctx=ctx)

    if cache is not None and pos is not None:
        # decode: write new kv at pos (rolling for window caches), then
        # attend. pos is a scalar or an int32 [B] per-slot position vector
        # (continuous-batching serving) — the vector form writes each row at
        # its own timestep.
        size = cache["k"].shape[1]
        posv = jnp.asarray(pos)
        write_at = posv % size if (cfg.window is not None and size <= cfg.window) else posv
        if posv.ndim == 0:
            new_k = cache["k"].at[:, write_at].set(k[:, 0].astype(cache["k"].dtype))
            new_v = cache["v"].at[:, write_at].set(v[:, 0].astype(cache["v"].dtype))
        else:
            rows = jnp.arange(B)
            new_k = cache["k"].at[rows, write_at].set(k[:, 0].astype(cache["k"].dtype))
            new_v = cache["v"].at[rows, write_at].set(v[:, 0].astype(cache["v"].dtype))
        o = decode_attention(q, new_k, new_v, pos, cfg)
        out = dense(params["o"], o.reshape(B, S, -1), ctx, f"{role_prefix}_o")
        return out, {"k": new_k, "v": new_v}

    o = multi_head_attention(q, k, v, cfg, cost_mode=ctx.cost_mode,
                             constrain=ctx.constrain_heads,
                             segs=None if memory is not None else segs,
                             sharded=ctx.mesh is not None)
    out = dense(params["o"], o.reshape(B, S, -1), ctx, f"{role_prefix}_o")
    if cache is not None:
        # prefill: fill the cache with the (possibly window-truncated) tail.
        size = cache["k"].shape[1]
        ktail = k[:, -size:].astype(cache["k"].dtype)
        vtail = v[:, -size:].astype(cache["v"].dtype)
        rolling = cfg.window is not None and size <= cfg.window
        if rolling and k.shape[1] >= size:
            # ring-buffer convention: absolute position p lives at slot p % size
            shift = k.shape[1] % size
            ktail = jnp.roll(ktail, shift, axis=1)
            vtail = jnp.roll(vtail, shift, axis=1)
        cache = {"k": jax.lax.dynamic_update_slice_in_dim(cache["k"], ktail, 0, axis=1),
                 "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vtail, 0, axis=1)}
        return out, cache
    return out
