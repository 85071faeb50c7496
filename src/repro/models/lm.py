"""Decoder-only / encoder-decoder LM assembly over heterogeneous layer stacks.

Architectures are compiled into a list of **segments**; each segment scans a
stack of identical **periods** (tuples of sub-blocks). Heterogeneous patterns
(gemma3's 5 local : 1 global, zamba2's 6 mamba : 1 shared-attention) become
homogeneous periods so `lax.scan` can stack them — the standard MaxText-style
trick that keeps HLO size O(1) in depth. ``ctx.cost_mode`` unrolls every loop
in python for scan-corrected HLO cost artifacts (see DESIGN.md / EXPERIMENTS).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ArchConfig
from repro.core import linear
from repro.nn.attention import AttnCfg, attention, attn_init, init_kv_cache
from repro.nn.common import Ctx, dense_init, rmsnorm, rmsnorm_init, trunc_normal
from repro.nn.mlp import mlp, mlp_init
from repro.nn.moe import MoECfg, moe_ffn, moe_init
from repro.nn.ssm import (MambaCfg, RWKVCfg, mamba_block, mamba_decode, mamba_init,
                          mamba_state_init, rwkv_channel_mix, rwkv_init,
                          rwkv_state_init, rwkv_time_mix)
from repro.obs import scopes

__all__ = ["LayerKind", "plan_segments", "init_params", "forward", "decode_step",
           "init_cache", "lm_loss", "num_params", "active_params_per_token"]


@dataclasses.dataclass(frozen=True)
class LayerKind:
    kind: str  # attn | mamba | rwkv | shared_attn
    window: Optional[int] = None
    moe: bool = False
    cross: bool = False  # decoder cross-attention after self-attention
    causal: bool = True
    theta: Optional[float] = None  # rope theta override (gemma3 global layers)


def _attn_cfg(cfg: ArchConfig, kind: LayerKind) -> AttnCfg:
    return AttnCfg(
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, d_head=cfg.head_dim,
        causal=kind.causal, window=kind.window, rope=cfg.rope,
        theta=kind.theta or cfg.rope_theta, q_chunk=cfg.q_chunk,
        kv_chunk=cfg.kv_chunk, impl=cfg.attn_impl)


def _cross_cfg(cfg: ArchConfig) -> AttnCfg:
    return AttnCfg(n_heads=cfg.n_heads, n_kv=cfg.n_kv, d_head=cfg.head_dim,
                   causal=False, rope="none", q_chunk=cfg.q_chunk,
                   kv_chunk=cfg.kv_chunk, impl=cfg.attn_impl, cross=True)


def _mamba_cfg(cfg: ArchConfig) -> MambaCfg:
    return MambaCfg(d_model=cfg.d_model, d_state=cfg.ssm_state,
                    head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk)


def _rwkv_cfg(cfg: ArchConfig) -> RWKVCfg:
    return RWKVCfg(d_model=cfg.d_model, head_dim=cfg.ssm_head_dim, d_ff=cfg.d_ff,
                   chunk=cfg.ssm_chunk)


def plan_segments(cfg: ArchConfig, *, encoder: bool = False):
    """Return [(period: tuple[LayerKind, ...], n_rep: int), ...]."""
    L = cfg.enc_layers if encoder else cfg.n_layers
    if encoder:
        return [((LayerKind("attn", causal=False),), L)]
    if cfg.block_kind == "rwkv":
        return [((LayerKind("rwkv"),), L)]
    if cfg.block_kind == "zamba":
        k = cfg.shared_attn_every
        period = tuple([LayerKind("mamba")] * k + [LayerKind("shared_attn")])
        n_full = L // k
        rem = L - n_full * k
        segs = [(period, n_full)] if n_full else []
        if rem:
            segs.append(((LayerKind("mamba"),), rem))
        return segs
    if cfg.local_global > 0:
        k = cfg.local_global
        local = LayerKind("attn", window=cfg.window)
        glob = LayerKind("attn", theta=cfg.rope_theta_global)
        period = tuple([local] * k + [glob])
        n_full = L // (k + 1)
        rem = L - n_full * (k + 1)
        segs = [(period, n_full)] if n_full else []
        if rem:
            segs.append(((local,), rem))
        return segs
    base = LayerKind("attn", window=cfg.window, moe=cfg.n_experts > 0,
                     cross=cfg.is_encdec)
    return [((base,), L)]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_sub(key, kind: LayerKind, cfg: ArchConfig, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {"norm1": rmsnorm_init(d, dtype)}
    if kind.kind in ("attn", "shared_attn"):
        p["attn"] = attn_init(ks[0], d, _attn_cfg(cfg, kind), dtype)
        p["norm2"] = rmsnorm_init(d, dtype)
        if kind.moe:
            p["moe"] = moe_init(ks[1], d, MoECfg(cfg.n_experts, cfg.top_k, cfg.d_ff,
                                                 cfg.capacity_factor, cfg.mlp_type), dtype)
        else:
            p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_type, dtype)
        if kind.cross:
            p["cross"] = attn_init(ks[2], d, _cross_cfg(cfg), dtype)
            p["norm_c"] = rmsnorm_init(d, dtype)
    elif kind.kind == "mamba":
        p["mamba"] = mamba_init(ks[0], _mamba_cfg(cfg), dtype)
    elif kind.kind == "rwkv":
        p["rwkv"] = rwkv_init(ks[0], _rwkv_cfg(cfg), dtype)
        p["norm2"] = rmsnorm_init(d, dtype)
    return p


def _init_segment(key, period, n_rep, cfg: ArchConfig, dtype):
    subs = []
    for i, kind in enumerate(period):
        if kind.kind == "shared_attn":
            subs.append(None)  # parameters live in params["shared"]
            continue
        keys = jax.random.split(jax.random.fold_in(key, i), n_rep)
        subs.append(jax.vmap(lambda k: _init_sub(k, kind, cfg, dtype))(keys))
    return subs


def init_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    if cfg.family == "mlp":
        from repro.models import mlp as mlpmod

        return mlpmod.mlp_init(key, mlpmod.mlp_sizes(cfg), dtype)
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    params = {
        "embed": trunc_normal(ks[0], (cfg.vocab, d), d ** -0.5, dtype),
        "final_norm": rmsnorm_init(d, dtype),
    }
    segs = plan_segments(cfg)
    params["segments"] = [
        _init_segment(jax.random.fold_in(ks[1], si), period, n_rep, cfg, dtype)
        for si, (period, n_rep) in enumerate(segs)]
    if any(k.kind == "shared_attn" for period, _ in segs for k in period):
        params["shared"] = _init_sub(ks[2], LayerKind("shared_attn"), cfg, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[3], d, cfg.vocab, dtype, scale=d ** -0.5)
    if cfg.is_encdec:
        enc_segs = plan_segments(cfg, encoder=True)
        params["encoder"] = {
            "segments": [_init_segment(jax.random.fold_in(ks[4], si), period, n_rep, cfg, dtype)
                         for si, (period, n_rep) in enumerate(enc_segs)],
            "final_norm": rmsnorm_init(d, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Sub-block application
# ---------------------------------------------------------------------------


def _apply_sub(kind: LayerKind, p, x, ctx: Ctx, cfg: ArchConfig, positions,
               memory=None, cache=None, pos=None, segs=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind.kind in ("attn", "shared_attn"):
        acfg = _attn_cfg(cfg, kind)
        h = rmsnorm(p["norm1"], x)
        if cache is not None:
            o, new_self = attention(p["attn"], h, ctx, acfg, positions,
                                    cache=cache["kv"], pos=pos, segs=segs)
        else:
            o = attention(p["attn"], h, ctx, acfg, positions, segs=segs)
            new_self = None
        x = x + o
        new_cache = {"kv": new_self} if cache is not None else None
        if kind.cross:
            hc = rmsnorm(p["norm_c"], x)
            ccfg = _cross_cfg(cfg)
            if cache is not None and pos is not None:
                # decode: reuse cached cross K/V (computed at prefill)
                from repro.nn.attention import decode_attention, _split_heads  # noqa
                from repro.nn.common import dense
                q = dense(p["cross"]["q"], hc, ctx, "cross_q").reshape(
                    hc.shape[0], hc.shape[1], ccfg.n_heads, ccfg.d_head)
                kc, vc = cache["cross"]["k"], cache["cross"]["v"]
                o = decode_attention(q, kc, vc, kc.shape[1] - 1, dataclasses.replace(ccfg, window=None))
                o = dense(p["cross"]["o"], o.reshape(hc.shape[0], hc.shape[1], -1), ctx, "cross_o")
                x = x + o
                new_cache["cross"] = cache["cross"]
            else:
                o = attention(p["cross"], hc, ctx, ccfg, positions, memory=memory,
                              role_prefix="cross")
                x = x + o
                if cache is not None:
                    # prefill: cache cross K/V from memory
                    from repro.nn.common import dense
                    kc = dense(p["cross"]["k"], memory, ctx, "cross_k").reshape(
                        memory.shape[0], memory.shape[1], ccfg.n_kv, ccfg.d_head)
                    vc = dense(p["cross"]["v"], memory, ctx, "cross_v").reshape(
                        memory.shape[0], memory.shape[1], ccfg.n_kv, ccfg.d_head)
                    new_cache["cross"] = {"k": kc.astype(x.dtype), "v": vc.astype(x.dtype)}
        h2 = rmsnorm(p["norm2"], x)
        if kind.moe:
            mcfg = MoECfg(cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.capacity_factor, cfg.mlp_type)
            o, aux = moe_ffn(p["moe"], h2, ctx, mcfg)
        else:
            o = mlp(p["mlp"], h2, ctx, cfg.mlp_type)
        return x + o, new_cache, aux

    if kind.kind == "mamba":
        mcfg = _mamba_cfg(cfg)
        h = rmsnorm(p["norm1"], x)
        if cache is not None and pos is not None:
            o, new_state = mamba_decode(p["mamba"], h, ctx, mcfg, cache)
            return x + o, new_state, aux
        o = mamba_block(p["mamba"], h, ctx, mcfg)
        new_cache = None
        if cache is not None:  # prefill: run decode-style to build state? use block + state capture
            # prefill builds state by running the chunked scan and keeping the
            # final state; redo cheaply via mamba_block internals is complex —
            # we recompute with state tracking below.
            o, new_cache = _mamba_prefill(p["mamba"], h, ctx, mcfg)
            return x + o, new_cache, aux
        return x + o, new_cache, aux

    if kind.kind == "rwkv":
        rcfg = _rwkv_cfg(cfg)
        h = rmsnorm(p["norm1"], x)
        tm_state = None
        if cache is not None:
            tm_state = {"wkv": cache["wkv"], "shift": cache["shift_tm"]}
        o, new_tm = rwkv_time_mix(p["rwkv"], h, ctx, rcfg, tm_state)
        x = x + o
        h2 = rmsnorm(p["norm2"], x)
        cm_state = cache["shift_cm"] if cache is not None else None
        o2, new_cm = rwkv_channel_mix(p["rwkv"], h2, ctx, rcfg, cm_state)
        x = x + o2
        new_cache = None
        if cache is not None:
            new_cache = {"wkv": new_tm["wkv"], "shift_tm": new_tm["shift"],
                         "shift_cm": new_cm}
        return x, new_cache, aux

    raise ValueError(kind.kind)


def _mamba_prefill(mp, h, ctx, mcfg):
    """mamba_block variant that also returns the final (ssm, conv) state."""
    from repro.nn.ssm import _mamba_pre, _ssd  # noqa: import inside to reuse internals
    Bsz, S, _ = h.shape
    H, P = mcfg.n_heads, mcfg.head_dim
    z, xs, Bc, Cc, dt, conv_tail = _mamba_pre(mp, h, ctx, mcfg, None)
    xh = xs.reshape(Bsz, S, H, P)
    A = jnp.exp(mp["A_log"])
    state0 = jnp.zeros((Bsz, H, P, mcfg.d_state), jnp.float32)
    y, state = _ssd(xh.astype(jnp.float32), dt, A, Bc.astype(jnp.float32),
                    Cc.astype(jnp.float32), mcfg, state0, ctx.cost_mode)
    y = y + mp["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(Bsz, S, mcfg.d_inner).astype(h.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(h.dtype)
    y = rmsnorm(mp["norm"], y)
    from repro.nn.common import dense
    out = dense(mp["out"], y, ctx, "ssm_out")
    return out, {"ssm": state, "conv": conv_tail}


# ---------------------------------------------------------------------------
# Segment runner
# ---------------------------------------------------------------------------


def _layer_uid(seg_base: int, rep, period_len: int, sub_i: int):
    return seg_base + rep * period_len + sub_i


@scopes.scoped(scopes.STACK)
def _run_segments(seg_params, segments, x, ctx: Ctx, cfg: ArchConfig, step_key,
                  positions, shared=None, memory=None, caches=None, pos=None,
                  seg_base: int = 0, segs=None):
    """Run all segments; returns (x, aux_total, new_caches)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    base = seg_base
    for si, (period, n_rep) in enumerate(segments):
        plen = len(period)
        subs_params = seg_params[si]
        seg_caches = caches[si] if caches is not None else None

        def one_period(x, rep, sp, sc):
            aux = jnp.zeros((), jnp.float32)
            ncs = []
            for i, kind in enumerate(period):
                uid = _layer_uid(base, rep, plen, i)
                lctx = ctx.for_layer(step_key, uid)
                p = shared if kind.kind == "shared_attn" else sp[i]
                c = sc[i] if sc is not None else None
                x, nc, a = _apply_sub(kind, p, x, lctx, cfg, positions, memory, c,
                                      pos, segs)
                # re-pin the residual stream sharding so the scan carry keeps
                # the sequence-parallel layout across iterations
                x = ctx.constrain(x)
                aux = aux + a
                ncs.append(nc)
            return x, aux, ncs

        if ctx.cost_mode:
            ncs_all = [[] for _ in period]
            for rep in range(n_rep):
                sp = [None if sub is None else jax.tree.map(lambda a: a[rep], sub)
                      for sub in subs_params]
                sc = None
                if seg_caches is not None:
                    sc = [None if c is None else jax.tree.map(lambda a: a[rep], c)
                          for c in seg_caches]
                x, aux, ncs = one_period(x, rep, sp, sc)
                aux_total = aux_total + aux
                for i, nc in enumerate(ncs):
                    ncs_all[i].append(nc)
            if seg_caches is not None:
                new_caches.append([
                    None if ncs_all[i][0] is None else jax.tree.map(
                        lambda *a: jnp.stack(a), *ncs_all[i])
                    for i in range(plen)])
            else:
                new_caches.append(None)
        else:
            # scan over the stacked reps. Caches ride in the CARRY (not xs/ys):
            # loop-carried buffers are updated in place by XLA, so decode holds
            # ONE cache stack instead of xs+ys double buffers, and per-layer
            # slices stay loop-variant (no hoisted whole-stack converts).
            scan_params = [sub for sub in subs_params if sub is not None]
            has_cache = seg_caches is not None

            def _rebuild(sp_flat):
                sp, j = [], 0
                for sub in subs_params:
                    if sub is None:
                        sp.append(None)
                    else:
                        sp.append(sp_flat[j])
                        j += 1
                return sp

            def body(carry, xs):
                x, aux, cstack = carry
                rep, sp_flat = xs
                sp = _rebuild(sp_flat)
                sc = None
                if has_cache:
                    sc = [None if c is None else jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, rep, 0, keepdims=False), c)
                        for c in cstack]
                x, a, ncs = one_period(x, rep, sp, sc)
                if has_cache:
                    cstack = [
                        old if nc is None else jax.tree.map(
                            lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                                buf, new.astype(buf.dtype), rep, 0),
                            old, nc)
                        for old, nc in zip(cstack, ncs)]
                return (x, aux + a, cstack), None

            xs = (jnp.arange(n_rep), scan_params)
            # remat only matters under differentiation; serving scans (cache in
            # carry) skip it so XLA can update cache buffers strictly in place.
            body_fn = body if (cfg.remat == "none" or has_cache) else jax.checkpoint(
                body, policy=(jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                              if cfg.remat == "dots" else None))
            (x, aux, cstack_out), _ = jax.lax.scan(
                body_fn, (x, aux_total, seg_caches if has_cache else None), xs)
            aux_total = aux
            new_caches.append(cstack_out if has_cache else None)
        base += n_rep * plen
    return x, aux_total, new_caches


# ---------------------------------------------------------------------------
# Public model API
# ---------------------------------------------------------------------------


@scopes.scoped(scopes.EMBED)
def _embed(params, tokens_or_embeds, cfg: ArchConfig):
    if jnp.issubdtype(tokens_or_embeds.dtype, jnp.integer):
        x = jnp.take(params["embed"], tokens_or_embeds, axis=0)
    else:
        x = tokens_or_embeds.astype(jnp.dtype(cfg.param_dtype))
    x = x.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _head(params, x, ctx: Ctx, cfg: ArchConfig):
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]["w"]
    hcfg = ctx.cfg_for("lm_head")
    if getattr(ctx, "tp_sketch", False) and hcfg is None and ctx.mesh is not None             and not cfg.tie_embeddings:
        n_mp = 1
        for a in ctx.model_axes:
            n_mp *= ctx.mesh.shape[a]
        if w.shape[0] % n_mp == 0:
            from repro.core.sharded_sketch import tp_exact_linear

            return tp_exact_linear(x, w, ctx)
    return linear(x, w, key=ctx.site_key("lm_head"), cfg=hcfg)


def _default_positions(cfg: ArchConfig, B, S, offset=0):
    # offset: scalar, or int32 [B] per-row start positions (serving decode)
    pos = jnp.asarray(offset)[..., None] + jnp.arange(S)[None, :]
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def encode(params, src_embeds, ctx: Ctx, cfg: ArchConfig, step_key=None):
    """Encoder stack (enc-dec archs). src_embeds: [B, S_enc, d] (stub frontend)."""
    enc = params["encoder"]
    segs = plan_segments(cfg, encoder=True)
    B, S, _ = src_embeds.shape
    positions = _default_positions(cfg, B, S)
    x = ctx.constrain(src_embeds.astype(jnp.dtype(cfg.dtype)))
    x, _, _ = _run_segments(enc["segments"], segs, x, ctx, cfg, step_key,
                            positions, seg_base=10_000)
    return rmsnorm(enc["final_norm"], x)


def forward(params, batch, ctx: Ctx, cfg: ArchConfig, step_key=None):
    """Training / scoring forward. Returns (logits, aux).

    batch: {"tokens": int[B,S]} or {"embeds": f32[B,S,d]} (+ optional
    "positions", "src_embeds" for enc-dec).
    """
    inp = batch.get("tokens", batch.get("embeds"))
    B, S = inp.shape[0], inp.shape[1]
    x = ctx.constrain(_embed(params, inp, cfg))
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    memory = None
    if cfg.is_encdec:
        memory = encode(params, batch["src_embeds"], ctx, cfg, step_key)
    segs = plan_segments(cfg)
    x, aux, _ = _run_segments(params["segments"], segs, x, ctx, cfg, step_key,
                              positions, shared=params.get("shared"), memory=memory,
                              segs=batch.get("segments"))
    with compat.named_scope(scopes.HEAD):
        x = rmsnorm(params["final_norm"], x)
        logits = _head(params, x, ctx, cfg)
    return logits, aux


def init_cache(cfg: ArchConfig, batch: int, max_len: int, enc_len: int = 0):
    """Decode caches for every segment/position (stacked over reps)."""
    dtype = jnp.dtype(cfg.dtype)
    segs = plan_segments(cfg)
    caches = []
    for period, n_rep in segs:
        seg = []
        for kind in period:
            if kind.kind in ("attn", "shared_attn"):
                acfg = _attn_cfg(cfg, kind)
                c = {"kv": init_kv_cache(batch, max_len, acfg, dtype)}
                if kind.cross:
                    c["cross"] = {"k": jnp.zeros((batch, enc_len, acfg.n_kv, acfg.d_head), dtype),
                                  "v": jnp.zeros((batch, enc_len, acfg.n_kv, acfg.d_head), dtype)}
                seg.append(jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), c))
            elif kind.kind == "mamba":
                st = mamba_state_init(batch, _mamba_cfg(cfg), dtype)
                seg.append(jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), st))
            elif kind.kind == "rwkv":
                st = rwkv_state_init(batch, _rwkv_cfg(cfg), dtype)
                seg.append(jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), st))
        caches.append(seg)
    return caches


def decode_step(params, caches, tokens, pos, ctx: Ctx, cfg: ArchConfig, step_key=None):
    """One decode step. tokens: int[B, 1] (or embeds [B,1,d]); pos: scalar, or
    an int32 [B] per-slot position vector (continuous-batching serving — each
    row writes/attends at its own timestep; see docs/serving.md).

    Returns (logits [B,1,V], new_caches).
    """
    B = tokens.shape[0]
    x = _embed(params, tokens, cfg)
    positions = _default_positions(cfg, B, 1, offset=pos)
    segs = plan_segments(cfg)
    x, _, new_caches = _run_segments(params["segments"], segs, x, ctx, cfg, step_key,
                                     positions, shared=params.get("shared"),
                                     caches=caches, pos=pos)
    x = rmsnorm(params["final_norm"], x)
    return _head(params, x, ctx, cfg), new_caches


def prefill(params, batch, ctx: Ctx, cfg: ArchConfig, max_len: int, step_key=None):
    """Prefill: forward + populate caches. Returns (logits, caches).

    Optional ``batch["segments"]`` (int32 [B,S], 0 = padding) segment-masks
    self-attention so several packed prompts share one prefill call.
    """
    inp = batch.get("tokens", batch.get("embeds"))
    B, S = inp.shape[0], inp.shape[1]
    x = _embed(params, inp, cfg)
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    memory = None
    if cfg.is_encdec:
        memory = encode(params, batch["src_embeds"], ctx, cfg, step_key)
    segs = plan_segments(cfg)
    caches = init_cache(cfg, B, max_len, enc_len=memory.shape[1] if memory is not None else 0)
    x, _, new_caches = _run_segments(params["segments"], segs, x, ctx, cfg, step_key,
                                     positions, shared=params.get("shared"),
                                     memory=memory, caches=caches, pos=None,
                                     segs=batch.get("segments"))
    x = rmsnorm(params["final_norm"], x)
    return _head(params, x, ctx, cfg), new_caches


def lm_loss(params, batch, ctx: Ctx, cfg: ArchConfig, step_key=None):
    """Next-token cross-entropy (vocab-shard friendly masked reduce).

    Returns (loss, metrics dict).

    ``family="mlp"`` configs (:func:`repro.models.mlp.mlp_arch`) dispatch to
    the §5 classification MLP instead — batch is ``{"x", "y"}`` and the
    metrics gain ``acc`` — so the one trainer/checkpoint/resilience stack
    drives both model families.
    """
    if cfg.family == "mlp":
        from repro.models import mlp as mlpmod

        loss, acc = mlpmod.mlp_loss(params, batch, ctx)
        return loss, {"loss": loss, "acc": acc, "nll": loss}
    logits, aux = forward(params, batch, ctx, cfg, step_key)
    labels = batch["labels"]
    with compat.named_scope(scopes.HEAD):
        lg32 = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lg32, axis=-1)
        V = lg32.shape[-1]
        iota = jax.lax.broadcasted_iota(jnp.int32, lg32.shape, len(lg32.shape) - 1)
        true_logit = jnp.sum(jnp.where(iota == labels[..., None], lg32, 0.0), axis=-1)
        nll = lse - true_logit
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(nll)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + aux
    return total, {"loss": loss, "aux": aux, "nll": loss}


def num_params(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


def active_params_per_token(params, cfg: ArchConfig) -> int:
    """Active parameter count (MoE: only top_k of n_experts per token)."""
    total = num_params(params)
    if cfg.n_experts == 0:
        return total

    def expert_leaves(p):
        n = 0
        for seg in p["segments"]:
            for sub in seg:
                if sub is None:
                    continue
                moe = sub.get("moe") if isinstance(sub, dict) else None
                if moe:
                    for k in ("wi", "wo", "wg"):
                        if k in moe:
                            n += moe[k].size
        return n

    e_total = expert_leaves(params)
    active = total - e_total + int(e_total * cfg.top_k / cfg.n_experts)
    return active
