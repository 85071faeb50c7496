"""Mixture-of-Experts FFN: token-choice top-k, capacity-bucketed, EP-shardable.

Dispatch is sort-based (no N×E×C one-hot tensors): token replicas are ranked
within their expert via a stable argsort, bucketed into ``[E_local, C, d]``
buffers, processed by a vmapped (sketched) GLU FFN, and combined back with the
router weights.

Two execution modes share the same body:
  * local  — single device / pjit-auto sharding (tests, smoke).
  * EP     — ``shard_map`` (via repro.compat) over the mesh: activations are sharded over the
             data axes and *replicated* over ``model``; experts are sharded
             over ``model``; each model shard processes its own experts for
             the whole local batch and the outputs are ``psum``-combined over
             ``model`` (GShard-style expert parallelism without all-to-all —
             the combine all-reduce plays the role the dense TP all-reduce
             would play for a dense FFN of the same width).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro import compat
from repro.nn.common import Ctx, dense_init
from repro.core import linear
from repro.obs import scopes

__all__ = ["MoECfg", "moe_init", "moe_ffn"]


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25
    mlp_type: str = "swiglu"
    aux_coef: float = 0.01


def moe_init(key, d_model: int, cfg: MoECfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    E, F = cfg.n_experts, cfg.d_ff
    p = {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "wi": jax.vmap(lambda k: dense_init(k, d_model, F, dtype)["w"])(jax.random.split(ks[1], E)),
        "wo": jax.vmap(lambda k: dense_init(k, F, d_model, dtype, scale=F ** -0.5)["w"])(jax.random.split(ks[2], E)),
    }
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["wg"] = jax.vmap(lambda k: dense_init(k, d_model, F, dtype)["w"])(jax.random.split(ks[3], E))
    return p


def _expert_ffn(wi, wg, wo, xb, ctx: Ctx, cfg: MoECfg, key):
    """One expert's FFN on its [C, d] bucket (sketched linears)."""
    kcfg_in = ctx.cfg_for("expert_in")
    kcfg_gate = ctx.cfg_for("expert_gate")
    kcfg_out = ctx.cfg_for("expert_out")
    k_in = k_gate = k_out = None
    if key is not None:
        k_in, k_gate, k_out = jax.random.split(key, 3)
    h = linear(xb, wi, key=k_in, cfg=kcfg_in)
    if wg is not None:
        g = linear(xb, wg, key=k_gate, cfg=kcfg_gate)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return linear(h, wo, key=k_out, cfg=kcfg_out)


def _moe_local(router_w, wi, wg, wo, x2d, ctx: Ctx, cfg: MoECfg, e_offset: int,
               n_total_experts: int, capacity: int):
    """Dispatch + expert compute + combine over the experts in wi/wo.

    x2d: [N, d]; wi: [E_loc, F, d] (d_out-major like all our dense weights).
    Returns (y2d [N, d], aux_stats dict).
    """
    N, d = x2d.shape
    E_loc = wi.shape[0]
    k = cfg.top_k
    logits = (x2d.astype(jnp.float32) @ router_w.T.astype(jnp.float32))  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)  # [N, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)  # renorm (Mixtral)

    flat_ids = top_ids.reshape(-1)  # [N*k]
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(N), k)

    # rank of each replica within its expert (stable sort ⇒ FIFO capacity)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_total_experts))
    ranks_sorted = jnp.arange(N * k) - starts[sorted_ids]
    ranks = jnp.zeros((N * k,), jnp.int32).at[order].set(ranks_sorted.astype(jnp.int32))

    local_e = flat_ids - e_offset
    keep = (local_e >= 0) & (local_e < E_loc) & (ranks < capacity)
    slot = jnp.where(keep, local_e * capacity + ranks, E_loc * capacity)  # overflow slot

    buf = jnp.zeros((E_loc * capacity + 1, d), x2d.dtype)
    buf = buf.at[slot].add(jnp.take(x2d, flat_tok, axis=0))
    xe = buf[:-1].reshape(E_loc, capacity, d)

    ekeys = None
    if ctx.key is not None:
        ekeys = jax.random.split(jax.random.fold_in(ctx.key, 1000), E_loc)
    if wg is None:
        fn = lambda wi_e, wo_e, xb, kk: _expert_ffn(wi_e, None, wo_e, xb, ctx, cfg, kk)
        ye = jax.vmap(fn)(wi, wo, xe, ekeys) if ekeys is not None else jax.vmap(
            lambda a, b, c: fn(a, b, c, None))(wi, wo, xe)
    else:
        fn = lambda wi_e, wg_e, wo_e, xb, kk: _expert_ffn(wi_e, wg_e, wo_e, xb, ctx, cfg, kk)
        ye = jax.vmap(fn)(wi, wg, wo, xe, ekeys) if ekeys is not None else jax.vmap(
            lambda a, b, c, e: fn(a, b, c, e, None))(wi, wg, wo, xe)

    ye_flat = jnp.concatenate([ye.reshape(E_loc * capacity, d),
                               jnp.zeros((1, d), ye.dtype)], axis=0)
    rows = jnp.take(ye_flat, slot, axis=0) * jnp.where(keep, flat_w, 0.0)[:, None].astype(ye.dtype)
    y = jnp.zeros((N, d), ye.dtype).at[flat_tok].add(rows)

    # Switch-style load-balance stats (fractions over *all* experts).
    me = jnp.mean(probs, axis=0)  # [E] mean router prob
    disp = jnp.zeros((n_total_experts,), jnp.float32).at[flat_ids].add(1.0) / (N * k)
    return y, {"me": me, "disp": disp}


@scopes.scoped(scopes.FFN)
def moe_ffn(params, x, ctx: Ctx, cfg: MoECfg):
    """x: [B, S, d] -> (y, aux_loss scalar)."""
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    N = x2d.shape[0]
    E = cfg.n_experts
    wg = params.get("wg")

    if ctx.mesh is None:
        capacity = max(1, -(-int(N * cfg.top_k * cfg.capacity_factor) // E))
        y2d, stats = _moe_local(params["router"]["w"], params["wi"], wg, params["wo"],
                                x2d, ctx, cfg, 0, E, capacity)
        aux = E * jnp.sum(stats["me"] * stats["disp"]) * cfg.aux_coef
        return y2d.reshape(B, S, d), aux

    # shard_map parallel MoE: tokens sharded over data axes. Two expert modes:
    #   EP  (E % n_mp == 0): experts partitioned over the model axis; each
    #       shard runs full FFNs for its experts, outputs psum-combined.
    #   TPX (E % n_mp != 0, e.g. Mixtral's 8e on a 16-wide axis): every shard
    #       holds all experts but a 1/n_mp slice of the expert *hidden* dim —
    #       Megatron-style tensor parallel experts; same psum combine.
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    dp = ctx.data_axes
    mp = ctx.model_axes
    assert len(mp) == 1, "expert parallelism uses a single model axis"
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_mp = mesh.shape[mp[0]]
    ep_mode = E % n_mp == 0
    if not ep_mode:
        assert cfg.d_ff % n_mp == 0, (
            f"neither experts ({E}) nor expert d_ff ({cfg.d_ff}) divide the "
            f"model axis ({n_mp})")
    rows_divide = N % n_dp == 0
    if not rows_divide:
        dp = ()  # tiny batches (e.g. B=1 decode): replicate tokens over data
        n_dp = 1
    N_loc = N // n_dp
    capacity = max(1, -(-int(N_loc * cfg.top_k * cfg.capacity_factor) // E))
    has_gate = wg is not None
    has_key = ctx.key is not None

    def body(router_w, wi_l, wg_l, wo_l, x_loc, key):
        e_off = (jax.lax.axis_index(mp[0]) * (E // n_mp)) if ep_mode else 0
        body_ctx = dataclasses.replace(ctx, mesh=None, key=key if has_key else None)
        y_loc, stats = _moe_local(router_w, wi_l, wg_l if has_gate else None, wo_l,
                                  x_loc, body_ctx, cfg, e_off, E, capacity)
        y_loc = jax.lax.psum(y_loc, mp)
        # dispatch stats cover ALL experts on every shard (global expert ids)
        me = jax.lax.pmean(stats["me"], dp) if dp else stats["me"]
        disp = jax.lax.pmean(stats["disp"], dp) if dp else stats["disp"]
        return y_loc, me, disp

    if ep_mode:
        wi_spec = P(mp[0], None, None)
        wo_spec = P(mp[0], None, None)
        wg_spec = P(mp[0], None, None)
    else:
        wi_spec = P(None, mp[0], None)  # [E, F, d] -> shard F
        wo_spec = P(None, None, mp[0])  # [E, d, F] -> shard F
        wg_spec = P(None, mp[0], None)

    key_arg = ctx.key if has_key else compat.prng_key(0)
    f = compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), wi_spec, wg_spec if has_gate else P(),
                  wo_spec, P(dp, None), P()),
        out_specs=(P(dp, None), P(), P()))
    wg_arg = wg if has_gate else jnp.zeros((), x.dtype)
    y2d, me, disp = f(params["router"]["w"], params["wi"], wg_arg, params["wo"], x2d, key_arg)
    aux = E * jnp.sum(me * disp) * cfg.aux_coef
    return y2d.reshape(B, S, d), aux
