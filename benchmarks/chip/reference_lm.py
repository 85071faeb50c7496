"""Plain float32 reference for decoder-only LMs of the Llama family, dense
or with a top-k mixture of experts, trained by AdamW.

Written from the configuration file's keys (Hugging Face names) and the
published descriptions; it imports nothing of the program. For the harness
it also names each weight's leaf in the program's parameter tree
(``program_paths``) and counts the model's FLOPs a token
(``model_flops_per_token``). Every matmul
runs at ``precision=HIGHEST``. Parameters are stored in the configuration's
``torch_dtype`` between steps, as the configuration states, and computed
in float32.

With a sketch estimator the backward of every sketched linear site follows
the paper's block-l1 column sketch given the site's seeded draw: column
scores s_j = sum_rows |G[:, j]|, block weights w_b = sum_{j in b} s_j^2,
water-filled probabilities p (sum p = r, p <= 1), systematic sampling of r
blocks from one uniform, and kept blocks rescaled by 1/p. The key that
draws the uniform is derived as the estimator documents it: the trainer's
key for the seed, folded with the step number + 1, the layer index and the
site's role id (experts: key 1000, split per expert, split three ways).

``precision="fp8"`` is the control: every matmul operand, forward and
backward, is rounded to float8 e4m3 with one scale per tensor (the head's
weight: one per eighth of its rows, as the log-sum-exp takes them).

Memory: the model runs layer by layer (one layer's activations and
gradient at a time, attention one key/value head at a time), and the AdamW
moments live on the host between steps, so that the reference fits on one
chip next to nothing else.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import cost

HIGHEST = jax.lax.Precision.HIGHEST
# role ids of the sketched sites, in the order the estimator numbers them
ROLE_ID = {"attn_q": 0, "attn_k": 1, "attn_v": 2, "attn_o": 3,
           "mlp_in": 4, "mlp_gate": 5, "mlp_out": 6}
EXPERT_KEY = 1000
F8_MAX = 448.0
# the weight whose gradient the planted ``answer`` fault doubles
FAULT_LEAF = "attn_o"
# weight name -> path of the program's parameter leaf (the layer stack is
# segment 0, sub-block 0: every layer of these models alike)
PROGRAM_PATHS = {
    "embed": ("embed",),
    "final_norm": ("final_norm", "g"),
    "lm_head": ("lm_head", "w"),
    "norm1": ("segments", 0, 0, "norm1", "g"),
    "norm2": ("segments", 0, 0, "norm2", "g"),
    "attn_q": ("segments", 0, 0, "attn", "q", "w"),
    "attn_k": ("segments", 0, 0, "attn", "k", "w"),
    "attn_v": ("segments", 0, 0, "attn", "v", "w"),
    "attn_o": ("segments", 0, 0, "attn", "o", "w"),
    "mlp_in": ("segments", 0, 0, "mlp", "in", "w"),
    "mlp_gate": ("segments", 0, 0, "mlp", "gate", "w"),
    "mlp_out": ("segments", 0, 0, "mlp", "out", "w"),
    "router": ("segments", 0, 0, "moe", "router", "w"),
    "expert_in": ("segments", 0, 0, "moe", "wi"),
    "expert_gate": ("segments", 0, 0, "moe", "wg"),
    "expert_out": ("segments", 0, 0, "moe", "wo"),
}


def run_value(c: dict, key: str, default=None):
    """A configuration key as run: where the program departs from the
    published value, the file's ``departures`` give the value it runs."""
    d = c.get("departures", {}).get(key)
    return d["run"] if d is not None else c.get(key, default)


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    n_layers: int
    theta: float
    eps: float
    dtype: str
    n_experts: int = 0
    top_k: int = 0
    norm_topk: bool = False
    aux_coef: float = 0.0
    capacity_factor: float = 0.0

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        heads = c["num_attention_heads"]
        return cls(d=c["hidden_size"], n_heads=heads,
                   n_kv=c.get("num_key_value_heads", heads),
                   d_head=c.get("head_dim", c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   n_layers=c["num_hidden_layers"], theta=c["rope_theta"],
                   eps=run_value(c, "rms_norm_eps"), dtype=c["torch_dtype"],
                   n_experts=c.get("num_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   norm_topk=run_value(c, "norm_topk_prob", False),
                   aux_coef=c.get("router_aux_loss_coef", 0.0),
                   capacity_factor=c.get("program", {}).get("capacity_factor", 0.0))


@dataclasses.dataclass(frozen=True)
class Sketch:
    method: str
    budget: float
    block: int

    @classmethod
    def from_traffic(cls, est: Optional[dict]) -> Optional["Sketch"]:
        if est is None:
            return None
        if est["method"] != "l1":
            raise ValueError(f"reference has no {est['method']!r} sketch")
        return cls(est["method"], float(est["budget"]), int(est.get("block", 0)))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_specs(m: Model) -> dict:
    """name -> (shape, dtype, std). Layer leaves are stacked over layers."""
    L, d, F, dh = m.n_layers, m.d, m.d_ff, m.d_head
    dt = m.dtype
    s = {"embed": ((m.vocab, d), dt, d ** -0.5),
         "final_norm": ((d,), dt, None),
         "lm_head": ((m.vocab, d), dt, d ** -0.5),
         "norm1": ((L, d), dt, None), "norm2": ((L, d), dt, None),
         "attn_q": ((L, m.n_heads * dh, d), dt, d ** -0.5),
         "attn_k": ((L, m.n_kv * dh, d), dt, d ** -0.5),
         "attn_v": ((L, m.n_kv * dh, d), dt, d ** -0.5),
         "attn_o": ((L, d, m.n_heads * dh), dt, (m.n_heads * dh) ** -0.5)}
    if m.n_experts:
        E = m.n_experts
        s.update({"router": ((L, E, d), "float32", d ** -0.5),
                  "expert_in": ((L, E, F, d), dt, d ** -0.5),
                  "expert_gate": ((L, E, F, d), dt, d ** -0.5),
                  "expert_out": ((L, E, d, F), dt, F ** -0.5)})
    else:
        s.update({"mlp_in": ((L, F, d), dt, d ** -0.5),
                  "mlp_gate": ((L, F, d), dt, d ** -0.5),
                  "mlp_out": ((L, d, F), dt, F ** -0.5)})
    return s


def program_paths(m: Model) -> dict:
    """name -> path of the program's leaf, for every weight of ``m``."""
    return {n: PROGRAM_PATHS[n] for n in weight_specs(m)}


def model_flops_per_token(c: dict, seq: int) -> float:
    """The exact model's training FLOPs per token: 6 per matmul parameter
    (every projection, the routed experts and the router, the head; not
    the embedding lookup) plus causal attention over the mean context."""
    return 6 * cost.matmul_params_per_token(c) + cost.attention_flops_per_token(c, seq)


def weight_key(seed: int):
    """The key the weights of ``seed`` are drawn from (a device value, so
    that the programs that draw them are the same for every seed)."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(words), 0x5EED)


def make_leaf(wkey, name: str, spec):
    """One weight leaf; independent of every other leaf."""
    shape, dtype, std = spec
    if std is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(wkey, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_weights(wkey, specs: dict) -> dict:
    return {n: make_leaf(wkey, n, s) for n, s in specs.items()}


# ---------------------------------------------------------------------------
# matmuls: float32 at HIGHEST, or the float8 control
# ---------------------------------------------------------------------------


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _q_operand(x):  # forward rounds, backward passes the cotangent through
    return _q8(x)


_q_operand.defvjp(lambda x: (_q8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_cotangent(y):  # forward identity, backward rounds the cotangent
    return y


_q_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_q8(g),))


def ein(spec: str, a, b, prec: str):
    if prec == "fp8":
        return _q_cotangent(jnp.einsum(spec, _q_operand(a), _q_operand(b),
                                       precision=HIGHEST))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# the sketch
# ---------------------------------------------------------------------------


def water_fill(w, r: int):
    """p = min(1, sqrt(w)/lam) with sum(p) = r: the minimiser of
    sum w/p under sum p <= r, 0 < p <= 1 (with a 1e-12 relative floor on
    w, so every coordinate keeps a positive probability)."""
    n = w.shape[0]
    w = jnp.maximum(w.astype(jnp.float32), 0.0)
    mw = jnp.mean(w)
    w = jnp.where(mw > 0, w + 1e-12 * mw, jnp.ones_like(w))
    t = jnp.sqrt(w)
    ts = jnp.sort(t)[::-1]
    tail = jnp.cumsum(ts[::-1])[::-1]           # sum of ts[k:]
    k = jnp.arange(n)
    lam = tail / jnp.maximum(r - k, 1).astype(jnp.float32)
    # k saturated entries: valid when the (k+1)-th largest is below the level
    ok = (k < r) & (ts <= lam)
    lam_star = lam[jnp.argmax(ok)]
    p = jnp.minimum(1.0, t / lam_star)
    # the saturated set can grow once clipped; settle the level exactly
    for _ in range(4):
        sat = p >= 1.0
        rest = jnp.sum(jnp.where(sat, 0.0, t))
        lam_star = rest / jnp.maximum(r - jnp.sum(sat), 1)
        p = jnp.where(sat, 1.0, jnp.minimum(1.0, t / lam_star))
    return p


def sketch_plan(sk: Sketch, G, key):
    """(kept block ids [r], 1/p [r], block size) for output gradient G [N, n]."""
    n = G.shape[1]
    bs = sk.block if (sk.block > 1 and n % sk.block == 0 and n >= sk.block) else 1
    nb = n // bs
    r = max(1, min(nb, int(round(sk.budget * nb))))
    s = jnp.sum(jnp.abs(G), axis=0)
    w = jnp.sum(jnp.square(s).reshape(nb, bs), axis=1)
    if r >= nb:
        return jnp.arange(nb), jnp.ones((nb,), jnp.float32), bs
    p = water_fill(w, r)
    cum = jnp.cumsum(p).at[-1].set(float(r))
    u = jax.random.uniform(key, (), minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
    idx = jnp.clip(jnp.searchsorted(cum, u + jnp.arange(r, dtype=jnp.float32),
                                    side="left"), 0, nb - 1)
    return idx, 1.0 / p[idx], bs


def sketch_g(sk: Sketch, G, key):
    """The unbiased surrogate: kept column blocks times 1/p, the rest 0."""
    idx, inv_p, bs = sketch_plan(sk, G, key)
    nb = G.shape[1] // bs
    gate = jnp.zeros((nb,), jnp.float32).at[idx].set(inv_p)
    return G * jnp.repeat(gate, bs)[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sketched(x, w, key, sk, prec):
    return ein("ni,oi->no", x, w, prec)


def _sk_fwd(x, w, key, sk, prec):
    return ein("ni,oi->no", x, w, prec), (x, w, key)


def _sk_bwd(sk, prec, res, g):
    x, w, key = res
    gh = sketch_g(sk, g, key)
    return ein("no,oi->ni", gh, w, prec), ein("no,ni->oi", gh, x, prec), None


_sketched.defvjp(_sk_fwd, _sk_bwd)


def linear(x, w, key, sk: Optional[Sketch], prec: str):
    """y = x W^T for x [N, in], W [out, in]; sketched backward if ``sk``."""
    if sk is None:
        return ein("ni,oi->no", x, w, prec)
    return _sketched(x, w, key, sk, prec)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [S, H, dh]; rotate-half pairs (i, i + dh/2), positions 0..S-1."""
    S, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_attention(q, k, v, prec):
    """q [S, H, dh], k/v [S, Kv, dh]: softmax(q k^T / sqrt(dh)) v, causal,
    query head h reading key/value head h // (H / Kv); one query head at a
    time, recomputed in the backward."""
    S, H, dh = q.shape
    kv_of = jnp.arange(H) // (H // k.shape[1])
    mask = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args
        s = ein("qd,kd->qk", qh, kh, prec) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ein("qk,kd->qd", p, vh, prec)

    o = jax.lax.map(one, (q.transpose(1, 0, 2), k[:, kv_of].transpose(1, 0, 2),
                          v[:, kv_of].transpose(1, 0, 2)))          # [H, S, dh]
    return o.transpose(1, 0, 2).reshape(S, H * dh)


def swiglu(x, wi, wg, wo, keys, sk, prec):
    h = linear(x, wi, keys[0], sk, prec)
    g = linear(x, wg, keys[1], sk, prec)
    return linear(jax.nn.silu(g) * h, wo, keys[2], sk, prec)


def moe(m: Model, p, x, layer_key, sk, prec):
    """Top-k routing over all experts, experts filled in token order up to
    their capacity (later tokens dropped), weighted sum of the kept
    outputs; plus the load-balancing loss."""
    N, d = x.shape
    E, k = m.n_experts, m.top_k
    cap = max(1, -(-int(N * k * m.capacity_factor) // E))
    probs = jax.nn.softmax(ein("nd,ed->ne", x, p["router"], prec), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if m.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    e_flat = top_e.reshape(-1)                                  # token-major
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    keep = rank < cap
    slot = jnp.where(keep, e_flat * cap + rank, E * cap)
    tok = jnp.repeat(jnp.arange(N), k)
    xe = jnp.zeros((E * cap + 1, d), x.dtype).at[slot].set(x[tok])
    xe = xe[:-1].reshape(E, cap, d)
    if layer_key is None:
        ekeys = [None] * 3
        ye = jax.vmap(lambda wi, wg, wo, xb: swiglu(xb, wi, wg, wo, ekeys, sk, prec))(
            p["expert_in"], p["expert_gate"], p["expert_out"], xe)
    else:
        ek = jax.random.split(jax.random.fold_in(layer_key, EXPERT_KEY), E)
        ye = jax.vmap(lambda wi, wg, wo, xb, kk: swiglu(
            xb, wi, wg, wo, jax.random.split(kk, 3), sk, prec))(
            p["expert_in"], p["expert_gate"], p["expert_out"], xe, ek)
    ye = jnp.concatenate([ye.reshape(E * cap, d), jnp.zeros((1, d), ye.dtype)])
    w = jnp.where(keep, top_w.reshape(-1), 0.0)
    y = jnp.zeros((N, d), x.dtype).at[tok].add(ye[slot] * w[:, None])
    share = jnp.zeros((E,), jnp.float32).at[e_flat].add(1.0) / (N * k)
    aux = m.aux_coef * E * jnp.sum(jnp.mean(probs, axis=0) * jax.lax.stop_gradient(share))
    return y, aux


def layer(m: Model, p, x, layer_key, sk, prec):
    """One block on x [B, S, d]: (x_out, aux loss)."""
    B, S, d = x.shape
    site = (lambda r: None) if layer_key is None else (
        lambda r: jax.random.fold_in(layer_key, ROLE_ID[r]))
    h = rmsnorm(x, p["norm1"], m.eps).reshape(B * S, d)
    heads = lambda r, n: linear(h, p[r], site(r), sk, prec).reshape(B, S, n, m.d_head)
    q, kk, v = heads("attn_q", m.n_heads), heads("attn_k", m.n_kv), heads("attn_v", m.n_kv)
    a = jax.vmap(lambda q_, k_, v_: causal_attention(
        rope(q_, m.theta), rope(k_, m.theta), v_, prec))(q, kk, v)
    x = x + linear(a.reshape(B * S, -1), p["attn_o"], site("attn_o"), sk,
                   prec).reshape(B, S, d)
    h = rmsnorm(x, p["norm2"], m.eps).reshape(B * S, d)
    if m.n_experts:
        y, aux = moe(m, p, h, layer_key, sk, prec)
    else:
        y = swiglu(h, p["mlp_in"], p["mlp_gate"], p["mlp_out"],
                   [site("mlp_in"), site("mlp_gate"), site("mlp_out")], sk, prec)
        aux = jnp.zeros((), jnp.float32)
    return x + y.reshape(B, S, d), aux


def head_loss(m: Model, g, w, x, labels, prec, chunks: int = 8):
    """Mean next-token cross-entropy over every position of x [B, S, d];
    the log-sum-exp over the vocabulary an eighth of its rows at a time
    (recomputed in the backward), so no full logits exist."""
    h = rmsnorm(x, g, m.eps).reshape(-1, x.shape[-1])
    lab = labels.reshape(-1)

    @jax.checkpoint
    def part(wc):
        return jax.nn.logsumexp(ein("nd,vd->nv", h, wc, prec), axis=-1)

    lse = jax.nn.logsumexp(jax.lax.map(part, w.reshape(chunks, -1, w.shape[-1])), axis=0)
    true = jnp.sum(ein("nd,nd->nd", h, w[lab], prec), axis=-1)
    return jnp.mean(lse - true)


# ---------------------------------------------------------------------------
# one training step, layer by layer
# ---------------------------------------------------------------------------


def layer_names(m: Model):
    base = ["norm1", "norm2", "attn_q", "attn_k", "attn_v", "attn_o"]
    return base + (["router", "expert_in", "expert_gate", "expert_out"]
                   if m.n_experts else ["mlp_in", "mlp_gate", "mlp_out"])


class Reference:
    """Trains the reference from given weights; reports what is compared.

    Each step runs forward and backward twice, layer by layer: the first
    pass only sums the squared gradient norms (AdamW's global-norm clip
    needs them before any update), the second updates every leaf as soon
    as its gradient exists. The moments stay on the device; no step ever
    holds more than one layer's gradient."""

    def __init__(self, m: Model, sk: Optional[Sketch], opt: dict, prec: str = "f32"):
        self.m, self.sk, self.opt, self.prec = m, sk, opt, prec
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        # gradients are taken with respect to float32 copies of the stored
        # parameters, so they come out in float32
        def fwd(p, x, lkey):
            return layer(m, f32(p), x, lkey, sk, prec)

        def vjp(p, x, lkey, dy):
            _, back = jax.vjp(lambda p_, x_: layer(m, p_, x_, lkey, sk, prec), f32(p), x)
            return back((dy, jnp.ones((), jnp.float32)))

        def head(g, w, x, labels):
            return jax.value_and_grad(
                lambda g_, w_, x_: head_loss(m, g_, w_, x_, labels, prec),
                argnums=(0, 1, 2))(f32(g), f32(w), x)

        def embed_grad(t, dx):
            return jnp.zeros((m.vocab, dx.shape[-1]), jnp.float32).at[
                t.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))

        self._fwd = jax.jit(fwd)
        self._vjp = jax.jit(vjp)
        self._head = jax.jit(head)
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._embed_grad = jax.jit(embed_grad)
        self._sq = jax.jit(lambda t: jax.tree.map(lambda a: jnp.sum(jnp.square(a)), t))
        self._adam = jax.jit(self._adam_leaf, static_argnums=(6,), donate_argnums=(0, 2, 3))
        self._adam_at = jax.jit(self._adam_slice, static_argnums=(7,),
                                donate_argnums=(0, 2, 3))

    def _adam_leaf(self, p, g, mom, var, t, scale, decay):
        o = self.opt
        b1, b2 = o["b1"], o["b2"]
        g = g * scale
        mom = b1 * mom + (1 - b1) * g
        var = b2 * var + (1 - b2) * g * g
        upd = (mom / (1 - b1 ** t)) / (jnp.sqrt(var / (1 - b2 ** t)) + o["eps"])
        p32 = p.astype(jnp.float32)
        if decay:
            upd = upd + o["weight_decay"] * p32
        return (p32 - o["lr"] * upd).astype(p.dtype), mom, var

    def _adam_slice(self, p, g, mom, var, i, t, scale, decay):
        pi, mi, vi = self._adam_leaf(p[i], g, mom[i], var[i], t, scale, decay)
        return p.at[i].set(pi), mom.at[i].set(mi), var.at[i].set(vi)

    def grad_pass(self, params: dict, batch: dict, step_key, visit) -> float:
        """Forward and backward of one batch, layer by layer. ``visit(name,
        layer, grad)`` gets every float32 gradient as it is made: the head's
        (layer None), then each layer's from the last down, the embedding's
        last; a visit may update that leaf. Returns the loss."""
        m = self.m
        names = layer_names(m)
        toks = jnp.asarray(batch["tokens"])
        keys = [None if self.sk is None else jax.random.fold_in(step_key, li)
                for li in range(m.n_layers)]
        layer_p = [{n: params[n][li] for n in names} for li in range(m.n_layers)]
        xs, loss = [self._embed(params["embed"], toks)], 0.0
        for li in range(m.n_layers):
            x, aux = self._fwd(layer_p[li], xs[-1], keys[li])
            xs.append(x)
            loss += float(aux)
        ce, (g_fn, g_head, dx) = self._head(params["final_norm"], params["lm_head"],
                                            xs.pop(), jnp.asarray(batch["labels"]))
        loss += float(ce)
        visit("final_norm", None, g_fn)
        visit("lm_head", None, g_head)
        del g_fn, g_head
        for li in reversed(range(m.n_layers)):
            dp, dx = self._vjp(layer_p[li], xs.pop(), keys[li], dx)
            layer_p[li] = None
            for n in names:
                visit(n, li, dp.pop(n))
        visit("embed", None, self._embed_grad(toks, dx))
        return loss

    def train(self, params: dict, batches: list, step_keys: list) -> dict:
        """Run len(batches) AdamW steps from ``params`` (updated in place).
        Returns the losses, the per-leaf norms of the first clipped
        gradient, and the final parameters."""
        o = self.opt
        mom = {n: jnp.zeros(a.shape, jnp.float32) for n, a in params.items()}
        var = {n: jnp.zeros(a.shape, jnp.float32) for n, a in params.items()}
        losses, g1 = [], None
        for t, (batch, key) in enumerate(zip(batches, step_keys), start=1):
            sq = {n: 0.0 for n in params}

            def norms(n, li, g):
                sq[n] += float(self._sq(g))

            def update(n, li, g):
                decay = params[n].ndim >= 2
                if li is None:
                    params[n], mom[n], var[n] = self._adam(
                        params[n], g, mom[n], var[n], jnp.float32(t), scale, decay)
                else:
                    params[n], mom[n], var[n] = self._adam_at(
                        params[n], g, mom[n], var[n], li, jnp.float32(t), scale, decay)

            losses.append(self.grad_pass(params, batch, key, norms))
            gn = float(np.sqrt(sum(sq.values())))
            scale = jnp.float32(min(1.0, o["clip"] / max(gn, 1e-12)) if o.get("clip") else 1.0)
            if g1 is None:
                g1 = {n: float(np.sqrt(v)) * float(scale) for n, v in sq.items()}
            self.grad_pass(params, batch, key, update)
        return {"losses": losses, "grad1": g1, "params": params}
