"""A stub reference module for a two-kind architecture, of the program's
zamba smoke shape: periods of ``shared_attn_every`` Mamba2 layers and one
invocation of a shared attention block, then a remainder of Mamba2 layers.

It names the weights, where the program keeps them and the model's FLOPs,
which is what the harness's set-up and ``mfu`` take from a reference. It
has no training steps (``Sketch``, ``Reference``), so it cannot decide
``correct``.
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np

FAULT_LEAF = "shared.attn_o"
MAMBA_MATRICES = ("in_z", "in_x", "in_B", "in_C", "in_dt", "out")
MAMBA_BARE = ("conv", "A_log", "D", "dt_bias")


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    n_layers: int
    every: int
    d_state: int
    ssm_head_dim: int
    expand: int
    d_conv: int
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        return cls(d=c["hidden_size"], n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], n_layers=c["num_hidden_layers"],
                   every=c["shared_attn_every"], d_state=c["mamba_d_state"],
                   ssm_head_dim=c["mamba_headdim"], expand=c["mamba_expand"],
                   d_conv=c["mamba_d_conv"], dtype=c["torch_dtype"])

    @property
    def periods(self) -> int:
        return self.n_layers // self.every

    @property
    def tail(self) -> int:
        return self.n_layers - self.periods * self.every


def _mamba(m: Model, n: int) -> dict:
    """One Mamba2 sub-layer's leaves, stacked over ``n`` layers."""
    d, di, dt = m.d, m.expand * m.d, m.dtype
    H = di // m.ssm_head_dim
    return {"norm1": ((n, d), dt, None),
            "in_z": ((n, di, d), dt, d ** -0.5), "in_x": ((n, di, d), dt, d ** -0.5),
            "in_B": ((n, m.d_state, d), dt, d ** -0.5),
            "in_C": ((n, m.d_state, d), dt, d ** -0.5),
            "in_dt": ((n, H, d), dt, d ** -0.5), "conv": ((n, m.d_conv, di), dt, 0.1),
            "A_log": ((n, H), "float32", 1.0), "D": ((n, H), "float32", None),
            "dt_bias": ((n, H), "float32", 1.0), "norm": ((n, di), dt, None),
            "out": ((n, d, di), dt, di ** -0.5)}


def _subs(m: Model):
    """(name prefix, segment, sub-layer, layers stacked) of each Mamba2 sub-layer."""
    out = [(f"mamba{j}", 0, j, m.periods) for j in range(m.every)]
    if m.tail:
        out.append(("mamba_tail", 1, 0, m.tail))
    return out


def weight_specs(m: Model) -> dict:
    d, F, dh, dt = m.d, m.d_ff, m.d // m.n_heads, m.dtype
    s = {"embed": ((m.vocab, d), dt, d ** -0.5), "final_norm": ((d,), dt, None),
         "lm_head": ((m.vocab, d), dt, d ** -0.5)}
    for prefix, _, _, n in _subs(m):
        s.update({f"{prefix}.{k}": v for k, v in _mamba(m, n).items()})
    s.update({"shared.norm1": ((d,), dt, None), "shared.norm2": ((d,), dt, None),
              "shared.attn_q": ((m.n_heads * dh, d), dt, d ** -0.5),
              "shared.attn_k": ((m.n_kv * dh, d), dt, d ** -0.5),
              "shared.attn_v": ((m.n_kv * dh, d), dt, d ** -0.5),
              "shared.attn_o": ((d, m.n_heads * dh), dt, d ** -0.5),
              "shared.mlp_in": ((F, d), dt, d ** -0.5),
              "shared.mlp_gate": ((F, d), dt, d ** -0.5),
              "shared.mlp_out": ((d, F), dt, F ** -0.5)})
    return s


def program_paths(m: Model) -> dict:
    p = {"embed": ("embed",), "final_norm": ("final_norm", "g"), "lm_head": ("lm_head", "w")}
    for prefix, seg, sub, _ in _subs(m):
        at = ("segments", seg, sub)
        p[f"{prefix}.norm1"] = at + ("norm1", "g")
        p[f"{prefix}.norm"] = at + ("mamba", "norm", "g")
        p.update({f"{prefix}.{k}": at + ("mamba", k, "w") for k in MAMBA_MATRICES})
        p.update({f"{prefix}.{k}": at + ("mamba", k) for k in MAMBA_BARE})
    p.update({"shared.norm1": ("shared", "norm1", "g"), "shared.norm2": ("shared", "norm2", "g")})
    p.update({f"shared.attn_{k}": ("shared", "attn", k, "w") for k in "qkvo"})
    p.update({f"shared.mlp_{k}": ("shared", "mlp", k, "w") for k in ("in", "gate", "out")})
    return p


def model_flops_per_token(c: dict, seq: int) -> float:
    """6 per matmul parameter (Mamba2 projections of every layer, the shared
    block once per period, the head) plus the shared block's causal
    attention and each Mamba2 layer's scan (2 FLOPs a multiply-add, 3
    passes, over its state of d_state per head channel)."""
    m = Model.from_config(c)
    d, di, dh = m.d, m.expand * m.d, m.d // m.n_heads
    H = di // m.ssm_head_dim
    mamba = d * (2 * di + 2 * m.d_state + H) + di * d
    shared = 2 * d * m.n_heads * dh + 2 * d * m.n_kv * dh + 3 * d * m.d_ff
    params = m.n_layers * mamba + m.periods * shared + m.vocab * d
    attn = m.periods * 3 * 2 * 2 * m.n_heads * dh * (seq + 1) / 2
    scan = m.n_layers * 3 * 2 * 2 * di * m.d_state
    return 6 * params + attn + scan


def weight_key(seed: int):
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words)


def make_leaf(wkey, name: str, spec):
    shape, dtype, std = spec
    if std is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(wkey, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_weights(wkey, specs: dict) -> dict:
    return {n: make_leaf(wkey, n, s) for n, s in specs.items()}
