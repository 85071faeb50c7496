"""Feed-forward blocks: plain MLP, GLU family (SwiGLU/GeGLU), squared-ReLU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.common import ACTIVATIONS, Ctx, dense, dense_init
from repro.obs import scopes

__all__ = ["mlp_init", "mlp"]

_GLU = {"swiglu": "silu", "geglu": "gelu"}


def mlp_init(key, d_model: int, d_ff: int, mlp_type: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"in": dense_init(ks[0], d_model, d_ff, dtype),
         "out": dense_init(ks[1], d_ff, d_model, dtype, scale=d_ff ** -0.5)}
    if mlp_type in _GLU:
        p["gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


@scopes.scoped(scopes.FFN)
def mlp(params, x, ctx: Ctx, mlp_type: str, role_prefix: str = "mlp"):
    h = dense(params["in"], x, ctx, f"{role_prefix}_in")
    if mlp_type in _GLU:
        g = dense(params["gate"], x, ctx, f"{role_prefix}_gate")
        h = ACTIVATIONS[_GLU[mlp_type]](g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        act = {"relu_sq": "relu_sq", "gelu": "gelu", "relu": "relu"}.get(mlp_type, "gelu")
        h = ACTIVATIONS[act](h.astype(jnp.float32)).astype(h.dtype)
    return dense(params["out"], h, ctx, f"{role_prefix}_out")
