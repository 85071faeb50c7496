"""Operations and bytes, computed from shapes: the model's FLOPs per token
(for ``mfu``, counted by the configuration's reference module), the parts
of a Llama-family count, and each sketch kernel call's least work (for
``sketch_kernel_roofline``)."""
from __future__ import annotations

import re

def matmul_params_per_token(c: dict) -> int:
    """Parameters a token multiplies by: every projection of every layer
    (for experts, the ``num_experts_per_tok`` it is routed to, and the
    router) and the head; not the embedding lookup."""
    d, F = c["hidden_size"], c["intermediate_size"]
    H = c["num_attention_heads"]
    Kv = c.get("num_key_value_heads", H)
    dh = c.get("head_dim", d // H)
    attn = d * H * dh * 2 + d * Kv * dh * 2
    if c.get("num_experts"):
        ffn = c["num_experts_per_tok"] * 3 * d * F + c["num_experts"] * d
    else:
        ffn = 3 * d * F
    return c["num_hidden_layers"] * (attn + ffn) + c["vocab_size"] * d


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Causal attention, forward and backward: QK^T and PV, 2 FLOPs a
    multiply-add, 3 passes, over the mean causal context (seq + 1) / 2."""
    H = c["num_attention_heads"]
    dh = c.get("head_dim", c["hidden_size"] // H)
    return c["num_hidden_layers"] * 3 * 2 * 2 * H * dh * (seq + 1) / 2


def model_flops_per_token(c: dict, seq: int, here: str | None = None) -> float:
    """The exact model's training FLOPs per token, as the reference module
    that configuration ``c`` names (found in ``here``, the benchmark's
    directory by default) counts them: 6 per matmul parameter plus the
    sequence mixer's own work. Recomputation and the sketch's savings are
    not counted, so sketched and exact cells share the yardstick."""
    from benchmarks.chip import bench

    return bench.load_reference(c, here or bench.HERE).model_flops_per_token(c, seq)


# ---------------------------------------------------------------------------
# the sketch kernels' calls
# ---------------------------------------------------------------------------


_SHAPE = re.compile(r"(bf16|f16|f32|s32|u32|s8|u8)\[([\d,]*)\]\{([^}]*)\}")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}


def _shapes(text: str) -> list:
    """(dtype, dims, in HBM) of each shape in an HLO fragment; ``S(1)`` in
    the layout places the buffer in VMEM."""
    return [(dt, tuple(int(v) for v in dims.split(",") if v), "S(1)" not in lay)
            for dt, dims, lay in _SHAPE.findall(text)]


def _bytes(shape) -> int:
    dt, dims, _ = shape
    n = 1
    for v in dims:
        n *= v
    return n * _ITEM[dt]


def kernel_work(name: str, hlo: str):
    """(FLOPs, HBM bytes) a sketch kernel's call needs at least, from the
    call's HLO text (``%name = out custom-call(operands), ...``): the
    arithmetic of its shapes, and the bytes of its operands and outputs
    that live in HBM (a buffer XLA placed in VMEM costs no HBM traffic).
    None for a kernel this table does not know."""
    head, _, rest = hlo.partition(" custom-call(")
    outs = _shapes(head.partition(" = ")[2])
    ins = _shapes(rest.partition("), custom_call_target")[0])
    hbm = lambda s, nbytes=None: (_bytes(s) if nbytes is None else nbytes) if s[2] else 0
    if name.startswith("col_l1_scores"):
        (g,) = ins
        return 2 * g[1][0] * g[1][1], hbm(g) + sum(hbm(o) for o in outs)
    if name.startswith("block_gather_matmul_fused"):
        idx, _, g, w, x = ins[:5]
        rb, N, d = idx[1][0], g[1][0], x[1][1]
        block = outs[1][1][1]
        r = rb * block
        item = _ITEM[g[0]]
        read = hbm(g, N * r * item) + hbm(w, r * d * item) + hbm(x)
        return 4 * N * r * d, read + sum(hbm(o) for o in outs)
    return None
