"""Device scopes: the train step's layer names on the device.

Each layer of the train step opens a ``compat.named_scope`` from one small
vocabulary, so every HLO instruction of the compiled step carries its layer
in ``metadata={op_name="..."}``:

======== ============================================================
scope    covers
======== ============================================================
embed    token lookup (and, by transposition, its gradient scatter)
stack    the layer scan: norms, residual adds, scan stacking, remat
attn     attention: projections' exact parts, RoPE, scores, softmax
ffn      dense MLP; MoE router, dispatch and experts
head     final norm, LM head, cross-entropy
sketch   the sketched VJP of every site, with the sub-scopes ``score``
         (column scores), ``plan`` (solver and sampling) and ``vjp``
         (gathered matmuls, Pallas kernel or XLA fallback, dW scatter)
optim    global-norm clip, the optimizer update, plan-state write-back
======== ============================================================

An op's layer is the innermost layer name in its ``op_name`` path (a
sketched site inside attention counts as ``sketch``); its sub-scope is the
innermost of ``score``/``plan``/``vjp`` under ``sketch``. Scopes change
metadata only: the computation, its numerics and the compiled program's
instructions are the same with and without them.

:func:`op_layer_table` reads the table instruction name → (layer, scope
path) from a compiled executable's HLO text; the Runtime records one per
step executable it builds when tracing is on
(:meth:`repro.obs.Observability.op_layers`).
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Optional, Tuple

from repro import compat

__all__ = ["EMBED", "STACK", "ATTN", "FFN", "HEAD", "SKETCH", "OPTIM",
           "SCORE", "PLAN", "VJP", "LAYERS", "scoped", "layer_of", "op_layer_table"]

EMBED, STACK, ATTN, FFN, HEAD, SKETCH, OPTIM = (
    "embed", "stack", "attn", "ffn", "head", "sketch", "optim")
SCORE, PLAN, VJP = "score", "plan", "vjp"
LAYERS = (EMBED, STACK, ATTN, FFN, HEAD, SKETCH, OPTIM)
_VOCAB = frozenset(LAYERS + (SCORE, PLAN, VJP))

# JAX wraps a scope in the transforms applied under it: ``jvp(attn)``,
# ``transpose(jvp(stack))``, ``vmap(sketch)``. ``jit(...)`` names a function,
# not a scope, and is never unwrapped.
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint|pmap|"
                        r"custom_jvp_call|custom_vjp_call)\((.*)\)$")


def scoped(name: str) -> Callable:
    """Decorator: the function's ops carry device scope ``name``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with compat.named_scope(name):
                return fn(*args, **kw)
        return wrapper
    return deco


def _names(op_name: str):
    for part in op_name.split("/"):
        m = _TRANSFORM.match(part)
        while m:
            part = m.group(1)
            m = _TRANSFORM.match(part)
        if part in _VOCAB:
            yield part


def layer_of(op_name: str) -> Tuple[Optional[str], str]:
    """(layer, scope path) of one ``op_name``: the innermost layer name and
    the vocabulary names of the path joined by ``/`` (``stack/attn/sketch/
    vjp/plan``). (None, "") where the path names no layer."""
    names = list(_names(op_name))
    layers = [n for n in names if n in LAYERS]
    return (layers[-1] if layers else None), "/".join(names)


_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
# a line that opens a module or a computation (``%name (params) -> ... {``)
_HEADER = re.compile(r"^(?:HloModule\s|ENTRY\s|%?[\w.\-]+\s+\()")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=%]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")

Entry = Tuple[Optional[str], str]


def _lines(hlo_text: str):
    """The text's lines with each instruction whole: a kernel's attributes
    may hold line breaks (the splash attention kernels' ``kernel_metadata``),
    which leave the rest of the instruction, its ``op_name`` included, on
    lines of their own that start at column 0 and open no computation."""
    out: list = []
    for line in hlo_text.splitlines():
        if (out and line and not line[0].isspace() and line.rstrip() != "}"
                and not _HEADER.match(line)):
            out[-1] += line
        else:
            out.append(line)
    return out


def op_layer_table(hlo_text: str) -> Tuple[str, Dict[str, Entry]]:
    """(HLO module name, {instruction name: (layer, scope path)}) of the
    instructions of a compiled module's HLO text (``compiled.as_text()``)
    that can run as device ops: every one outside a fused computation.

    An instruction whose own ``op_name`` names no layer (compiler-made
    copies, loop bookkeeping, reducer bodies) takes the layer of the ops it
    fuses, else that of the instruction whose computation it runs in (the
    ``while`` of a scanned layer, the fusion it belongs to); (None, "")
    where neither names one."""
    module, comp = "", None
    own: Dict[str, Entry] = {}
    comp_of: Dict[str, str] = {}
    members: Dict[str, list] = {}      # computation -> instructions, root first
    caller: Dict[str, str] = {}        # computation -> first instruction calling it
    fused: Dict[str, list] = {}        # fusion instruction -> its computations
    parsed: Dict[str, Entry] = {}
    for line in _lines(hlo_text):
        if not line or line[0] == "}":
            continue
        if not line[0].isspace():
            m = _MODULE.match(line)
            if m and not module:
                module = m.group(1)
            elif line.rstrip().endswith("{"):
                head = line.split()
                comp = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
                members[comp] = []
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        key = op.group(1) if op else ""
        hit = parsed.get(key)
        if hit is None:
            hit = parsed[key] = layer_of(key) if key else (None, "")
        own[name] = hit
        comp_of[name] = comp
        if line.lstrip().startswith("ROOT"):
            members[comp].insert(0, name)
        else:
            members[comp].append(name)
        called = _CALLS.findall(line)
        for group in _CALL_LISTS.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        for c in called:
            caller.setdefault(c, name)
        if " fusion(" in line:
            fused[name] = called

    table: Dict[str, Entry] = {}

    def resolve(name: str) -> Entry:
        hit = table.get(name)
        if hit is not None:
            return hit
        table[name] = (None, "")  # cycle guard
        hit = own[name]
        if hit[0] is None:
            # a fusion named by no scope of its own: the scope of the ops it fuses
            for c in fused.get(name, ()):
                hit = next((own[i] for i in members.get(c, ()) if own[i][0]), hit)
                if hit[0]:
                    break
        if hit[0] is None and comp_of[name] in caller:
            hit = resolve(caller[comp_of[name]])
        table[name] = hit
        return hit

    inside = {c for calls in fused.values() for c in calls}
    return module, {name: resolve(name) for name in own if comp_of[name] not in inside}
