"""AST lint rules (stdlib ``ast`` only — no JAX import at lint time).

Every rule sees a parsed module plus a :class:`FileContext` that owns the
import-alias table, so detection is *name-resolving*: ``from
jax.experimental import shard_map as sm`` trips the version-gate rule at the
import and at every ``sm(...)`` use — patterns the old ``test_compat.py``
regexes missed — while prose mentions in docstrings/comments no longer
false-positive (strings are not names).

Rule ids are stable kebab-case strings; waive one occurrence with an inline
``# lint: waive=<rule-id>`` comment (see findings.py). Per-rule ``allow``
patterns are fnmatch'ed against the file's path relative to the ``repro``
package root (so ``compat.py`` means ``src/repro/compat.py`` wherever the
tree is checked out).
"""
from __future__ import annotations

import ast
import dataclasses
from fnmatch import fnmatch
from typing import Callable, Dict, List, Optional, Protocol, Sequence, Tuple

from repro.analysis.findings import Finding

__all__ = ["Rule", "FileContext", "DEFAULT_RULES", "rule_ids"]


# ---------------------------------------------------------------------------
# Import resolution
# ---------------------------------------------------------------------------


def _import_table(tree: ast.Module) -> Tuple[Dict[str, str], List[Tuple[int, str]]]:
    """(local name -> dotted path, [(line, imported dotted path)]).

    The second list replays every from-import as a "virtual use" so rules
    can flag the import line itself (`from jax import custom_vjp` is already
    the violation, whether or not the name is ever called).
    """
    table: Dict[str, str] = {}
    imported: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                table[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
                imported.append((node.lineno, a.name))
        elif isinstance(node, ast.ImportFrom):
            base = ("." * node.level) + (node.module or "")
            for a in node.names:
                if a.name == "*":
                    continue
                full = f"{base}.{a.name}" if base else a.name
                table[a.asname or a.name] = full
                imported.append((node.lineno, full))
    return table, imported


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclasses.dataclass
class FileContext:
    """Everything a rule needs about one parsed file."""

    path: str        # display path (as passed to the linter)
    relpath: str     # path relative to the repro package root (allow match)
    source: str
    tree: ast.Module
    imports: Dict[str, str] = dataclasses.field(default_factory=dict)
    imported_names: List[Tuple[int, str]] = dataclasses.field(default_factory=list)
    _jitted: Optional[List[ast.AST]] = None

    def __post_init__(self):
        self.imports, self.imported_names = _import_table(self.tree)

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Alias-expanded dotted path of a Name/Attribute chain."""
        d = _dotted(node)
        if d is None:
            return None
        head, _, rest = d.partition(".")
        head = self.imports.get(head, head)
        return f"{head}.{rest}" if rest else head

    def jitted_functions(self) -> List[ast.AST]:
        """Function defs whose body runs under ``jax.jit`` tracing: defs
        decorated with ``*.jit`` (directly or via ``partial(jit, ...)``),
        defs passed to a ``jit(...)`` call, and every def nested inside one
        of those."""
        if self._jitted is not None:
            return self._jitted

        def is_jit(expr) -> bool:
            r = self.resolve(expr)
            return r is not None and (r == "jit" or r.endswith(".jit")
                                      or r.endswith(".pjit"))

        roots: List[ast.AST] = []
        defs: Dict[str, ast.AST] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)
                for dec in node.decorator_list:
                    if is_jit(dec) or (isinstance(dec, ast.Call)
                                       and (is_jit(dec.func)
                                            or any(is_jit(a) for a in dec.args))):
                        roots.append(node)
                        break
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and is_jit(node.func) and node.args:
                target = node.args[0]
                if isinstance(target, ast.Name) and target.id in defs:
                    roots.append(defs[target.id])
        out: List[ast.AST] = []
        seen = set()
        for root in roots:
            for node in ast.walk(root):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and id(node) not in seen:
                    seen.add(id(node))
                    out.append(node)
        self._jitted = out
        return out


class Rule(Protocol):
    """One pluggable lint rule."""

    id: str
    description: str
    allow: Tuple[str, ...]

    def check(self, ctx: FileContext) -> List[Finding]: ...


def _allowed(rule, ctx: FileContext) -> bool:
    return any(fnmatch(ctx.relpath, pat) for pat in rule.allow)


# ---------------------------------------------------------------------------
# Rule 1: version-gated JAX surfaces outside compat.py
# ---------------------------------------------------------------------------


def _jax_rooted(path: str) -> bool:
    return path == "jax" or path.startswith("jax.")


def _version_gated(path: str) -> Optional[str]:
    """Why a resolved jax-rooted dotted path is version-gated, or None."""
    if not _jax_rooted(path):
        return None
    if path.split(".")[-1] == "AxisType":
        return "jax.sharding.AxisType arrived late and changed across releases"
    if path == "jax.shard_map" or ".experimental.shard_map" in path \
            or path.endswith(".shard_map"):
        return "shard_map moved modules and renamed its check kwarg"
    if path == "jax.make_mesh":
        return "jax.make_mesh changed signature across releases"
    return None


_GATED_KWARGS = ("axis_types", "check_vma", "check_rep")


@dataclasses.dataclass(frozen=True)
class JaxVersionGatedRule:
    id: str = "jax-version-gated"
    description: str = ("version-gated JAX symbol used outside repro/compat.py "
                        "(AxisType, shard_map, make_mesh, "
                        "axis_types=/check_vma=/check_rep=)")
    allow: Tuple[str, ...] = ("compat.py",)

    def check(self, ctx: FileContext) -> List[Finding]:
        if _allowed(self, ctx):
            return []
        out = set()

        def add(line, what, why):
            out.add(Finding(ctx.path, line, self.id,
                            f"{what} — {why}; route through repro.compat"))

        for line, dotted in ctx.imported_names:
            why = _version_gated(dotted)
            if why:
                add(line, f"import of {dotted}", why)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Attribute, ast.Name)):
                r = ctx.resolve(node)
                if r:
                    why = _version_gated(r)
                    if why:
                        add(node.lineno, r, why)
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in _GATED_KWARGS:
                        add(node.lineno, f"keyword {kw.arg}=",
                            "gated mesh/shard_map kwarg")
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 2: custom_vjp outside the one spine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CustomVjpRule:
    id: str = "custom-vjp-outside-site"
    description: str = ("jax.custom_vjp outside core/site.py — a second "
                        "sketched-site spine in the making")
    # THE spine; and the pipeline-parallel stage-boundary vjp (not a
    # sketched site). A kernel/decode path that genuinely needs its own vjp
    # must extend this tuple explicitly, with a comment.
    allow: Tuple[str, ...] = ("core/site.py", "launch/pipeline.py")

    def check(self, ctx: FileContext) -> List[Finding]:
        if _allowed(self, ctx):
            return []
        out = set()

        def add(line, what):
            out.add(Finding(
                ctx.path, line, self.id,
                f"{what}: route the site through the one spine "
                "(SiteSpec/ExecutionPlan in core/site.py) or extend the "
                "allowlist explicitly"))

        for line, dotted in ctx.imported_names:
            if _jax_rooted(dotted) and dotted.split(".")[-1] == "custom_vjp":
                add(line, f"import of {dotted}")
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Attribute, ast.Name)):
                r = ctx.resolve(node)
                if r and _jax_rooted(r) and r.split(".")[-1] == "custom_vjp":
                    add(node.lineno, r)
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 3: Ctx construction outside api/ + nn/
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CtxConstructionRule:
    id: str = "ctx-outside-api-nn"
    description: str = ("direct Ctx(...) construction outside repro/api + "
                        "repro/nn")
    allow: Tuple[str, ...] = ("nn/*", "api/*")

    def check(self, ctx: FileContext) -> List[Finding]:
        if _allowed(self, ctx):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else None)
            if name == "Ctx":
                out.append(Finding(
                    ctx.path, node.lineno, self.id,
                    "direct Ctx(...) construction (route through "
                    "ExecutionConfig.make_ctx / Runtime.ctx)"))
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 4: PRNG key reuse
# ---------------------------------------------------------------------------

# jax.random ops that *derive* new keys rather than consuming entropy;
# everything else under jax.random consumes its key argument.
_KEY_DERIVING = frozenset({"split", "fold_in", "key", "PRNGKey", "key_data",
                           "wrap_key_data", "clone", "key_impl"})


@dataclasses.dataclass(frozen=True)
class PrngKeyReuseRule:
    id: str = "prng-key-reuse"
    description: str = ("the same PRNG key consumed by two jax.random ops "
                        "without an intervening split/fold_in")
    allow: Tuple[str, ...] = ()

    def check(self, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        for fn in ast.walk(ctx.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_function(fn, ctx, out)
        return sorted(set(out))

    def _consumed_key(self, call: ast.Call, ctx: FileContext) -> Optional[str]:
        r = ctx.resolve(call.func)
        if r is None or not r.startswith("jax.random."):
            return None
        if r.split(".")[-1] in _KEY_DERIVING:
            return None
        if call.args and isinstance(call.args[0], ast.Name):
            return call.args[0].id
        for kw in call.keywords:
            if kw.arg == "key" and isinstance(kw.value, ast.Name):
                return kw.value.id
        return None

    def _scan_function(self, fn, ctx: FileContext, out: List[Finding]) -> None:
        def bound_names(target) -> List[str]:
            return [n.id for n in ast.walk(target) if isinstance(n, ast.Name)]

        def scan_expr(node, consumed: Dict[str, int]) -> None:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    name = self._consumed_key(sub, ctx)
                    if name is None:
                        continue
                    if name in consumed:
                        out.append(Finding(
                            ctx.path, sub.lineno, self.id,
                            f"key '{name}' already consumed at line "
                            f"{consumed[name]} — split or fold_in first"))
                    else:
                        consumed[name] = sub.lineno

        def scan_block(stmts, consumed: Dict[str, int]) -> None:
            for st in stmts:
                if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                    continue  # separate scope, scanned on its own
                if isinstance(st, ast.If):
                    scan_expr(st.test, consumed)
                    # exclusive branches don't see each other's consumption;
                    # afterwards either may have happened (union)
                    a, b = dict(consumed), dict(consumed)
                    scan_block(st.body, a)
                    scan_block(st.orelse, b)
                    consumed.update(a)
                    consumed.update(b)
                    continue
                if isinstance(st, (ast.For, ast.AsyncFor)):
                    scan_expr(st.iter, consumed)
                    for n in bound_names(st.target):
                        consumed.pop(n, None)
                    scan_block(st.body, consumed)
                    scan_block(st.orelse, consumed)
                    continue
                if isinstance(st, ast.While):
                    scan_expr(st.test, consumed)
                    scan_block(st.body, consumed)
                    scan_block(st.orelse, consumed)
                    continue
                if isinstance(st, (ast.With, ast.AsyncWith)):
                    for item in st.items:
                        scan_expr(item.context_expr, consumed)
                    scan_block(st.body, consumed)
                    continue
                if isinstance(st, ast.Try):
                    scan_block(st.body, consumed)
                    for h in st.handlers:
                        scan_block(h.body, consumed)
                    scan_block(st.orelse, consumed)
                    scan_block(st.finalbody, consumed)
                    continue
                scan_expr(st, consumed)
                if isinstance(st, ast.Assign):
                    for t in st.targets:
                        for n in bound_names(t):
                            consumed.pop(n, None)
                elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
                    for n in bound_names(st.target):
                        consumed.pop(n, None)

        scan_block(fn.body, {})


# ---------------------------------------------------------------------------
# Shared static-expression analysis (rules 5 and 6)
# ---------------------------------------------------------------------------

# metadata reads that are static under tracing (never force a host sync)
_STATIC_ATTRS = frozenset({"ndim", "shape", "dtype", "size", "sharding",
                           "aval", "itemsize", "nbytes"})
_STATIC_CALLS = frozenset({"isinstance", "len", "getattr", "hasattr",
                           "callable", "type", "issubclass"})


def _dynamic_value_use(node: ast.AST, names: frozenset) -> bool:
    """True if the expression reads the traced *value* of one of ``names``
    (rather than static metadata like ``x.ndim`` / ``x.shape[0]``)."""
    if isinstance(node, ast.Name):
        return node.id in names
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return False
        return _dynamic_value_use(node.value, names)
    if isinstance(node, ast.Subscript):
        return _dynamic_value_use(node.value, names)
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False
        return any(_dynamic_value_use(c, names)
                   for c in [node.left] + node.comparators)
    if isinstance(node, ast.Call):
        fname = node.func.attr if isinstance(node.func, ast.Attribute) else (
            node.func.id if isinstance(node.func, ast.Name) else None)
        if fname in _STATIC_CALLS:
            return False
        if isinstance(node.func, ast.Attribute) \
                and _dynamic_value_use(node.func.value, names):
            return True  # method call on a traced receiver, e.g. x.sum()
        return any(_dynamic_value_use(a, names) for a in node.args)
    if isinstance(node, (ast.BoolOp, ast.BinOp, ast.UnaryOp, ast.IfExp)):
        return any(_dynamic_value_use(c, names) for c in ast.iter_child_nodes(node))
    return False


def _param_names(fn) -> frozenset:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return frozenset(names)


# ---------------------------------------------------------------------------
# Rule 5: host sync inside jitted step functions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HostSyncInJitRule:
    id: str = "host-sync-in-jit"
    description: str = ("float()/.item()/np.asarray on traced values inside "
                        "a jitted function (host sync / trace error)")
    allow: Tuple[str, ...] = ()

    def check(self, ctx: FileContext) -> List[Finding]:
        out = set()
        for fn in ctx.jitted_functions():
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if isinstance(func, ast.Attribute) \
                        and func.attr in ("item", "tolist"):
                    out.add(Finding(
                        ctx.path, node.lineno, self.id,
                        f".{func.attr}() inside a jitted function forces a "
                        "host sync"))
                    continue
                r = ctx.resolve(func)
                if r in ("float", "int") and node.args \
                        and not isinstance(node.args[0], ast.Constant) \
                        and _dynamic_value_use(node.args[0], frozenset(
                            n.id for n in ast.walk(node.args[0])
                            if isinstance(n, ast.Name))):
                    out.add(Finding(
                        ctx.path, node.lineno, self.id,
                        f"{r}() on a traced value inside a jitted function "
                        "forces a host sync"))
                elif r is not None and (r.startswith("numpy.")
                                        and r.split(".")[-1] in
                                        ("asarray", "array")):
                    out.add(Finding(
                        ctx.path, node.lineno, self.id,
                        f"{r}() inside a jitted function materializes the "
                        "traced value on host"))
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 6: Python branches on traced values
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TracerBranchRule:
    id: str = "tracer-branch"
    description: str = ("Python if/while on a traced value inside a jitted "
                        "function (TracerBoolConversionError; use lax.cond/"
                        "jnp.where)")
    allow: Tuple[str, ...] = ()

    def check(self, ctx: FileContext) -> List[Finding]:
        out = set()
        for fn in ctx.jitted_functions():
            params = _param_names(fn)
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)) \
                        and _dynamic_value_use(node.test, params):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    out.add(Finding(
                        ctx.path, node.lineno, self.id,
                        f"Python `{kind}` on the traced value of a function "
                        "argument (static checks like .ndim/.shape/`is None` "
                        "are fine; data-dependent control flow needs "
                        "lax.cond / jnp.where)"))
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 7: swallowed exceptions
# ---------------------------------------------------------------------------

_BROAD_EXC = frozenset({"Exception", "BaseException"})


def _broad_handler(handler: ast.ExceptHandler, ctx: FileContext) -> bool:
    """Bare ``except:``, or a handler naming Exception/BaseException
    (directly or inside a tuple). Narrow handlers (``except TypeError``)
    are the caller saying exactly what it expects — never flagged."""
    t = handler.type
    if t is None:
        return True
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in elts:
        r = ctx.resolve(e)
        if r is not None and r.split(".")[-1] in _BROAD_EXC:
            return True
    return False


def _handles_or_records(handler: ast.ExceptHandler) -> bool:
    """Does the handler body *do* anything with the failure? Re-raising,
    returning/yielding a fallback, assigning (recording) or calling
    (logging, forwarding through a queue) all count; ``pass``/docstrings/
    ``continue``/``break`` alone do not."""
    for node in ast.walk(handler):
        if isinstance(node, (ast.Raise, ast.Return, ast.Yield, ast.YieldFrom,
                             ast.Call, ast.Assign, ast.AugAssign,
                             ast.AnnAssign, ast.Delete)):
            return True
    return False


@dataclasses.dataclass(frozen=True)
class SwallowedExceptionRule:
    id: str = "swallowed-exception"
    description: str = ("broad except (bare / Exception / BaseException) that "
                        "neither re-raises nor records — failures vanish "
                        "silently")
    allow: Tuple[str, ...] = ()

    def check(self, ctx: FileContext) -> List[Finding]:
        if _allowed(self, ctx):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ExceptHandler) \
                    and _broad_handler(node, ctx) \
                    and not _handles_or_records(node):
                out.append(Finding(
                    ctx.path, node.lineno, self.id,
                    "broad exception handler swallows the failure — "
                    "re-raise, narrow the type, or record it (log / store / "
                    "forward), with a `# lint: waive=swallowed-exception` "
                    "comment only for a justified sink"))
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 8: threading.Thread targets that lose their exceptions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ThreadUncapturedTargetRule:
    id: str = "thread-uncaptured-target"
    description: str = ("threading.Thread(target=...) whose target cannot "
                        "surface an exception — a failing worker dies "
                        "silently on the daemon thread")
    allow: Tuple[str, ...] = ()

    def check(self, ctx: FileContext) -> List[Finding]:
        if _allowed(self, ctx):
            return []
        defs = {node.name: node for node in ast.walk(ctx.tree)
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))}
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            r = ctx.resolve(node.func)
            if r is None or r.split(".")[-1] != "Thread" \
                    or not (r == "Thread" or r.startswith("threading.")):
                continue
            target = None
            for kw in node.keywords:
                if kw.arg == "target":
                    target = kw.value
            if target is None:
                continue  # subclass style (run() overridden) — its job
            captured = False
            if isinstance(target, ast.Name) and target.id in defs:
                captured = any(isinstance(n, ast.ExceptHandler)
                               for n in ast.walk(defs[target.id]))
            if not captured:
                out.append(Finding(
                    ctx.path, node.lineno, self.id,
                    "Thread target has no exception capture — wrap the "
                    "worker body in try/except and store or forward the "
                    "failure (re-raised on join/wait), or subclass Thread "
                    "with an error-capturing run()"))
        return sorted(out)


# ---------------------------------------------------------------------------
# Rule 9: wall-clock reads outside repro/obs
# ---------------------------------------------------------------------------

# the sanctioned clock lives in repro/obs/clock.py; every timing read in the
# package goes through it so spans / metrics / ad-hoc timers share one
# timebase. _ns/monotonic variants are the same violation in disguise.
_WALL_CLOCK = frozenset({"time.perf_counter", "time.perf_counter_ns",
                         "time.time", "time.time_ns",
                         "time.monotonic", "time.monotonic_ns"})


@dataclasses.dataclass(frozen=True)
class WallClockOutsideObsRule:
    id: str = "wall-clock-outside-obs"
    description: str = ("time.perf_counter/time.time read outside repro/obs — "
                        "use repro.obs.clock.now()/wall() so every timer "
                        "shares the span/metrics timebase")
    allow: Tuple[str, ...] = ("obs/*",)

    def check(self, ctx: FileContext) -> List[Finding]:
        if _allowed(self, ctx):
            return []
        out = set()

        def add(line, what):
            out.add(Finding(
                ctx.path, line, self.id,
                f"{what} — use repro.obs.clock.now() (perf_counter) or "
                "repro.obs.clock.wall() (time.time) instead"))

        for line, dotted in ctx.imported_names:
            if dotted in _WALL_CLOCK:
                add(line, f"import of {dotted}")
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Attribute, ast.Name)):
                r = ctx.resolve(node)
                if r in _WALL_CLOCK:
                    add(node.lineno, r)
        return sorted(out)


DEFAULT_RULES: Tuple[Rule, ...] = (
    JaxVersionGatedRule(),
    CustomVjpRule(),
    CtxConstructionRule(),
    PrngKeyReuseRule(),
    HostSyncInJitRule(),
    TracerBranchRule(),
    SwallowedExceptionRule(),
    ThreadUncapturedTargetRule(),
    WallClockOutsideObsRule(),
)


def rule_ids() -> List[str]:
    return [r.id for r in DEFAULT_RULES]
