"""Jaxpr sketch-coverage: prove every parameter matmul is on the spine.

The paper's savings only accrue at sites that actually route through the one
sketched-site ``custom_vjp`` spine (``core/site.py``). This analyzer traces
a train cell's backward with ``jax.make_jaxpr(jax.grad(loss))`` (abstract —
no FLOP is spent, no state is touched), then answers, per weight leaf:
*which matmuls produce this gradient, and do they run through the spine?*

Mechanics (validated against every registered arch family):

* **Flattened provenance graph** — ``jit`` / ``remat2`` /
  ``custom_vjp_call_jaxpr`` sub-jaxprs are inlined into one global var
  graph (loop primitives stay opaque; under ``cost_mode`` ctx the chunk
  scans are python-unrolled so almost nothing hides in a loop body).
* **Equation provenance** — ``compat.user_frames`` yields user-code
  (file, line) frames per equation. JAX's transpose rules inherit the
  forward equation's source info, so a site's forward, dX and dW matmuls
  all share one provenance key — grouping by it collects a site's full
  FLOP footprint from any one attributed equation.
* **Gradient attribution** — from each parameter's grad output var, walk
  producers backward through *gradient-transparent* ops (add_any,
  transpose, reshape, pad, convert, psum, ...) until hitting opaque
  "terminal" equations. A terminal ``dot_general``/``scatter-add`` whose
  provenance lies in ``repro/core`` is spine evidence (compact dW is a
  scatter of sketched rows into zeros — still the spine); a terminal
  ``dot_general`` elsewhere is an **escaped dense matmul**, named by its
  file:line. ``mul``/``select_n``/``reduce_sum`` are deliberately opaque:
  keeping them transparent would let the embedding cotangent cone swallow
  the whole graph.

Per-site categories:

* ``resolved`` — ``core.site.resolve_tree_site`` yields a SiteSpec (the
  slot builders, telemetry and TP planning all see this site).
* ``exact`` — on the spine but deliberately exact: the role is
  policy-excluded (lm_head, router-class small sites, ssm_small) or the
  multi-use ``shared`` subtree.
* ``unresolved`` — executes through the spine at runtime (role hints via
  ``Ctx.cfg_for``) but is invisible to path-based spec resolution: no
  gslots, no probes, no TP plan. This is exactly the ROADMAP MoE/SSM gap.
* ``escaped`` — at least one gradient-producing dense matmul bypasses the
  spine entirely (MoE router, RWKV decay-LoRA ``w1``/``w2``).
* ``no_matmul`` — gradient produced without any matmul (embeddings,
  norms, convs, gates).

``escaped``/``unresolved`` sites must be waived by ``baseline.json`` or
:func:`check_baseline` fails, naming the site and its file:line — the gate
starts green on the known gap and *ratchets*.
"""
from __future__ import annotations

import dataclasses
import json
import os
from fnmatch import fnmatch
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["SiteCoverage", "CoverageReport", "BaselineResult", "analyze_loss",
           "analyze_runtime", "role_hint", "load_baseline", "check_baseline",
           "BASELINE_PATH"]

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baseline.json")

# Gradient accumulation / layout ops the backward walk sees through. NOT
# mul/select_n/reduce_sum/gather: those would let the walk escape the
# gradient cone (the embedding cotangent reaches the whole graph via adds
# and masks) and mis-attribute activation matmuls to parameters.
_TRANSPARENT = frozenset({
    "add_any", "add", "transpose", "reshape", "convert_element_type",
    "broadcast_in_dim", "squeeze", "expand_dims", "slice", "pad",
    "concatenate", "rev", "copy", "psum", "sharding_constraint",
    "reduce_precision", "optimization_barrier",
})

# Straight-line higher-order primitives inlined into the flat graph.
_INLINE = frozenset({"jit", "remat2", "custom_vjp_call_jaxpr",
                     "custom_jvp_call", "custom_vjp_call", "closed_call",
                     "checkpoint"})

# Anything under repro/core is the spine's own machinery (site.py fwd/bwd,
# sketched_linear residuals, estimator plans, compact scatter emission).
_SPINE_DIR = os.sep + os.path.join("repro", "core") + os.sep


# ---------------------------------------------------------------------------
# Role hints: the analyzer's *extended* path->role map
# ---------------------------------------------------------------------------

# Read-only superset of core.compact_grad._site_role. The runtime map must
# NOT learn these entries (a gslot emitted for a site whose `linear` call
# never consumes it silently zeroes that gradient); the analyzer only needs
# them to say which policy role a path *would* carry.
_PARENT_ROLES = {
    "moe": {"wi": "expert_in", "wg": "expert_gate", "wo": "expert_out",
            "router": "router"},
    "mamba": {"in_z": "ssm_in", "in_x": "ssm_in", "out": "ssm_out",
              "in_B": "ssm_small", "in_C": "ssm_small", "in_dt": "ssm_small"},
    "rwkv": {"r": "attn_q", "k": "attn_k", "v": "attn_v", "g": "mlp_gate",
             "out": "attn_o", "cm_k": "mlp_in", "cm_v": "mlp_out",
             "cm_r": "mlp_gate", "w1": "ssm_small", "w2": "ssm_small"},
}


def role_hint(path: Tuple) -> Optional[str]:
    """Policy role a params-tree path would carry at runtime (via explicit
    ``Ctx.cfg_for`` role arguments), including the paths that
    ``core.compact_grad._site_role`` is deliberately blind to."""
    from repro.core.compact_grad import _site_role

    role = _site_role(path)
    if role is not None:
        return role
    if not path:
        return None
    if path[-1] == "embed":
        return "embed"
    if len(path) >= 2 and path[-2] == "lm_head":
        return "lm_head"
    if len(path) >= 2:
        parent, leaf = path[-2], path[-1]
        if leaf == "w" and len(path) >= 3:
            parent, leaf = path[-3], path[-2]
        sub = _PARENT_ROLES.get(parent)
        if sub:
            return sub.get(leaf)
    return None


# ---------------------------------------------------------------------------
# Jaxpr graph
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for s in vs:
            if hasattr(s, "jaxpr"):          # ClosedJaxpr
                yield s.jaxpr
            elif hasattr(s, "eqns"):         # raw Jaxpr
                yield s


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    batch = 1.0
    for d in lb:
        batch *= lhs[d]
    contract = 1.0
    for d in lc:
        contract *= lhs[d]
    lfree = rfree = 1.0
    for i, d in enumerate(lhs):
        if i not in lc and i not in lb:
            lfree *= d
    for i, d in enumerate(rhs):
        if i not in rc and i not in rb:
            rfree *= d
    return 2.0 * batch * lfree * rfree * contract


def _modelled_site_flops(shape, n_tokens: float) -> float:
    """Dense-equivalent fwd+dX+dW FLOPs of one weight site: 6·T·d_out·d_in
    (× stacked leading dims for vmapped expert weights). Spine sites all
    share one provenance key (the single custom_vjp call line), so their
    per-site cost comes from this static model instead of provenance
    grouping; the telemetry site_cost_table uses the same convention."""
    lead = 1.0
    for d in shape[:-2]:
        lead *= d
    return 6.0 * lead * n_tokens * shape[-2] * shape[-1]


def _prov_key(eqn) -> str:
    from repro import compat  # lazy: keep the lint CLI jax-free

    frames = compat.user_frames(eqn.source_info)
    if not frames:
        return "?"
    f, line = frames[0]
    return f"{f}:{line}"


def _is_spine(eqn) -> bool:
    from repro import compat

    for f, _ in compat.user_frames(eqn.source_info):
        if _SPINE_DIR in f.replace("/", os.sep):
            return True
    return False


class _Graph:
    """Flattened producer graph over a closed jaxpr (see module docstring)."""

    def __init__(self, closed_jaxpr):
        from jax.extend.core import Literal

        self._literal = Literal
        self.eqns: List[Tuple[object, dict]] = []   # (eqn, invar-substitution)
        self.alias: Dict[object, object] = {}       # outer var -> inner var
        self.dots: List[Tuple[object, float]] = []  # every dot, x trip count
        self._flatten(closed_jaxpr.jaxpr, {}, 1.0)
        self.producer: Dict[object, Tuple[object, dict]] = {}
        for eqn, amap in self.eqns:
            for ov in eqn.outvars:
                self.producer[ov] = (eqn, amap)

    def _flatten(self, jaxpr, amap, mult) -> None:
        for eqn in jaxpr.eqns:
            prim = str(eqn.primitive)
            if prim == "dot_general":
                self.dots.append((eqn, mult))
            if prim in _INLINE:
                inner = next(iter(_sub_jaxprs(eqn)), None)
                if inner is not None and len(inner.invars) == len(eqn.invars):
                    outer = [iv if isinstance(iv, self._literal)
                             else amap.get(iv, iv) for iv in eqn.invars]
                    inner_map = dict(zip(inner.invars, outer))
                    self._flatten(inner, inner_map, mult)
                    for ov, iov in zip(eqn.outvars, inner.outvars):
                        self.alias[ov] = (iov if isinstance(iov, self._literal)
                                          else inner_map.get(iov, iov))
                    continue
            self.eqns.append((eqn, amap))
            # opaque sub-jaxprs (loops, failed inlines): still surface their
            # dots for the FLOP totals, scaled by the scan trip count
            trips = mult * float(eqn.params.get("length", 1)) \
                if prim == "scan" else mult
            for sub in _sub_jaxprs(eqn):
                self._collect_dots(sub, trips)

    def _collect_dots(self, jaxpr, mult) -> None:
        for eqn in jaxpr.eqns:
            prim = str(eqn.primitive)
            if prim == "dot_general":
                self.dots.append((eqn, mult))
            trips = mult * float(eqn.params.get("length", 1)) \
                if prim == "scan" else mult
            for sub in _sub_jaxprs(eqn):
                self._collect_dots(sub, trips)

    def resolve(self, v):
        seen = set()
        while v in self.alias and id(v) not in seen:
            seen.add(id(v))
            v = self.alias[v]
        return v

    def terminals(self, outvar) -> List[object]:
        """Opaque equations producing ``outvar`` through transparent ops."""
        seen, terms = set(), []
        frontier = [self.resolve(outvar)]
        while frontier:
            v = frontier.pop()
            if isinstance(v, self._literal) or id(v) in seen:
                continue
            seen.add(id(v))
            got = self.producer.get(v)
            if got is None:
                continue
            eqn, amap = got
            if str(eqn.primitive) in _TRANSPARENT:
                for iv in eqn.invars:
                    if not isinstance(iv, self._literal):
                        frontier.append(self.resolve(amap.get(iv, iv)))
            else:
                terms.append(eqn)
        return terms


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SiteCoverage:
    """Coverage verdict for one parameter leaf."""

    param: str                       # "segments/0/0/moe/router/w"
    role: Optional[str]              # policy role hint (extended map)
    category: str                    # resolved|exact|unresolved|escaped|no_matmul
    provenance: List[str]            # file:line keys of gradient terminals
    flops: float                     # modelled dot FLOPs sharing that provenance
    detail: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CoverageReport:
    sites: List[SiteCoverage]
    total_dot_flops: float
    escaped_flops: float
    unresolved_flops: float

    @property
    def escaped_flop_frac(self) -> float:
        """Traced escaped-dot FLOPs over all traced dot FLOPs."""
        return self.escaped_flops / self.total_dot_flops \
            if self.total_dot_flops else 0.0

    @property
    def unresolved_flop_frac(self) -> float:
        """Modelled dense-equivalent FLOPs of unresolved sites over traced
        dot FLOPs. Indicative, not a proportion: at aggressive budgets the
        traced denominator is already sketch-reduced, so this can exceed 1
        when most sites are unresolved."""
        return self.unresolved_flops / self.total_dot_flops \
            if self.total_dot_flops else 0.0

    def by_category(self) -> Dict[str, List[SiteCoverage]]:
        out: Dict[str, List[SiteCoverage]] = {}
        for s in self.sites:
            out.setdefault(s.category, []).append(s)
        return out

    def escapes(self) -> List[SiteCoverage]:
        return [s for s in self.sites if s.category in ("escaped", "unresolved")]

    def escaped_frac_vs_hlo(self, hlo_flops: float) -> Optional[float]:
        """Escaped modelled FLOPs over an HLO-measured total (the
        ``launch.hlo_analysis.cost_summary`` join)."""
        return self.escaped_flops / hlo_flops if hlo_flops else None

    def summary(self) -> dict:
        cats = {k: len(v) for k, v in self.by_category().items()}
        return {
            "n_sites": len(self.sites),
            "categories": cats,
            "total_dot_flops": self.total_dot_flops,
            "escaped_flops": self.escaped_flops,
            "escaped_flop_frac": self.escaped_flop_frac,
            "unresolved_flop_frac": self.unresolved_flop_frac,
            "escapes": [{"param": s.param, "provenance": s.provenance,
                         "category": s.category, "flops": s.flops}
                        for s in self.escapes()],
        }


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


def _tree_node(tree, path):
    """Parent dict of the leaf at ``path`` (for resolve_tree_site)."""
    node = tree
    for k in path[:-1]:
        key = getattr(k, "key", getattr(k, "idx", k))
        try:
            node = node[key]
        except (KeyError, IndexError, TypeError):
            return None
    return node


def analyze_loss(loss_fn, params, *args, policy=None, n_layers=1,
                 n_tokens: float = 1.0, resolve_kwargs=None) -> CoverageReport:
    """Coverage of ``grad(loss_fn)(params, *args)``'s backward graph.

    ``loss_fn(params, *args) -> scalar``; ``params``/``args`` may be
    concrete arrays or ``ShapeDtypeStruct``s (tracing is abstract either
    way — nothing executes, nothing is mutated). ``policy`` drives
    ``resolve_tree_site``; pass the same one the Runtime trains with.
    ``n_tokens`` scales the static per-site cost model for on-spine sites
    (escaped sites are costed from the traced dots themselves).
    """
    import jax

    from repro import compat
    from repro.core.site import resolve_tree_site

    jaxpr = jax.make_jaxpr(jax.grad(loss_fn))(params, *args)
    graph = _Graph(jaxpr)

    flops_by_prov: Dict[str, float] = {}
    for eqn, mult in graph.dots:
        flops_by_prov[_prov_key(eqn)] = flops_by_prov.get(_prov_key(eqn), 0.0) \
            + _dot_flops(eqn) * mult
    total = sum(flops_by_prov.values())

    leaves_with_path = compat.tree_flatten_with_path(params)[0]
    outvars = jaxpr.jaxpr.outvars
    rk = dict(resolve_kwargs or {})
    rk.setdefault("n_layers", n_layers)

    sites: List[SiteCoverage] = []
    escaped_keys = set()
    unresolved = 0.0
    for (path, leaf), ov in zip(leaves_with_path, outvars):
        if getattr(leaf, "ndim", 0) < 2:
            continue
        pstr = _path_str(path)
        terms = graph.terminals(ov)
        raw_path = tuple(getattr(k, "key", getattr(k, "idx", k)) for k in path)
        role = role_hint(raw_path)

        off_dots = [e for e in terms
                    if str(e.primitive) == "dot_general" and not _is_spine(e)]
        spine_evidence = [e for e in terms if _is_spine(e)]
        has_dot = off_dots or any(str(e.primitive) == "dot_general"
                                  for e in terms)

        if off_dots:
            category = "escaped"
            prov = sorted({_prov_key(e) for e in off_dots})
            flops = sum(flops_by_prov.get(p, 0.0) for p in prov)
            escaped_keys.update(prov)
            detail = "gradient produced by a dense matmul off the spine"
        elif spine_evidence:
            prov = sorted({_prov_key(e) for e in spine_evidence})
            flops = _modelled_site_flops(leaf.shape, n_tokens)
            spec = None
            if policy is not None and "shared" not in raw_path:
                node = _tree_node(params, path)
                if isinstance(node, dict):
                    spec = resolve_tree_site(raw_path[:-1] if
                                             raw_path[-1] == "w" else raw_path,
                                             node, policy, **rk)
            if spec is not None:
                category, detail = "resolved", f"plan={spec.plan.kind}"
            elif "shared" in raw_path:
                category = "exact"
                detail = "multi-use shared subtree — deliberately slot-free"
            elif role is not None and (policy is None or
                                       policy.config_for(role, 0,
                                                         rk["n_layers"]) is None):
                category, detail = "exact", f"role {role!r} is policy-excluded"
            else:
                category = "unresolved"
                unresolved += flops
                detail = ("on the spine at runtime (role hint) but invisible "
                          "to path-based spec resolution — no gslots/probes/"
                          "TP plan")
        elif has_dot:
            # dot inside an opaque loop body etc. — treat as escaped
            category = "escaped"
            prov = sorted({_prov_key(e) for e in terms
                           if str(e.primitive) == "dot_general"})
            flops = sum(flops_by_prov.get(p, 0.0) for p in prov)
            escaped_keys.update(prov)
            detail = "matmul terminal outside the spine"
        else:
            category, prov, flops = "no_matmul", [], 0.0
            detail = "gradient carries no matmul"
        sites.append(SiteCoverage(param=pstr, role=role, category=category,
                                  provenance=prov, flops=flops, detail=detail))

    # escaped total dedupes shared provenance (two params produced by one
    # fused off-spine site — RWKV's w1/w2 decay-LoRA line — count once)
    escaped = sum(flops_by_prov.get(k, 0.0) for k in escaped_keys)
    return CoverageReport(sites=sites, total_dot_flops=total,
                          escaped_flops=escaped, unresolved_flops=unresolved)


def analyze_runtime(runtime, cfg, *, batch_size: int = 2, seq_len: int = 16,
                    resolve_kwargs=None) -> CoverageReport:
    """Coverage of one Runtime train cell's backward (abstract trace).

    Builds the same ``lm_loss`` the train step differentiates, under a
    ``cost_mode`` ctx (python-unrolled chunk loops — nothing hides inside
    scan bodies), over ``ShapeDtypeStruct`` params: read-only by
    construction.
    """
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.models import lm

    ex = dc.replace(runtime.execution, cost_mode=True)
    rt = runtime.replace(execution=ex)
    ctx = rt.ctx(key=compat.prng_key(0))
    pshapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                             compat.prng_key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32),
             "labels": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)}
    if getattr(cfg, "is_encdec", False):
        batch["src_embeds"] = jax.ShapeDtypeStruct(
            (batch_size, seq_len, cfg.d_model), jnp.float32)
    kstruct = jax.ShapeDtypeStruct((), compat.key_dtype())

    def loss(p, b, k):
        return lm.lm_loss(p, b, dc.replace(ctx), cfg, k)[0]

    return analyze_loss(loss, pshapes, batch, kstruct, policy=rt.policy,
                        n_layers=cfg.n_layers,
                        n_tokens=float(batch_size * seq_len),
                        resolve_kwargs=resolve_kwargs)


# ---------------------------------------------------------------------------
# Baseline gate
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BaselineResult:
    ok: bool
    unwaived: List[SiteCoverage]
    used: List[str]      # waiver ids that matched at least one site
    unused: List[str]    # waiver ids that matched nothing (stale)

    def message(self) -> str:
        if self.ok:
            return (f"coverage gate: ok ({len(self.used)} baseline waiver(s) "
                    "in use)")
        lines = ["coverage gate: un-waived escapes — every parameter matmul "
                 "must route through core/site.py or be waived in "
                 "src/repro/analysis/baseline.json:"]
        for s in self.unwaived:
            lines.append(f"  {s.param} [{s.category}] at "
                         f"{', '.join(s.provenance) or '?'} — {s.detail}")
        return "\n".join(lines)


def load_baseline(path: Optional[str] = None) -> dict:
    with open(path or BASELINE_PATH) as f:
        return json.load(f)


def _waiver_matches(w: dict, site: SiteCoverage) -> bool:
    if w.get("category") and w["category"] != site.category:
        return False
    if not fnmatch(site.param, w.get("param", "*")):
        return False
    prov_pat = w.get("provenance")
    if prov_pat:
        files = [p.rsplit(":", 1)[0] for p in site.provenance]
        if not any(fnmatch(f, prov_pat) or fnmatch(os.path.basename(f),
                                                   prov_pat) or prov_pat in f
                   for f in files):
            return False
    return True


def check_baseline(report: CoverageReport,
                   baseline: Optional[dict] = None) -> BaselineResult:
    """Gate: every escaped/unresolved site must match a baseline waiver."""
    baseline = baseline if baseline is not None else load_baseline()
    waivers = baseline.get("waivers", [])
    used = set()
    unwaived = []
    for site in report.escapes():
        hit = False
        for w in waivers:
            if _waiver_matches(w, site):
                used.add(w["id"])
                hit = True
        if not hit:
            unwaived.append(site)
    unused = [w["id"] for w in waivers if w["id"] not in used]
    return BaselineResult(ok=not unwaived, unwaived=unwaived,
                          used=sorted(used), unused=unused)
