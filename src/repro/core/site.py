"""One sketched-site spine: the single ``custom_vjp`` behind every linear site.

Before this module the repo carried four separately-built ``custom_vjp``
spines — the local ``sketched_linear`` plumbing plus three shard_map builds in
``sharded_sketch`` (TP column-parallel, TP row-parallel, TP exact) — each
re-implementing residual capture, RNG-key threading, CompactGrad ``gslot``
cotangents, telemetry ``pslot`` cotangents, bias handling and
estimator-registry dispatch. ``nn.common.dense`` and the slot builders then
had to mirror the dispatch by hand ("must mirror exactly" comments).

This module collapses all of that into:

* :class:`ExecutionPlan` — *where* a site's backward runs: ``local`` (single
  program, pjit-auto sharding), ``tp_column`` / ``tp_row`` (TP-local sketch
  inside ``shard_map`` with compressed DP gradient collectives), or
  ``tp_exact`` (explicit Megatron column-parallel with an exact backward).
* :class:`SiteSpec` — the *declarative* resolution of one site: role, the
  effective :class:`SketchConfig` (after the TP-incompatibility fallback to
  the mask backend), the plan, bias presence, and the derived capabilities
  (``compact_rows`` — the gslot rank, or None when the backward stays dense —
  and ``probe_capable``). :func:`resolve_site` is the one dispatch function;
  ``nn.common.dense``, the CompactGrad slot builder and the telemetry probe
  slot builder all consume the same resolved specs, so slot emission can no
  longer drift from backward dispatch.
* :func:`sketched_site` — the single ``custom_vjp`` spine, parameterized by a
  ``SiteSpec``. It owns, once, everything the four spines duplicated:
  residuals, key threading (per-model-shard fold on the column plan), the
  estimator-registry dispatch (``apply`` / ``apply_with_probe`` locally,
  ``plan`` inside the shard_map bodies), compact-vs-dense dW emission,
  bias gradients on **every** plan (the TP streams fold db through the same
  kept-column gather), and the per-site probe — computed inside the shard_map
  backward body and ``psum``-ed over the model axis on the TP plans, so
  telemetry and adaptive budget control work under tensor parallelism.

Estimator contract on the TP plans: a ``tp_shardable`` estimator's ``plan``
hook returns a compact :class:`~repro.core.sketching.ColumnPlan` whose
``probs`` are the per-column keep marginals — that is what the in-body probe
consumes (``probe_from_rows`` math; see repro/telemetry/probes.py).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import estimators
from repro.core.compact_grad import (TP_OUT_ROLES, TP_ROW_ROLES, CompactGrad,
                                     _site_role)
from repro.core.sketching import (SketchConfig, effective_cfg,
                                  static_block_rank, static_rank)
from repro.obs import scopes

__all__ = ["ExecutionPlan", "SiteSpec", "resolve_site", "resolve_tree_site",
           "sketched_site", "local_spec", "tp_estimator"]


# ---------------------------------------------------------------------------
# Declarative plan + spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Where one site's backward executes (static / hashable).

    kind: ``local`` | ``tp_column`` | ``tp_row`` | ``tp_exact``. The TP kinds
    run inside ``shard_map`` over ``mesh`` with activations sharded on
    ``data_axes`` and the weight's parallel dimension on ``model_axis``.
    """

    kind: str = "local"
    mesh: Optional[object] = None
    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None

    def __post_init__(self):
        if self.kind not in ("local", "tp_column", "tp_row", "tp_exact"):
            raise ValueError(f"unknown plan kind {self.kind!r}")
        if self.kind != "local" and (self.mesh is None or self.model_axis is None):
            raise ValueError(f"plan {self.kind!r} needs a mesh and model_axis")
        object.__setattr__(self, "data_axes", tuple(self.data_axes))

    @property
    def is_tp(self) -> bool:
        return self.kind != "local"


_LOCAL = ExecutionPlan()


@dataclasses.dataclass(frozen=True)
class SiteSpec:
    """One resolved sketched-linear site (static / hashable).

    ``cfg`` is the *effective* config: on TP-incompatible sites under
    ``tp_sketch`` the compact-form backend is replaced by the dense mask
    backend (scatter-hostile compact rows must not be produced where the
    slot builder emits no slot — that invariant is now structural).

    ``compact_rows``: static number of compact dW rows the backward emits
    (the gslot rank), or None when the weight cotangent stays dense.
    ``probe_capable``: the backward can emit the telemetry probe vector —
    via the estimator's ``apply_with_probe`` hook on the local plan, via the
    in-body ``plan()`` marginals on the TP plans.
    ``carry_rows``: static size of the per-site plan-carry state (sslot) a
    plan-carry estimator ("onepass"/"stale") threads through the backward —
    the previous step's column scores — or None when the estimator carries
    no plan. Local plan only; the sslot builder in core/plan_state.py emits
    state leaves from this field, the same way gslot/pslot builders consume
    compact_rows/probe_capable.
    """

    role: str
    cfg: Optional[SketchConfig]
    plan: ExecutionPlan = _LOCAL
    has_bias: bool = False
    d_out: int = 0
    d_in: int = 0
    compact_rows: Optional[int] = None
    probe_capable: bool = False
    carry_rows: Optional[int] = None


@lru_cache(maxsize=None)
def local_spec(cfg: Optional[SketchConfig]) -> SiteSpec:
    """The plain single-program spec ``sketched_linear`` instantiates."""
    return SiteSpec(role="linear", cfg=cfg)


def tp_estimator(cfg):
    """The registered estimator for ``cfg`` iff it opted into the TP plans.

    Any estimator with ``tp_shardable=True`` (builtin compact/pallas, or a
    third-party entry) has its ``plan`` hook called inside the shard_map
    backward; its ``validate`` runs here too, so a config is
    rejected/accepted consistently with the single-device path. Estimators
    without the flag return None and the site resolves to a local plan.
    """
    if cfg is None or cfg.is_noop:
        return None
    try:
        est = estimators.get_estimator(cfg.backend)
    except KeyError:
        return None
    if not getattr(est, "tp_shardable", False):
        return None
    est.validate(cfg)
    return est


def _mesh_prod(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _compact_capable(backend: str) -> bool:
    try:
        return bool(estimators.get_estimator(backend).supports_compact_grad)
    except KeyError:
        return False


def _tp_column_ok(cfg, d_out, mesh, model_axes) -> bool:
    n_mp = _mesh_prod(mesh, model_axes)
    if d_out % n_mp != 0:
        return False
    n_loc = d_out // n_mp
    if cfg.block > 1:
        return n_loc % cfg.block == 0 and static_block_rank(cfg, n_loc) >= 1
    return static_rank(cfg, n_loc) >= 1


def _tp_row_ok(d_in, mesh, model_axes) -> bool:
    return d_in % _mesh_prod(mesh, model_axes) == 0


@lru_cache(maxsize=4096)
def _resolve(role, cfg, d_out, d_in, has_bias, x_ndim, mesh, data_axes,
             model_axes, tp_sketch) -> SiteSpec:
    plan = _LOCAL
    eff = cfg
    if (cfg is not None and tp_sketch and mesh is not None and x_ndim == 3
            and model_axes and tp_estimator(cfg) is not None):
        if role in TP_OUT_ROLES and _tp_column_ok(cfg, d_out, mesh, model_axes):
            plan = ExecutionPlan("tp_column", mesh, data_axes, model_axes[0])
        elif role in TP_ROW_ROLES and _tp_row_ok(d_in, mesh, model_axes):
            plan = ExecutionPlan("tp_row", mesh, data_axes, model_axes[0])
    if plan.kind == "local" and cfg is not None and tp_sketch \
            and _compact_capable(cfg.backend):
        # TP-incompatible site (e.g. kv heads < model axis, or no mesh at
        # all): fall back to the dense-mask estimator rather than the
        # scatter-hostile compact path. Applies to ANY registered
        # compact-form estimator; the slot builder sees the same spec, so no
        # gslot is emitted and the backward produces no compact rows here.
        eff = dataclasses.replace(cfg, backend="mask", block=0)

    rows = None
    carry = None
    if eff is not None and not eff.is_noop:
        try:
            est = estimators.get_estimator(eff.backend)
        except KeyError:
            est = None
        if est is not None and est.supports_compact_grad:
            if plan.kind == "tp_column":
                n_mp = _mesh_prod(mesh, model_axes)
                rows = n_mp * est.compact_rank(eff, d_out // n_mp)
            else:  # tp_row and local both emit d_out-indexed rows
                rows = est.compact_rank(eff, d_out)
        if (est is not None and getattr(est, "plan_carry", False)
                and plan.kind == "local"):
            # Plan-carry estimators thread previous-step scores through the
            # spine; the mask fallback above already rewrote eff.backend for
            # TP-incompatible sites, so carry stays local-plan only.
            carry = est.carry_size(eff, d_out)

    if plan.is_tp:
        # TP plans probe from the in-body plan marginals (ColumnPlan.probs)
        probe = True
    else:
        from repro.telemetry.probes import probe_capable

        probe = probe_capable(eff)
    return SiteSpec(role=role, cfg=eff, plan=plan, has_bias=has_bias,
                    d_out=d_out, d_in=d_in, compact_rows=rows,
                    probe_capable=probe, carry_rows=carry)


def resolve_site(role: str, cfg: Optional[SketchConfig], *, d_out: int,
                 d_in: int, has_bias: bool = False, x_ndim: int = 3,
                 mesh=None, data_axes=("data",), model_axes=("model",),
                 tp_sketch: bool = False) -> SiteSpec:
    """Resolve one linear site to its :class:`SiteSpec` (memoized).

    This is the ONE dispatch decision for sketched sites: ``nn.common.dense``
    executes whatever plan it returns, and the gslot/pslot builders emit
    slots from the same spec — replacing the old per-call
    ``x.ndim == 3 and b is None and role in TP_OUT_ROLES`` heuristics that
    the slot builders had to mirror by hand.
    """
    return _resolve(role, cfg, int(d_out), int(d_in), bool(has_bias),
                    int(x_ndim), mesh, tuple(data_axes), tuple(model_axes),
                    bool(tp_sketch))


def resolve_tree_site(path, node, policy, *, n_layers=1, mesh=None,
                      data_axes=("data",), model_axes=("model",),
                      tp_sketch=False) -> Optional[SiteSpec]:
    """Spec for one params-tree node, or None if the node is not a sketched
    site (role-matched by path: attn/cross q|k|v|o, mlp in|gate|out; the
    multi-use ``"shared"`` subtree is excluded — see with_grad_slots).

    Shared by the gslot and pslot builders and the drift-guard tests: slot
    emission consumes the *same* resolution as ``dense``'s dispatch.
    """
    role = None if "shared" in path else _site_role(path)
    if role is None or not isinstance(node, dict):
        return None
    w = node.get("w")
    if w is None or getattr(w, "ndim", 0) < 2:
        return None
    cfg = policy.config_for(role, 0, n_layers)
    if cfg is None or cfg.is_noop:
        return None
    return resolve_site(role, cfg, d_out=w.shape[-2], d_in=w.shape[-1],
                        has_bias="b" in node, x_ndim=3, mesh=mesh,
                        data_axes=data_axes, model_axes=model_axes,
                        tp_sketch=tp_sketch)


# ---------------------------------------------------------------------------
# The spine
# ---------------------------------------------------------------------------


def _flatten_leading(x):
    lead = x.shape[:-1]
    return x.reshape((-1, x.shape[-1])), lead


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _site_linear(spec: SiteSpec, x, w, b, key, slot, pslot, sslot):
    plan = spec.plan
    if plan.kind == "local":
        y = jnp.einsum("...i,oi->...o", x, w)
        return y + b if b is not None else y
    mesh, dp, mp = plan.mesh, plan.data_axes, plan.model_axis
    if plan.kind in ("tp_column", "tp_exact"):
        def body(x_l, w_l, *b_l):
            y = jnp.einsum("bsi,oi->bso", x_l, w_l)
            return y + b_l[0] if b_l else y

        args = (x, w) + (() if b is None else (b,))
        in_specs = (P(dp, None, None), P(mp, None)) \
            + (() if b is None else (P(mp),))
        return compat.shard_map(body, mesh=mesh, in_specs=in_specs,
                                out_specs=P(dp, None, mp))(*args)

    def body(x_l, w_l, *b_l):
        y = jax.lax.psum(jnp.einsum("bsi,oi->bso", x_l, w_l), mp)
        return y + b_l[0] if b_l else y

    args = (x, w) + (() if b is None else (b,))
    in_specs = (P(dp, None, mp), P(None, mp)) + (() if b is None else (P(None),))
    return compat.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=P(dp, None, None))(*args)


def _fwd(spec, x, w, b, key, slot, pslot, sslot):
    y = _site_linear(spec, x, w, b, key, slot, pslot, sslot)
    return y, (x, w, key, b is not None, slot, pslot is not None, sslot)


def _bwd(spec, res, g):
    # every site's sketched VJP, local and TP plans alike, on the device as
    # sketch/vjp (score and plan open their own sub-scopes inside)
    with compat.named_scope(scopes.SKETCH), compat.named_scope(scopes.VJP):
        return _bwd_plan(spec, res, g)


def _bwd_plan(spec, res, g):
    x, w, key, has_b, slot, want_probe, sslot = res
    kind = spec.plan.kind
    if kind == "local":
        return _local_bwd(spec.cfg, x, w, key, has_b, slot, want_probe,
                          sslot, g)
    if kind == "tp_exact":
        outs = _tp_exact_bwd(spec, x, w, has_b, slot, want_probe, g)
    else:
        outs = _tp_sketch_bwd(spec, x, w, key, has_b, slot, want_probe, g)
    # Plan-carry estimators are local-plan only (tp_shardable=False ⇒ the
    # mask fallback strips the carry before a TP plan is chosen), so on the
    # TP plans the sslot cotangent — when a carry rode along at all — is the
    # unchanged carry: echo zeros so the train step's write-back is a no-op.
    s_ct = None if sslot is None else jnp.zeros_like(sslot)
    return outs + (s_ct,)


_site_linear.defvjp(_fwd, _bwd)


def sketched_site(spec: SiteSpec, x, w, b=None, key=None, slot=None,
                  pslot=None, sslot=None):
    """Run one site through the spine. ``key=None`` / noop cfg on the local
    plan short-circuits to a plain exact linear (no custom_vjp at all —
    identical to the historical ``sketched_linear`` behavior).

    ``sslot`` (optional): the site's plan-carry state leaf (previous-step
    column scores) for plan-carry estimators. Its cotangent out of the
    custom_vjp is the REFRESHED carry, which core/plan_state.py writes back
    into the params tree after the optimizer step."""
    if spec.plan.kind == "local" and (spec.cfg is None or spec.cfg.is_noop
                                      or key is None):
        y = jnp.einsum("...i,oi->...o", x, w)
        return y + b if b is not None else y
    if spec.plan.kind in ("tp_column", "tp_row"):
        assert tp_estimator(spec.cfg) is not None, \
            "TP sketched site on a non-tp_shardable backend"
    return _site_linear(spec, x, w, b, key, slot, pslot, sslot)


# -- local plan --------------------------------------------------------------


def _local_bwd(cfg, x, w, key, has_b, slot, want_probe, sslot, g):
    G2d, _ = _flatten_leading(g)
    X2d, _ = _flatten_leading(x)
    n = G2d.shape[-1]

    est = estimators.get_estimator("mask" if cfg.is_noop else cfg.backend)
    if getattr(est, "plan_carry", False):
        # one-pass plan-carry backward: the step-t sketch is sampled from
        # the carried step-(t-1) scores (sslot; None ⇒ uniform prior), and
        # the refreshed scores come back in out.state. want_probe is folded
        # in so the carry estimator runs at most one sweep over G.
        out = est.apply_with_state(cfg, G2d, X2d, w, key, sslot, has_b=has_b,
                                   want_probe=want_probe)
    elif want_probe:
        # telemetry: the optional estimator hook may fill out.probe; the
        # probe rides the probe slot's cotangent out of jax.grad
        out = est.apply_with_probe(cfg, G2d, X2d, w, key, has_b=has_b)
    else:
        out = est.apply(cfg, G2d, X2d, w, key, has_b=has_b)
    probe_ct = None
    if want_probe:
        from repro.telemetry.probes import PROBE_WIDTH

        probe_ct = (out.probe if out.probe is not None
                    else jnp.zeros((PROBE_WIDTH,), jnp.float32))
    state_ct = None
    if sslot is not None:
        # the sslot cotangent carries the refreshed scores out of jax.grad;
        # zeros (= "carry unchanged" after the train step's write-back merge)
        # when the estimator emitted no refresh
        state_ct = (out.state.astype(sslot.dtype)
                    if out.state is not None else jnp.zeros_like(sslot))
    dX = out.dx.reshape(x.shape)
    if not out.is_compact:
        return _pack(dX, out.dw.astype(w.dtype), out.db, has_b, slot,
                     probe_ct, state_ct)

    db = None
    if has_b:
        db = jnp.zeros((n,), g.dtype).at[out.cols].add(out.db_c.astype(g.dtype))
    if slot is not None:
        # compact-gradient mode: rows/indices ride the slot cotangent,
        # the dense w cotangent is structural zeros (folded by XLA)
        slot_ct = CompactGrad(rows=out.rows.astype(jnp.float32),
                              idx=out.cols.astype(jnp.float32))
        return (dX, jnp.zeros_like(w), db if has_b else None, None, slot_ct,
                probe_ct, state_ct)
    dW = jnp.zeros_like(w).at[out.cols].add(out.rows.astype(w.dtype))
    return _pack(dX, dW, db, has_b, slot, probe_ct, state_ct)


def _pack(dx, dw, db, has_b, slot, probe_ct, state_ct=None):
    # slot primal is all-zeros, so returning it doubles as its zero cotangent
    return (dx, dw, db if has_b else None, None, slot, probe_ct, state_ct)


# -- TP sketched plans (column / row) ----------------------------------------


def _plan_via_registry(est, lcfg, G2d, w_l, key, dp):
    """One shard-local sketch plan, routed through the registered
    estimator's ``plan`` hook (tp_shardable contract: a compact
    ``ColumnPlan`` with indices + scales + keep marginals)."""
    plan = est.plan(lcfg, G2d, w_l, key, want_compact=True,
                    score_psum_axes=dp)
    if plan is None or plan.indices is None:
        raise ValueError(
            f"estimator {est.name!r} is tp_shardable but plan() returned no "
            "compact ColumnPlan — the TP-sharded backward needs indices/scales")
    return plan


def _gather_compact(lcfg, G2d, w_l, idx, scales):
    """Gather the kept G columns / W rows for the local plan.

    Block-granular plans gather whole contiguous blocks (reshape + one
    block-level take — the lane-aligned slab layout the Pallas kernels use)
    instead of expanding to per-column indices; the returned ``idx`` is the
    expanded per-column index vector for the dW scatter / CompactGrad.
    """
    if lcfg.block > 1:
        bs = lcfg.block
        nb = G2d.shape[-1] // bs
        Gc = (jnp.take(G2d.reshape(-1, nb, bs), idx, axis=1)
              * scales[None, :, None].astype(G2d.dtype)).reshape(G2d.shape[0], -1)
        Wc = jnp.take(w_l.reshape(nb, bs, -1), idx, axis=0).reshape(-1, w_l.shape[-1])
        idx = (idx[:, None] * bs + jnp.arange(bs, dtype=idx.dtype)).reshape(-1)
        return Gc, Wc, idx
    Gc = jnp.take(G2d, idx, axis=1) * scales[None, :].astype(G2d.dtype)
    Wc = jnp.take(w_l, idx, axis=0)
    return Gc, Wc, idx


def _tp_sketch_bwd(spec, x, w, key, has_b, slot, want_probe, g):
    plan = spec.plan
    column = plan.kind == "tp_column"
    mesh, dp, mp = plan.mesh, plan.data_axes, plan.model_axis
    cfg = spec.cfg
    est = tp_estimator(cfg)
    assert est is not None, "TP sketched site on a non-tp_shardable backend"
    n, din = w.shape
    scatter_axis = dp[-1] if dp else None
    n_scatter = mesh.shape[scatter_axis] if scatter_axis else 1
    psum_rest = tuple(dp[:-1])
    n_mp = mesh.shape[mp]
    n_loc = n // n_mp if column else n
    din_ok = (din if column else din // n_mp) % n_scatter == 0
    with_slot = slot is not None
    din_sp = scatter_axis if (scatter_axis and din_ok) else None

    def body(g_l, x_l, w_l, key):
        # column plan: per-shard local plan — fold the (DP-shared) key with
        # the model shard index so shards sample independent column subsets.
        # row plan: g is mp-replicated, the plan must be identical on every
        # shard (same key, scores psum'ed over dp) so dX stays ff-local.
        kk = (jax.random.fold_in(key, jax.lax.axis_index(mp)) if column
              else key)
        G2d = g_l.reshape(-1, g_l.shape[-1])
        X2d = x_l.reshape(-1, x_l.shape[-1])
        lcfg = effective_cfg(cfg, G2d.shape[-1])
        cplan = _plan_via_registry(est, lcfg, G2d, w_l, kk, dp)
        idx, scales = cplan.indices, cplan.scales
        Gc, Wc, idx = _gather_compact(lcfg, G2d, w_l, idx, scales)
        dx = (Gc @ Wc).reshape(x_l.shape)
        if column:
            dx = jax.lax.psum(dx, mp)  # the standard TP backward all-reduce
        dWc = Gc.T.astype(jnp.float32) @ X2d.astype(jnp.float32)
        if psum_rest:
            dWc = jax.lax.psum(dWc, psum_rest)
        if scatter_axis and din_ok:
            # compressed DP gradient collective: reduce-scatter the COMPACT
            # block (≈ budget × dense volume) along d_in
            dWc = jax.lax.psum_scatter(dWc, scatter_axis, scatter_dimension=1,
                                       tiled=True)
        elif scatter_axis:
            dWc = jax.lax.psum(dWc, scatter_axis)
        outs = [dx]
        if with_slot:
            if column:
                # global row indices into the full [n, din] weight; the
                # compact block never gets scattered on the backward path.
                # Rows/indices are all-gathered over mp (compact volume) so
                # the optimizer's sparse-row scatter partitions
                # collective-free.
                gidx = (jax.lax.axis_index(mp) * n_loc + idx).astype(jnp.float32)
                outs += [jax.lax.all_gather(dWc, mp, axis=0, tiled=True),
                         jax.lax.all_gather(gidx, mp, axis=0, tiled=True)]
            else:
                outs += [dWc, idx.astype(jnp.float32)]
        else:
            if scatter_axis and din_ok:
                dW_l = jnp.zeros((w_l.shape[0], dWc.shape[1]), w_l.dtype)
            else:
                dW_l = jnp.zeros_like(w_l)
            outs.append(dW_l.at[idx].add(dWc.astype(w_l.dtype)))
        if has_b:
            # bias gradient folded into the same kept-column stream: db is
            # the column sums of the (rescaled) kept G columns — the exact
            # db restricted to the sketch, still unbiased (E[Ĝ|G] = G)
            db_l = jnp.zeros((w_l.shape[0],), g_l.dtype).at[idx].add(
                jnp.sum(Gc, axis=0).astype(g_l.dtype))
            if dp:
                db_l = jax.lax.psum(db_l, dp)
            outs.append(db_l)
        if want_probe:
            # per-shard probe from the rows the backward just produced:
            # ‖row_j‖² needs the full d_in extent (psum the squared partial
            # over whatever axes shard d_in here), then the 3 probe stats
            # psum over the model axis on the column plan (each shard kept
            # its own column subset; the site probe is their sum).
            rs = jnp.einsum("rd,rd->r", dWc, dWc)
            rs_axes = (() if column else (mp,)) + (
                (scatter_axis,) if (scatter_axis and din_ok) else ())
            if rs_axes:
                rs = jax.lax.psum(rs, rs_axes)
            p = jnp.take(cplan.probs, idx).astype(jnp.float32)
            v3 = rs @ jnp.stack([p, 1.0 - p, jnp.ones_like(p)], axis=-1)
            if column:
                v3 = jax.lax.psum(v3, mp)
            outs.append(jnp.concatenate([v3, jnp.ones((1,), jnp.float32)]))
        return tuple(outs)

    specs = [P(dp, None, None) if column else P(dp, None, mp)]  # dx
    if with_slot:
        rows_sp = (P(None, din_sp) if column
                   else P(None, (mp, scatter_axis) if din_sp else mp))
        specs += [rows_sp, P(None)]
    else:
        specs.append(P(mp, din_sp) if column
                     else P(None, (mp, scatter_axis) if din_sp else mp))
    if has_b:
        specs.append(P(mp) if column else P(None))
    if want_probe:
        specs.append(P(None))
    in_specs = ((P(dp, None, mp), P(dp, None, None), P(mp, None), P())
                if column else
                (P(dp, None, None), P(dp, None, mp), P(None, mp), P()))
    res = compat.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=tuple(specs))(g, x, w, key)

    it = iter(res)
    dx = next(it)
    if with_slot:
        rows, gidx = next(it), next(it)
        slot_ct = CompactGrad(rows=rows.astype(jnp.float32), idx=gidx)
        dw = jnp.zeros_like(w)
    else:
        dw, slot_ct = next(it), None
    db = next(it) if has_b else None
    probe_ct = next(it) if want_probe else None
    return dx, dw, db, None, slot_ct, probe_ct


# -- TP exact plan ------------------------------------------------------------


def _tp_exact_bwd(spec, x, w, has_b, slot, want_probe, g):
    """Explicit Megatron column-parallel EXACT backward (e.g. the vocabulary
    head, which the paper keeps exact): same shard_map structure as the
    sketched plans so the dW einsum never hits the pjit sharding conflict
    that replicates full fp32 weight gradients."""
    plan = spec.plan
    mesh, dp, mp = plan.mesh, plan.data_axes, plan.model_axis
    scatter_axis = dp[-1] if dp else None
    n_scatter = mesh.shape[scatter_axis] if scatter_axis else 1
    psum_rest = tuple(dp[:-1])
    din_ok = w.shape[1] % n_scatter == 0

    def body(g_l, x_l, w_l):
        G2d = g_l.reshape(-1, g_l.shape[-1])
        X2d = x_l.reshape(-1, x_l.shape[-1])
        dx = (G2d @ w_l).reshape(x_l.shape)
        dx = jax.lax.psum(dx, mp)
        dW = jax.lax.dot_general(G2d.astype(jnp.float32),
                                 X2d.astype(jnp.float32),
                                 (((0,), (0,)), ((), ())))
        if psum_rest:
            dW = jax.lax.psum(dW, psum_rest)
        if scatter_axis and din_ok:
            dW = jax.lax.psum_scatter(dW, scatter_axis, scatter_dimension=1,
                                      tiled=True)
        elif scatter_axis:
            dW = jax.lax.psum(dW, scatter_axis)
        outs = [dx, dW.astype(w_l.dtype)]
        if has_b:
            db_l = jnp.sum(G2d, axis=0)
            if dp:
                db_l = jax.lax.psum(db_l, dp)
            outs.append(db_l)
        return tuple(outs)

    out_w_spec = P(mp, scatter_axis if (scatter_axis and din_ok) else None)
    specs = [P(dp, None, None), out_w_spec] + ([P(mp)] if has_b else [])
    dx, dw, *rest = compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, mp), P(dp, None, None), P(mp, None)),
        out_specs=tuple(specs))(g, x, w)
    db = rest[0] if has_b else None
    probe_ct = None
    if want_probe:
        from repro.telemetry.probes import PROBE_WIDTH

        probe_ct = jnp.zeros((PROBE_WIDTH,), jnp.float32)
    # slot primal (if any) is all-zeros: returning it is its zero cotangent
    return dx, dw, db, None, slot, probe_ct
