"""Unbiased randomized VJP sketches (paper §3–4).

The central object is :class:`SketchConfig` (static / hashable — safe to close
over in ``jax.jit``) plus pure functions that turn an output-gradient matrix
``G`` (shape ``[N, d_out]``, practical row convention of App. C) into an
unbiased surrogate ``Ĝ`` with ``E[Ĝ | G] = G``.

Two execution *backends* realise the same estimator:

* ``mask``    — paper-faithful (Alg. 3–6): full-size ``Ĝ`` with zeroed and
                rescaled columns; dense downstream matmuls.
* ``compact`` — beyond-paper TPU adaptation (DESIGN.md §3): exact-r correlated
                sampling guarantees a *static* keep count ``r``, so we gather
                the kept columns and run reduced-shape matmuls (optionally via
                Pallas kernels, backend ``pallas``).

Method families
---------------
uniform masks (§4.1):  ``per_element``, ``per_column``, ``per_sample``
data-dependent (§4.2): ``l1``, ``l2``, ``var``, ``ds``, ``gsv`` (+ ``_sq``),
spectral (Prop. 3.3):  ``rcs``
and ``none`` (exact backprop).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import solver
from repro.core.scores import SCORE_METHODS, column_scores
from repro.obs import scopes

__all__ = [
    "SketchConfig",
    "ColumnPlan",
    "COLUMN_METHODS",
    "ALL_METHODS",
    "static_rank",
    "column_plan",
    "column_plan_from_scores",
    "column_gate",
    "apply_rcs",
    "sketch_dense",
]

COLUMN_METHODS = ("per_column",) + SCORE_METHODS
ALL_METHODS = ("none", "per_element", "per_sample", "rcs") + COLUMN_METHODS


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Static configuration of one sketched VJP site.

    Attributes:
      method: one of :data:`ALL_METHODS`.
      budget: fraction ``p ∈ (0, 1]`` of coordinates kept (in expectation for
        independent sampling; exactly for correlated sampling).
      exact_r: correlated exact-r Bernoulli sampling (Lemma 3.1; paper default
        after Fig. 1a) vs independent gates (Lemma 3.4).
      backend: ``mask`` | ``compact`` | ``pallas``, or any additional
        estimator registered via ``repro.api.register_estimator``
        (see core/estimators.py).
      round_to: round the static keep-count ``r`` *up* to a multiple (128 keeps
        compact matmuls MXU/lane aligned on TPU; 1 = paper-faithful count).
      block: column-block granularity. 0/1 = per-column (paper-faithful).
        >1 (e.g. 128) samples whole column *blocks*: scores are pooled per
        block and the convex program runs over blocks. Structured variant for
        TPU — a kept block is a contiguous, lane-aligned slab, so the Pallas
        backward kernels gather it straight from HBM via BlockSpec index maps
        (DESIGN.md §3). Slightly coarser variance for the same budget; the
        trade-off is benchmarked in benchmarks/bench_block_granularity.py.
      ridge: relative ridge added to Γ_B for the RCS inverse square root.
    """

    method: str = "l1"
    budget: float = 0.1
    exact_r: bool = True
    backend: str = "mask"
    round_to: int = 1
    block: int = 0
    ridge: float = 1e-5

    def __post_init__(self):
        if self.method not in ALL_METHODS:
            raise ValueError(f"unknown sketch method {self.method!r}")
        if not (0.0 < self.budget <= 1.0):
            raise ValueError(f"budget must be in (0, 1], got {self.budget}")
        if self.backend in ("mask", "compact", "pallas"):
            # builtin backends: static checks (registered in sketched_linear,
            # which may still be mid-import when presets are built)
            if self.backend in ("compact", "pallas") and self.method not in COLUMN_METHODS:
                raise ValueError(
                    f"backend {self.backend!r} requires a column-family method, got {self.method!r}")
            if self.backend in ("compact", "pallas") and not self.exact_r:
                raise ValueError("compact/pallas backends need exact_r=True (static shapes)")
        else:
            # open registry: any estimator registered via
            # repro.api.register_estimator is a valid backend
            from repro.core import estimators as _est

            try:
                est = _est.get_estimator(self.backend)
            except KeyError as e:
                raise ValueError(str(e)) from None
            est.validate(self)

    @property
    def is_noop(self) -> bool:
        return self.method == "none" or self.budget >= 1.0


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def static_rank(cfg: SketchConfig, n: int) -> int:
    """Static keep-count r for a node with n output coordinates."""
    r = max(1, int(round(cfg.budget * n)))
    r = min(n, _round_up(r, max(1, cfg.round_to)))
    return r


def static_block_rank(cfg: SketchConfig, n: int) -> int:
    """Static number of kept column-*blocks* (block-granular sketches)."""
    assert cfg.block > 1 and n % cfg.block == 0, (n, cfg.block)
    nb = n // cfg.block
    return max(1, min(nb, int(round(cfg.budget * nb))))


def effective_cfg(cfg: SketchConfig, n: int) -> SketchConfig:
    """Degrade block-granular configs gracefully on sites whose width does
    not divide the block (tiny smoke configs, odd head dims): fall back to
    per-column granularity — same estimator family, still unbiased."""
    if cfg.block > 1 and (n < cfg.block or n % cfg.block != 0):
        return dataclasses.replace(cfg, block=0)
    return cfg


@dataclasses.dataclass
class ColumnPlan:
    """A sampled column sketch: either compact (indices) or dense gate."""

    indices: Optional[jax.Array]  # [r] int32, ascending (exact-r only)
    scales: Optional[jax.Array]  # [r] f32: 1/p at kept columns
    gate: Optional[jax.Array]  # [n] f32: z_i/p_i (dense mask-and-rescale)
    probs: jax.Array  # [n] f32 marginals (diagnostics / tests)


@scopes.scoped(scopes.SCORE)
def _proxy_scores(cfg: SketchConfig, G2d: jax.Array, W: Optional[jax.Array]) -> jax.Array:
    """Column proxy scores, routed through the Pallas reduction kernel for the
    ℓ1/ℓ2 families on the pallas backend (one streaming HBM pass over G with
    fp32 accumulation) and through the jnp scores otherwise."""
    base = cfg.method[:-3] if cfg.method.endswith("_sq") else cfg.method
    if cfg.backend == "pallas" and base in ("l1", "l2"):
        from repro.kernels import ops as kops

        if base == "l1":
            s = kops.col_l1_scores(G2d, mode="l1")
        else:
            s = jnp.sqrt(kops.col_l1_scores(G2d, mode="l2"))
        return jnp.square(s) if cfg.method.endswith("_sq") else s
    return column_scores(cfg.method, G2d, W)


def _column_probs(cfg: SketchConfig, G2d: jax.Array, W: Optional[jax.Array], r: int,
                  score_psum_axes=None) -> jax.Array:
    n = G2d.shape[-1]
    if cfg.method == "per_column":
        return jnp.full((n,), jnp.float32(r) / n)
    s = _proxy_scores(cfg, G2d, W)
    if score_psum_axes:
        # distributed batch: pool scores across data shards so every replica
        # plans the SAME sketch (required for the compressed gradient
        # collective, and matches the paper's batch-shared R)
        s = jax.lax.psum(s, score_psum_axes)
    w = jnp.square(s)  # probabilities ∝ s  ⇔  weights w = s²  (Eq. 23)
    return solver.optimal_probabilities(w, r)


@scopes.scoped(scopes.PLAN)
def column_plan(
    cfg: SketchConfig,
    G2d: jax.Array,
    W: Optional[jax.Array],
    key: jax.Array,
    *,
    want_compact: bool,
    score_psum_axes=None,
) -> ColumnPlan:
    """Sample a column sketch for gradient matrix ``G2d`` ([N, n]).

    With ``cfg.block > 1`` the plan is block-granular: ``indices``/``scales``
    refer to column *blocks* and ``gate`` (when materialised) is expanded back
    to per-column size.
    """
    n = G2d.shape[-1]
    cfg = effective_cfg(cfg, n)
    if cfg.block > 1:
        return _block_plan(cfg, G2d, W, key, want_compact=want_compact,
                           score_psum_axes=score_psum_axes)
    r = static_rank(cfg, n)
    p = _column_probs(cfg, G2d, W, r, score_psum_axes)
    if r >= n:
        ones = jnp.ones((n,), jnp.float32)
        idx = jnp.arange(n, dtype=jnp.int32)
        return ColumnPlan(indices=idx, scales=ones, gate=ones, probs=ones)
    if cfg.exact_r:
        idx = solver.sample_exact_r(key, p, r)
        inv_p_sel = 1.0 / jnp.maximum(jnp.take(p, idx), 1e-20)
        if want_compact:
            return ColumnPlan(indices=idx, scales=inv_p_sel, gate=None, probs=p)
        gate = jnp.zeros((n,), jnp.float32).at[idx].set(inv_p_sel)
        return ColumnPlan(indices=idx, scales=inv_p_sel, gate=gate, probs=p)
    z = solver.sample_independent(key, p)
    gate = z / jnp.maximum(p, 1e-20)
    return ColumnPlan(indices=None, scales=None, gate=gate, probs=p)


def _block_plan(cfg: SketchConfig, G2d, W, key, *, want_compact: bool,
                score_psum_axes=None) -> ColumnPlan:
    """Block-granular column sketch: pool scores per block, sample blocks.

    Unbiasedness is inherited coordinate-wise: every column in a kept block is
    rescaled by 1/p_block and E[z_b/p_b] = 1.
    """
    n = G2d.shape[-1]
    bs = cfg.block
    nb = n // bs
    rb = static_block_rank(cfg, n)
    if cfg.method == "per_column":
        p = jnp.full((nb,), jnp.float32(rb) / nb)
    else:
        s = _proxy_scores(cfg, G2d, W)
        if score_psum_axes:
            s = jax.lax.psum(s, score_psum_axes)
        # pool proxy *weights* (w = s²) per block, probabilities ∝ sqrt(pool)
        w_blk = jnp.sum(jnp.square(s).reshape(nb, bs), axis=-1)
        p = solver.optimal_probabilities(w_blk, rb)
    if rb >= nb:
        ones = jnp.ones((n,), jnp.float32)
        return ColumnPlan(indices=jnp.arange(nb, dtype=jnp.int32),
                          scales=jnp.ones((nb,), jnp.float32), gate=ones, probs=ones)
    idx = solver.sample_exact_r(key, p, rb)
    inv_p_sel = 1.0 / jnp.maximum(jnp.take(p, idx), 1e-20)
    probs_cols = jnp.repeat(p, bs)
    if want_compact:
        return ColumnPlan(indices=idx, scales=inv_p_sel, gate=None, probs=probs_cols)
    gate_blk = jnp.zeros((nb,), jnp.float32).at[idx].set(inv_p_sel)
    gate = jnp.repeat(gate_blk, bs)
    return ColumnPlan(indices=idx, scales=inv_p_sel, gate=gate, probs=probs_cols)


def _weights_from_scores(scores: jax.Array) -> jax.Array:
    """Convex-program weights from precomputed proxy scores: w = s², with an
    all-zero guard (uniform) so the sampler's marginals stay well-defined for
    any carried state. ``optimal_probabilities`` then adds its own relative
    floor, keeping every p_i strictly positive — the property that makes a
    plan sampled from STALE scores still conditionally unbiased (staleness
    can only inflate variance, never zero out a coordinate's probability)."""
    w = jnp.square(scores.astype(jnp.float32))
    return jnp.where(jnp.sum(w) > 0, w, jnp.ones_like(w))


@scopes.scoped(scopes.PLAN)
def column_plan_from_scores(cfg: SketchConfig, scores: jax.Array,
                            key: jax.Array, *,
                            want_compact: bool = True) -> ColumnPlan:
    """Sample a column sketch from PRECOMPUTED per-column proxy scores — no
    read of G. This is the planning half of the one-pass backward paths:
    the carry estimators feed it the previous step's scores (O(n) state), so
    the only G traffic left is the backward kernel's own single sweep.

    ``scores`` must follow :func:`repro.core.scores.column_scores` semantics
    for ``cfg.method`` ([n] f32, non-negative). Requires ``exact_r`` (the
    carry paths need static compact shapes).
    """
    n = scores.shape[-1]
    cfg = effective_cfg(cfg, n)
    if not cfg.exact_r:
        raise ValueError("column_plan_from_scores requires exact_r=True")
    if cfg.block > 1:
        bs = cfg.block
        nb = n // bs
        rb = static_block_rank(cfg, n)
        w_blk = jnp.sum(_weights_from_scores(scores).reshape(nb, bs), axis=-1)
        w_blk = jnp.where(jnp.sum(w_blk) > 0, w_blk, jnp.ones_like(w_blk))
        p = solver.optimal_probabilities(w_blk, rb)
        if rb >= nb:
            ones = jnp.ones((n,), jnp.float32)
            return ColumnPlan(indices=jnp.arange(nb, dtype=jnp.int32),
                              scales=jnp.ones((nb,), jnp.float32),
                              gate=ones, probs=ones)
        idx = solver.sample_exact_r(key, p, rb)
        inv_p_sel = 1.0 / jnp.maximum(jnp.take(p, idx), 1e-20)
        probs_cols = jnp.repeat(p, bs)
        if want_compact:
            return ColumnPlan(indices=idx, scales=inv_p_sel, gate=None,
                              probs=probs_cols)
        gate = jnp.repeat(
            jnp.zeros((nb,), jnp.float32).at[idx].set(inv_p_sel), bs)
        return ColumnPlan(indices=idx, scales=inv_p_sel, gate=gate,
                          probs=probs_cols)
    r = static_rank(cfg, n)
    p = solver.optimal_probabilities(_weights_from_scores(scores), r)
    if r >= n:
        ones = jnp.ones((n,), jnp.float32)
        idx = jnp.arange(n, dtype=jnp.int32)
        return ColumnPlan(indices=idx, scales=ones, gate=ones, probs=ones)
    idx = solver.sample_exact_r(key, p, r)
    inv_p_sel = 1.0 / jnp.maximum(jnp.take(p, idx), 1e-20)
    if want_compact:
        return ColumnPlan(indices=idx, scales=inv_p_sel, gate=None, probs=p)
    gate = jnp.zeros((n,), jnp.float32).at[idx].set(inv_p_sel)
    return ColumnPlan(indices=idx, scales=inv_p_sel, gate=gate, probs=p)


def column_gate(cfg: SketchConfig, G2d, W, key) -> jax.Array:
    """Dense ``[n]`` gate (z/p) for mask-backend column methods."""
    return column_plan(cfg, G2d, W, key, want_compact=False).gate


# ---------------------------------------------------------------------------
# RCS — Rank-Constrained Sketch (Prop. 3.3), factored low-rank application.
# ---------------------------------------------------------------------------


def _sym_sqrt_invsqrt(gamma: jax.Array, ridge: float):
    evals, evecs = jnp.linalg.eigh(gamma)
    floor = ridge * jnp.maximum(jnp.mean(evals), 1e-30)
    evals = jnp.maximum(evals, floor)
    s = jnp.sqrt(evals)
    half = (evecs * s) @ evecs.T
    inv_half = (evecs / s) @ evecs.T
    return half, inv_half


def apply_rcs(cfg: SketchConfig, G2d: jax.Array, W: jax.Array, key: jax.Array) -> jax.Array:
    """Ĝ = G R*ᵀ with R* from Prop. 3.3 (minimal-distortion rank-r sketch).

    Factored as Ĝ = ((G Γ^{-1/2}) U_sel ⊙ d_sel) (U_selᵀ Γ^{1/2}) —
    O(N n r + n² r) instead of materialising the n×n operator.
    """
    N, n = G2d.shape
    r = static_rank(cfg, n)
    Gf = G2d.astype(jnp.float32)
    gamma = (Gf.T @ Gf) / N
    half, inv_half = _sym_sqrt_invsqrt(gamma, cfg.ridge)
    # A = Γ^{1/2} (W Wᵀ) Γ^{1/2};   (JᵀJ = W Wᵀ in the row convention)
    Wf = W.astype(jnp.float32)
    WWt = Wf @ Wf.T
    A = half @ WWt @ half
    evals, U = jnp.linalg.eigh(A)  # ascending
    sigma_sq = jnp.maximum(evals, 0.0)
    p = solver.optimal_probabilities(sigma_sq, r)
    if r >= n:
        return G2d
    idx = solver.sample_exact_r(key, p, r)
    d_sel = 1.0 / jnp.maximum(jnp.take(p, idx), 1e-20)  # z/p on kept dirs
    U_sel = jnp.take(U, idx, axis=1)  # [n, r]
    T1 = inv_half @ U_sel  # [n, r]
    T2 = U_sel.T @ half  # [r, n]
    Ghat = ((Gf @ T1) * d_sel[None, :]) @ T2
    return Ghat.astype(G2d.dtype)


# ---------------------------------------------------------------------------
# Dense (mask-backend) sketch application — paper-faithful semantics.
# ---------------------------------------------------------------------------


def sketch_dense(cfg: SketchConfig, G2d: jax.Array, W: Optional[jax.Array], key: jax.Array) -> jax.Array:
    """Return the full-size unbiased surrogate Ĝ (E[Ĝ|G] = G).

    ``per_element`` is *not* handled here (it masks W and X, not G — Alg. 3);
    the sketched-linear backward special-cases it.
    """
    if cfg.is_noop:
        return G2d
    N, n = G2d.shape
    if cfg.method == "per_sample":
        # Alg. 4: Bernoulli gate per (flattened) sample row.
        z = jax.random.bernoulli(key, cfg.budget, (N,)).astype(G2d.dtype)
        return G2d * (z / cfg.budget)[:, None]
    if cfg.method == "rcs":
        if W is None:
            raise ValueError("RCS requires the layer weight W")
        return apply_rcs(cfg, G2d, W, key)
    gate = column_gate(cfg, G2d, W, key)
    return G2d * gate[None, :].astype(G2d.dtype)
