"""Pallas TPU kernels for the block-sketched backward matmuls.

The sketch keeps ``rb`` 128-wide column *blocks* of the output-gradient matrix
G (see ``SketchConfig.block``). Because kept blocks are contiguous lane-aligned
slabs, the gather is folded into the BlockSpec index map: the kernel's DMA
engine fetches only the selected G column-blocks / W row-blocks straight from
HBM — the compacted operands are never materialised. The MXU then runs a dense
[N, rb·128] × [rb·128, d] matmul, i.e. the paper's element sparsity realised as
*shape* sparsity (DESIGN.md §3).

VMEM budget per grid step (defaults, bf16): G tile 256×128 (64 KiB) + W tile
128×256 (64 KiB) + fp32 acc 256×256 (256 KiB) ≈ 0.4 MiB, double buffering
included well below the 16 MiB scoped-VMEM limit the v5e compiler enforces
by default. The fused kernels keep larger accumulators resident; see
``fused_vmem_bytes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_gather_matmul", "block_gather_matmul_dw",
           "block_gather_matmul_fused", "block_stream_matmul_fused",
           "fused_vmem_bytes", "stream_vmem_bytes"]


def _dx_kernel(idx_ref, scale_ref, g_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sc = scale_ref[k]
    g = g_ref[...].astype(jnp.float32) * sc
    acc_ref[...] += jax.lax.dot(g, w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tile_n", "tile_d", "interpret"))
def block_gather_matmul(G, block_idx, scales, W, *, block: int = 128,
                        tile_n: int = 256, tile_d: int = 256, interpret: bool = False):
    """dX = Σ_k scale_k · G[:, blk_k] @ W[blk_k, :].

    G: [N, n]; block_idx: [rb] int32 (ascending block ids); scales: [rb] f32;
    W: [n, d]. Returns [N, d] in G.dtype. N, d padded internally to tiles.
    """
    N, n = G.shape
    d = W.shape[1]
    rb = block_idx.shape[0]
    tn = min(tile_n, max(8, N))
    td = min(tile_d, d)
    Np = -(-N // tn) * tn
    dp = -(-d // td) * td
    if Np != N:
        G = jnp.pad(G, ((0, Np - N), (0, 0)))
    if dp != d:
        W = jnp.pad(W, ((0, 0), (0, dp - d)))

    grid = (Np // tn, dp // td, rb)
    out = pl.pallas_call(
        functools.partial(_dx_kernel, n_k=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tn, block), lambda i, j, k, idx, sc: (i, idx[k])),
                pl.BlockSpec((block, td), lambda i, j, k, idx, sc: (idx[k], j)),
            ],
            out_specs=pl.BlockSpec((tn, td), lambda i, j, k, idx, sc: (i, j)),
            scratch_shapes=[pltpu.VMEM((tn, td), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Np, dp), G.dtype),
        interpret=interpret,
        name="block_gather_matmul_dx",
    )(block_idx, scales.astype(jnp.float32), G, W)
    return out[:N, :d]


def _dw_kernel(idx_ref, scale_ref, g_ref, x_ref, o_ref, acc_ref, *, n_i: int):
    i = pl.program_id(2)
    k = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # scale G up front (not the accumulator at the end) so the accumulation
    # order is bit-identical to the fused kernel, which shares one scaled G
    # tile between the dX and dW products.
    g = g_ref[...].astype(jnp.float32) * scale_ref[k]
    # contract over the N tile: gᵀ @ x without an explicit transpose
    acc_ref[...] += jax.lax.dot_general(
        g, x_ref[...].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tile_n", "tile_d", "interpret"))
def block_gather_matmul_dw(G, block_idx, scales, X, *, block: int = 128,
                           tile_n: int = 256, tile_d: int = 256, interpret: bool = False):
    """dWc[k] = scale_k · G[:, blk_k]ᵀ @ X  ->  [rb, block, d_in].

    The caller scatters the compact rows into the full dW (indices are shared
    across DP replicas, enabling the compressed all-reduce — DESIGN.md §3).
    """
    N, n = G.shape
    din = X.shape[1]
    rb = block_idx.shape[0]
    tn = min(tile_n, max(8, N))
    td = min(tile_d, din)
    Np = -(-N // tn) * tn
    dp = -(-din // td) * td
    if Np != N:
        G = jnp.pad(G, ((0, Np - N), (0, 0)))
        X = jnp.pad(X, ((0, Np - N), (0, 0)))
    if dp != din:
        X = jnp.pad(X, ((0, 0), (0, dp - din)))

    grid = (rb, dp // td, Np // tn)
    out = pl.pallas_call(
        functools.partial(_dw_kernel, n_i=Np // tn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tn, block), lambda k, j, i, idx, sc: (i, idx[k])),
                pl.BlockSpec((tn, td), lambda k, j, i, idx, sc: (i, j)),
            ],
            out_specs=pl.BlockSpec((1, block, td), lambda k, j, i, idx, sc: (k, 0, j)),
            scratch_shapes=[pltpu.VMEM((block, td), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rb, block, dp), G.dtype),
        interpret=interpret,
        name="block_gather_matmul_dw",
    )(block_idx, scales.astype(jnp.float32), G, X)
    return out[:, :, :din]


# ---------------------------------------------------------------------------
# One-pass fused backward: dX, compact dW and compact db from a single
# stream of G's kept column-blocks.
# ---------------------------------------------------------------------------


def _fused_kernel(idx_ref, scale_ref, g_ref, w_ref, x_ref, *refs,
                  n_i: int, n_k: int, n_j: int, td: int,
                  with_scores: bool = False, score_mode: str = "l1"):
    if with_scores:
        o_dx, o_dw, o_db, o_s, acc_dx, acc_dw, acc_db, acc_s = refs
    else:
        o_dx, o_dw, o_db, acc_dx, acc_dw, acc_db = refs
        o_s = acc_s = None
    i, k, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # one scaled G tile feeds both MXU products and the db reduction; the raw
    # (pre-scale) tile additionally feeds the score refresh when requested
    graw = g_ref[...].astype(jnp.float32)
    g = graw * scale_ref[k]

    @pl.when(jnp.logical_and(i == 0, jnp.logical_and(k == 0, j == 0)))
    def _():
        acc_dw[...] = jnp.zeros_like(acc_dw)
        acc_db[...] = jnp.zeros_like(acc_db)
        if with_scores:
            acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(jnp.logical_and(k == 0, j == 0))
    def _():
        acc_dx[...] = jnp.zeros_like(acc_dx)

    jsl = pl.ds(j * td, td)
    acc_dx[:, jsl] += jax.lax.dot(g, w_ref[...].astype(jnp.float32),
                                  preferred_element_type=jnp.float32)
    acc_dw[k, :, jsl] += jax.lax.dot_general(
        g, x_ref[...].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        acc_db[k, :] += jnp.sum(g, axis=0)
        if with_scores:
            v = jnp.abs(graw) if score_mode == "l1" else jnp.square(graw)
            acc_s[k, :] += jnp.sum(v, axis=0)

    @pl.when(k == n_k - 1)
    def _():
        o_dx[:, jsl] = acc_dx[:, jsl].astype(o_dx.dtype)

    @pl.when(jnp.logical_and(i == n_i - 1,
                             jnp.logical_and(k == n_k - 1, j == n_j - 1)))
    def _():
        o_dw[...] = acc_dw[...].astype(o_dw.dtype)
        o_db[...] = acc_db[...]
        if with_scores:
            o_s[...] = acc_s[...]


@functools.partial(jax.jit, static_argnames=("block", "tile_n", "tile_d",
                                             "interpret", "with_scores",
                                             "score_mode"))
def block_gather_matmul_fused(G, block_idx, scales, W, X, *, block: int = 128,
                              tile_n: int = 256, tile_d: int = 256,
                              interpret: bool = False,
                              with_scores: bool = False,
                              score_mode: str = "l1"):
    """Fused one-pass backward for a block-sketched linear site.

        dX     = Σ_k scale_k · G[:, blk_k] @ W[blk_k, :]      [N, d]
        dWc[k] = scale_k · G[:, blk_k]ᵀ @ X                   [rb, block, d]
        db_c[k] = scale_k · Σ_rows G[:, blk_k]                [rb, block] f32

    G: [N, n]; block_idx: [rb] int32; scales: [rb] f32; W: [n, d]; X: [N, d].
    Each kept G column-block is DMA'd into VMEM exactly once per row tile —
    the G index map is constant over the inner d-tile sweep, so the whole
    backward makes ONE HBM pass over the kept part of G (vs one per output
    per d-tile for the unfused pair). The price is residency: the f32
    accumulators for a [tn, d] dX row panel and the full [rb·block, d]
    compact dW live in VMEM for the whole call — see ``fused_vmem_bytes``;
    the ops dispatcher falls back to the unfused pair when it doesn't fit.

    Accumulation order (ascending k for dX, ascending row tiles for dWc,
    scaled-G operands) matches ``block_gather_matmul`` /
    ``block_gather_matmul_dw`` exactly, so fused and unfused are
    bit-identical for the same plan.

    ``with_scores=True`` additionally emits the raw (pre-scale) column score
    reduction of the KEPT blocks — Σ_rows |G| (``score_mode="l1"``) or
    Σ_rows G² (``"l2"``) as a 4th output [rb, block] f32 — from the same G
    tiles already resident for the matmuls, i.e. a free partial score
    refresh for the stale-plan estimator. The first three outputs are
    bit-identical with the flag on or off.
    """
    N, n = G.shape
    d = W.shape[1]
    assert X.shape[1] == d, (X.shape, W.shape)
    rb = block_idx.shape[0]
    tn = min(tile_n, max(8, N))
    td = min(tile_d, d)
    Np = -(-N // tn) * tn
    dp = -(-d // td) * td
    if Np != N:
        G = jnp.pad(G, ((0, Np - N), (0, 0)))
        X = jnp.pad(X, ((0, Np - N), (0, 0)))
    if dp != d:
        W = jnp.pad(W, ((0, 0), (0, dp - d)))
        X = jnp.pad(X, ((0, 0), (0, dp - d)))

    n_i, n_j = Np // tn, dp // td
    grid = (n_i, rb, n_j)
    out_specs = [
        pl.BlockSpec((tn, dp), lambda i, k, j, idx, sc: (i, 0)),
        pl.BlockSpec((rb, block, dp), lambda i, k, j, idx, sc: (0, 0, 0)),
        pl.BlockSpec((rb, block), lambda i, k, j, idx, sc: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Np, dp), G.dtype),
        jax.ShapeDtypeStruct((rb, block, dp), G.dtype),
        jax.ShapeDtypeStruct((rb, block), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((tn, dp), jnp.float32),
        pltpu.VMEM((rb, block, dp), jnp.float32),
        pltpu.VMEM((rb, block), jnp.float32),
    ]
    if with_scores is True:  # static flag (static_argnames), not a tracer
        out_specs.append(pl.BlockSpec((rb, block), lambda i, k, j, idx, sc: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((rb, block), jnp.float32))
        scratch.append(pltpu.VMEM((rb, block), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_fused_kernel, n_i=n_i, n_k=rb, n_j=n_j, td=td,
                          with_scores=with_scores, score_mode=score_mode),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tn, block), lambda i, k, j, idx, sc: (i, idx[k])),
                pl.BlockSpec((block, td), lambda i, k, j, idx, sc: (idx[k], j)),
                pl.BlockSpec((tn, td), lambda i, k, j, idx, sc: (i, j)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="block_gather_matmul_fused",
    )(block_idx, scales.astype(jnp.float32), G, W, X)
    dX, dWc, db = outs[0][:N, :d], outs[1][:, :, :d], outs[2]
    if with_scores is True:  # static flag (static_argnames), not a tracer
        return dX, dWc, db, outs[3]
    return dX, dWc, db


def fused_vmem_bytes(N: int, d: int, rb: int, block: int, itemsize: int,
                     tile_n: int = 256, tile_d: int = 256,
                     with_scores: bool = False) -> int:
    """Scoped-VMEM bytes the TPU compiler allocates for
    ``block_gather_matmul_fused``, counted the way it counts them: the f32
    accumulators, the double-buffered input tiles, the dX row-panel output
    double-buffered (its block moves with the row tile), and the compact
    dW / db (/ scores) outputs once (their block never moves). On a v5e
    this reproduces the compiler's "Scoped allocation" figure for kernels
    whose outputs leave through HBM (tests/test_tpu_compile.py). XLA may
    instead place small outputs in VMEM proper, and then needs less; the
    dispatcher cannot see that whole-program choice, so it budgets for the
    HBM case."""
    tn = min(tile_n, max(8, N))
    td = min(tile_d, d)
    dp = -(-d // td) * td
    rbp = -(-rb // 8) * 8  # an f32 [rb, block] tile pads rows to 8 sublanes
    small = 4 * rbp * block * (2 if with_scores else 1)  # db (+ scores)
    acc = 4 * (tn * dp + rb * block * dp) + small
    tiles = 2 * itemsize * (tn * block + block * td + tn * td)
    outs = itemsize * (2 * tn * dp + rb * block * dp) + small
    return acc + tiles + outs


# ---------------------------------------------------------------------------
# Streaming one-pass backward: ALL of G streams through VMEM once; kept
# blocks feed dX/compact-dW/db through per-block gates while EVERY block's
# fresh column scores are reduced in the same sweep — the separate
# col_scores pass no longer exists.
# ---------------------------------------------------------------------------


def _stream_kernel(gate_ref, slot_ref, g_ref, w_ref, x_ref,
                   o_dx, o_dw, o_db, o_s, acc_dx, acc_dw, acc_db, acc_s,
                   *, n_i: int, n_k: int, n_j: int, td: int, score_mode: str):
    i, k, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    graw = g_ref[...].astype(jnp.float32)
    sc = gate_ref[k]      # 0.0 for dropped blocks, the 1/p scale for kept
    slot = slot_ref[k]    # compact slot of block k (0 for dropped; unused)

    @pl.when(jnp.logical_and(i == 0, jnp.logical_and(k == 0, j == 0)))
    def _():
        acc_dw[...] = jnp.zeros_like(acc_dw)
        acc_db[...] = jnp.zeros_like(acc_db)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(jnp.logical_and(k == 0, j == 0))
    def _():
        acc_dx[...] = jnp.zeros_like(acc_dx)

    jsl = pl.ds(j * td, td)

    # fresh scores for EVERY block, from the raw tile, once per (i, k)
    @pl.when(j == 0)
    def _():
        v = jnp.abs(graw) if score_mode == "l1" else jnp.square(graw)
        acc_s[k, :] += jnp.sum(v, axis=0)

    # gated contributions: dropped blocks skip both MXU products entirely,
    # so the accumulation sequence over kept blocks (ascending block id =
    # ascending slot) is exactly the fused kernel's — bit-identical outputs
    # for the same keep decisions. One scaled tile feeds the dots AND the db
    # reduction, as in the fused kernel: recomputing the product inside the
    # reduction lets the compiler fuse it there and round differently.
    g = graw * sc

    @pl.when(sc > 0)
    def _():
        acc_dx[:, jsl] += jax.lax.dot(g, w_ref[...].astype(jnp.float32),
                                      preferred_element_type=jnp.float32)
        acc_dw[slot, :, jsl] += jax.lax.dot_general(
            g, x_ref[...].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(sc > 0, j == 0))
    def _():
        acc_db[slot, :] += jnp.sum(g, axis=0)

    @pl.when(k == n_k - 1)
    def _():
        o_dx[:, jsl] = acc_dx[:, jsl].astype(o_dx.dtype)

    @pl.when(jnp.logical_and(i == n_i - 1,
                             jnp.logical_and(k == n_k - 1, j == n_j - 1)))
    def _():
        o_dw[...] = acc_dw[...].astype(o_dw.dtype)
        o_db[...] = acc_db[...]
        o_s[...] = acc_s[...]


@functools.partial(jax.jit, static_argnames=("rb", "block", "tile_n", "tile_d",
                                             "score_mode", "interpret"))
def block_stream_matmul_fused(G, gates, slot_map, W, X, *, rb: int,
                              block: int = 128, tile_n: int = 256,
                              tile_d: int = 256, score_mode: str = "l1",
                              interpret: bool = False):
    """Streaming selection backward: ONE HBM pass over ALL of G.

    Every 128-wide column block of G streams through VMEM exactly once per
    row tile. Kept blocks (``gates[k] > 0``) are scaled by their gate and
    accumulated into dX / compact dW / compact db at compact slot
    ``slot_map[k]``; every block — kept or dropped — contributes its raw
    column score reduction (Σ|G| or ΣG² per ``score_mode``) to a fresh [n]
    score vector. The separate score/plan pass over G disappears: selection
    is evaluated online as G streams by, against gates sampled from the
    carried previous-step scores (see ``core/sketched_linear`` "onepass").

    G: [N, n]; gates: [nb] f32 (nb = n // block; 0 = dropped, else 1/p
    scale); slot_map: [nb] int32 (compact slot per kept block, ascending
    over kept blocks); W: [n, d]; X: [N, d]; rb: number of kept blocks
    (static). Returns (dX [N, d], dWc [rb, block, d], db_c [rb, block] f32,
    scores [n] f32).

    Given identical keep decisions, dX/dWc/db are bit-identical to
    ``block_gather_matmul_fused``: the kept-block accumulation order and
    operands are the same; dropped blocks only touch the score reduction.
    The extra HBM cost over the fused gather is the dropped part of G and
    the full (not kept-only) W row stream — see docs/perf.md for the
    traffic table.
    """
    N, n = G.shape
    d = W.shape[1]
    assert X.shape[1] == d, (X.shape, W.shape)
    nb = n // block
    assert nb * block == n, (n, block)
    assert gates.shape == (nb,) and slot_map.shape == (nb,), (gates.shape, nb)
    tn = min(tile_n, max(8, N))
    td = min(tile_d, d)
    Np = -(-N // tn) * tn
    dp = -(-d // td) * td
    if Np != N:
        G = jnp.pad(G, ((0, Np - N), (0, 0)))
        X = jnp.pad(X, ((0, Np - N), (0, 0)))
    if dp != d:
        W = jnp.pad(W, ((0, 0), (0, dp - d)))
        X = jnp.pad(X, ((0, 0), (0, dp - d)))

    n_i, n_j = Np // tn, dp // td
    grid = (n_i, nb, n_j)
    dX, dWc, db, s = pl.pallas_call(
        functools.partial(_stream_kernel, n_i=n_i, n_k=nb, n_j=n_j, td=td,
                          score_mode=score_mode),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tn, block), lambda i, k, j, gt, sl: (i, k)),
                pl.BlockSpec((block, td), lambda i, k, j, gt, sl: (k, j)),
                pl.BlockSpec((tn, td), lambda i, k, j, gt, sl: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((tn, dp), lambda i, k, j, gt, sl: (i, 0)),
                pl.BlockSpec((rb, block, dp), lambda i, k, j, gt, sl: (0, 0, 0)),
                pl.BlockSpec((rb, block), lambda i, k, j, gt, sl: (0, 0)),
                pl.BlockSpec((nb, block), lambda i, k, j, gt, sl: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((tn, dp), jnp.float32),
                pltpu.VMEM((rb, block, dp), jnp.float32),
                pltpu.VMEM((rb, block), jnp.float32),
                pltpu.VMEM((nb, block), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Np, dp), G.dtype),
            jax.ShapeDtypeStruct((rb, block, dp), G.dtype),
            jax.ShapeDtypeStruct((rb, block), jnp.float32),
            jax.ShapeDtypeStruct((nb, block), jnp.float32),
        ],
        interpret=interpret,
        name="block_stream_matmul_fused",
    )(gates.astype(jnp.float32), slot_map.astype(jnp.int32), G, W, X)
    return dX[:N, :d], dWc[:, :, :d], db, s.reshape(n)


def stream_vmem_bytes(N: int, d: int, rb: int, nb: int, block: int,
                      itemsize: int, tile_n: int = 256,
                      tile_d: int = 256) -> int:
    """Scoped-VMEM bytes for ``block_stream_matmul_fused``: the fused
    kernel's (see :func:`fused_vmem_bytes`) plus the [nb, block] f32 score
    accumulator and its output."""
    nbp = -(-nb // 8) * 8
    return (fused_vmem_bytes(N, d, rb, block, itemsize,
                             tile_n=tile_n, tile_d=tile_d)
            + 2 * 4 * nbp * block)
