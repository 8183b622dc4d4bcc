"""Pallas TPU kernel: grouped posit GEMM — the MoE expert hot path.

`posit_grouped_gemm(x_sorted, w_experts, group_offsets)` multiplies rows of
an expert-sorted activation matrix by *their own group's* weight matrix:

    out[r] = x_sorted[r] @ w_experts[g]   for group_offsets[g] <= r <
                                              group_offsets[g + 1]

This is the sort-based-routing replacement for the GShard one-hot dispatch
(models/moe.py): tokens are argsorted by expert, the per-expert segment
offsets come in as a scalar-prefetched table (the same idiom as the paged
page-table prefetch in kernels/flash_attention.py), and the BlockSpec index
maps stream **only the experts that own at least one row** from HBM — an
inactive expert's [d_model, d_ff] posit block never leaves HBM, and the
full [E, d_model, d_ff] f32 decode the one-hot path performs never exists.
Posit weight tiles decode to exact f32 in VMEM right in front of the MXU
(stage (i) of posit_gemm), and each group accumulates in a f32 scratch —
the PERCIVAL-style quire-per-accumulation analogue (arXiv:2111.15286)
mapped onto the MXU epilogue.

Ragged groups are native: group sizes are arbitrary (including zero), so
the capacity zero-padding of the GShard dispatch disappears.  Groups do
not need to align to tile boundaries — the grid iterates over (group,
m-tile) *incidences* and masks the rows of a shared tile that belong to a
different group, megablocks-style:

  * a physical m-tile fully inside one group is visited once;
  * a tile straddling a group boundary is visited once per group, each
    visit accumulating only its own rows (the other rows of the x tile are
    zeroed before the dot, so the f32 accumulator composes disjoint row
    sets across the consecutive visits);
  * the output tile is written exactly once, at the last visit of its run.

The incidence count is data-dependent but bounded by m_tiles + E - 1, so
the grid is static; trailing slack steps repeat the last incidence with an
all-false row mask (idempotent no-ops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.decode import decode_to_f32
from repro.core.types import PositConfig
from repro.kernels.tpu_params import compiler_params


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _group_metadata(group_offsets: jnp.ndarray, n_m_tiles: int, bm: int,
                    n_groups: int):
    """(group, m-tile) incidence tables for the static grid.

    Returns (m_tile_ids [L], group_ids [L], valid [L]) with
    L = n_m_tiles + n_groups - 1 (the worst case: every interior group
    boundary lands strictly inside a tile).  Incidences are ordered by
    (group, tile), which — because groups are contiguous in row space —
    also visits each physical m-tile's incidences consecutively, the
    property the kernel's run-accumulation relies on.  Slack steps past the
    true incidence count repeat the last incidence and are flagged invalid
    (the kernel masks their rows off entirely).
    """
    offsets = group_offsets.astype(jnp.int32)
    starts, ends = offsets[:-1], offsets[1:]
    sizes = ends - starts
    tile_starts = starts // bm
    tile_ends = -(-ends // bm)
    tiles_pg = jnp.where(sizes > 0, tile_ends - tile_starts, 0)
    inc_cum = jnp.cumsum(tiles_pg)
    num_inc = inc_cum[-1]
    L = n_m_tiles + n_groups - 1
    t = jnp.arange(L, dtype=jnp.int32)
    valid = (t < num_inc).astype(jnp.int32)
    tc = jnp.clip(jnp.minimum(t, num_inc - 1), 0, None)
    g = jnp.clip(jnp.searchsorted(inc_cum, tc, side="right"),
                 0, n_groups - 1).astype(jnp.int32)
    pos = tc - (inc_cum - tiles_pg)[g]
    mt = jnp.clip(tile_starts[g] + pos, 0, n_m_tiles - 1).astype(jnp.int32)
    return mt, g, valid


def _grouped_kernel(off_ref, mt_ref, gid_ref, valid_ref, x_ref, w_ref, o_ref,
                    acc_ref, *, cfg_b, bm, nk, L, transpose_b=False):
    """One (n-tile, incidence, k-tile) cell.

    The BlockSpec index maps already resolved this incidence's x m-tile and
    its group's weight tile from the prefetched tables; posit weight tiles
    decode here, in VMEM, right before the dot.  Rows of the x tile outside
    [off[g], off[g+1]) are zeroed so the accumulator — shared across the
    consecutive incidences of one physical tile — composes disjoint row
    sets; it initializes at the first incidence of the run and the output
    tile is written once, at the run's last incidence's final k step.

    transpose_b contracts w on its *last* (storage) dim — the dX backward
    streams the same posit weight tiles at storage width instead of
    materializing a decoded f32 transpose.
    """
    t = pl.program_id(1)
    k = pl.program_id(2)
    mt = mt_ref[t]
    first = jnp.logical_or(t == 0, mt != mt_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = gid_ref[t]
    rows = mt * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    live = ((rows >= off_ref[g]) & (rows < off_ref[g + 1])
            & (valid_ref[t] > 0))
    x = jnp.where(live, x_ref[...].astype(jnp.float32), 0.0)
    w = w_ref[0]
    if cfg_b is not None:
        w = decode_to_f32(w, cfg_b)          # stage (i): posit tile -> f32
    else:
        w = w.astype(jnp.float32)
    if transpose_b:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    last = jnp.logical_or(t == L - 1, mt_ref[jnp.minimum(t + 1, L - 1)] != mt)

    @pl.when(last & (k == nk - 1))
    def _done():
        o_ref[...] = acc_ref[...]


# n-tiles own disjoint output columns; the incidence axis carries the
# per-run accumulator and the k axis the partial sums — both must stay
# ordered
_GROUPED_SEMANTICS = ("parallel", "arbitrary", "arbitrary")


@functools.partial(
    jax.jit,
    static_argnames=("cfg_b", "bm", "bn", "bk", "transpose_b", "interpret"),
)
def posit_grouped_gemm(x: jnp.ndarray, w: jnp.ndarray,
                       group_offsets: jnp.ndarray, *,
                       cfg_b: PositConfig | None,
                       bm: int = 128, bn: int = 512, bk: int = 512,
                       transpose_b: bool = False,
                       interpret: bool = False) -> jnp.ndarray:
    """x [S, k] (expert-sorted rows) x w [E, k, n] -> [S, n] f32.

    group_offsets [E + 1] int32, non-decreasing, with offsets[0] == 0 and
    offsets[E] <= S: rows [offsets[g], offsets[g+1]) belong to group g.
    Rows at or past offsets[E] (e.g. the non-local tail under expert-
    parallel sharding) belong to no group and come back as exact zeros.
    cfg_b None means float weights (still grouped — the one-hot dispatch
    einsums are gone either way); otherwise w holds posit storage ints that
    decode tile-by-tile in VMEM.

    transpose_b: x [S, n] x w [E, k, n] -> [S, k], contracting w on its
    last dim — the dX backward (dx = g @ w[g]^T) over the *same* storage
    layout, so posit experts stream at posit width in the backward too.

    Per-step HBM weight traffic is (incidences x k x n) storage bytes with
    incidences <= ceil(S/bm) + E_active — for a decode step (S = B*top_k
    rows) that is the active experts' posit blocks only, vs the one-hot
    path's full E x k x n f32 materialization (the roofline columns in
    benchmarks/moe_throughput.py).
    """
    S, C = x.shape
    if transpose_b:
        E, Nout, C2 = w.shape
    else:
        E, C2, Nout = w.shape
    assert C == C2, (x.shape, w.shape, transpose_b)
    bm_ = min(bm, _round_up(max(S, 1), 8))
    bk_ = min(bk, C)
    bn_ = min(bn, max(128, Nout))
    Sp, Cp, Np = (_round_up(S, bm_), _round_up(C, bk_), _round_up(Nout, bn_))
    if (Sp, Cp) != (S, C):
        x = jnp.pad(x, ((0, Sp - S), (0, Cp - C)))
    if (Cp, Np) != (C, Nout):
        # zero int padding is posit zero, so padded tiles decode to 0.0
        if transpose_b:
            w = jnp.pad(w, ((0, 0), (0, Np - Nout), (0, Cp - C)))
        else:
            w = jnp.pad(w, ((0, 0), (0, Cp - C), (0, Np - Nout)))
    nm, nk, nn = Sp // bm_, Cp // bk_, Np // bn_
    L = nm + E - 1
    mt, gid, valid = _group_metadata(group_offsets, nm, bm_, E)

    if transpose_b:
        w_spec = pl.BlockSpec((1, bn_, bk_),
                              lambda j, t, k, off, mt, gid, vl: (gid[t], j, k))
    else:
        w_spec = pl.BlockSpec((1, bk_, bn_),
                              lambda j, t, k, off, mt, gid, vl: (gid[t], k, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nn, L, nk),
        in_specs=[
            pl.BlockSpec((bm_, bk_),
                         lambda j, t, k, off, mt, gid, vl: (mt[t], k)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((bm_, bn_),
                               lambda j, t, k, off, mt, gid, vl: (mt[t], j)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, cfg_b=cfg_b, bm=bm_, nk=nk, L=L,
                          transpose_b=transpose_b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Sp, Np), jnp.float32),
        compiler_params=compiler_params(*_GROUPED_SEMANTICS),
        name="posit_grouped_gemm",
        interpret=interpret,
    )(group_offsets.astype(jnp.int32), mt, gid, valid, x, w)[:S, :Nout]
    # tiles that no group touches are never written (their buffer content
    # is undefined); rows outside [offsets[0], offsets[-1]) are defined to
    # be zero, so mask them rather than trust the unwritten buffer
    rows = jnp.arange(S)
    inb = (rows >= group_offsets[0]) & (rows < group_offsets[-1])
    return jnp.where(inb[:, None], out, 0.0)


def _grouped_dw_kernel(off_ref, mt_ref, gid_ref, valid_ref, x_ref, g_ref,
                       o_ref, acc_ref, *, bm, L):
    """One (k-tile, n-tile, incidence) cell of the dW grid.

    dw[g] = x[rows(g)]^T @ gout[rows(g)]: the incidence axis is innermost
    and a group's incidences are consecutive, so one f32 scratch (the
    per-group quire) accumulates the whole group's outer product across its
    m-tiles; it zeroes at the group's first incidence and the [k, n] output
    tile is written once at the group's last.  Rows of a straddling tile
    that belong to the neighbour group are zeroed on the x side — zero rows
    contribute nothing to the contraction.  Empty groups never appear in
    the incidence table; their (unwritten) output blocks are masked by the
    caller.
    """
    t = pl.program_id(2)
    g = gid_ref[t]
    first = jnp.logical_or(t == 0, gid_ref[jnp.maximum(t - 1, 0)] != g)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mt = mt_ref[t]
    rows = mt * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    live = ((rows >= off_ref[g]) & (rows < off_ref[g + 1])
            & (valid_ref[t] > 0))
    x = jnp.where(live, x_ref[...].astype(jnp.float32), 0.0)
    gout = g_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, gout, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    last = jnp.logical_or(t == L - 1, gid_ref[jnp.minimum(t + 1, L - 1)] != g)

    @pl.when(last)
    def _done():
        o_ref[0] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret"),
)
def posit_grouped_gemm_dw(x: jnp.ndarray, g: jnp.ndarray,
                          group_offsets: jnp.ndarray, *,
                          bm: int = 128, bn: int = 512, bk: int = 512,
                          interpret: bool = False) -> jnp.ndarray:
    """x [S, k] x g [S, n], both expert-sorted -> dw [E, k, n] f32.

    The grouped-GEMM weight gradient: dw[e] = x[rows(e)]^T @ g[rows(e)],
    accumulated per group in f32 VMEM scratch over the same (group, m-tile)
    incidence grid as the forward.  Only meaningful for float (QAT) expert
    weights — posit storage ints carry no tangent, so the dispatcher never
    calls this for them.
    """
    S, K = x.shape
    S2, N = g.shape
    assert S == S2, (x.shape, g.shape)
    E = group_offsets.shape[0] - 1
    bm_ = min(bm, _round_up(max(S, 1), 8))
    bk_ = min(bk, max(8, K))
    bn_ = min(bn, max(128, N))
    Sp, Kp, Np = (_round_up(S, bm_), _round_up(K, bk_), _round_up(N, bn_))
    if (Sp, Kp) != (S, K):
        x = jnp.pad(x, ((0, Sp - S), (0, Kp - K)))
    if (Sp, Np) != (S, N):
        g = jnp.pad(g, ((0, Sp - S), (0, Np - N)))
    nm, nk, nn = Sp // bm_, Kp // bk_, Np // bn_
    L = nm + E - 1
    mt, gid, valid = _group_metadata(group_offsets, nm, bm_, E)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nk, nn, L),
        in_specs=[
            pl.BlockSpec((bm_, bk_),
                         lambda ki, ni, t, off, mt, gid, vl: (mt[t], ki)),
            pl.BlockSpec((bm_, bn_),
                         lambda ki, ni, t, off, mt, gid, vl: (mt[t], ni)),
        ],
        out_specs=pl.BlockSpec(
            (1, bk_, bn_),
            lambda ki, ni, t, off, mt, gid, vl: (gid[t], ki, ni)),
        scratch_shapes=[pltpu.VMEM((bk_, bn_), jnp.float32)],
    )
    dw = pl.pallas_call(
        functools.partial(_grouped_dw_kernel, bm=bm_, L=L),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, Kp, Np), jnp.float32),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        name="posit_grouped_gemm_dw",
        interpret=interpret,
    )(group_offsets.astype(jnp.int32), mt, gid, valid, x, g)[:, :K, :N]
    # empty groups own no incidence: their blocks were never written
    sizes = group_offsets[1:] - group_offsets[:-1]
    return jnp.where(sizes[:, None, None] > 0, dw, 0.0)
