"""Pairwise Pareto-dominance Pallas kernels — the O(N^2) hot spot of NSGA-II
non-dominated sorting at the paper's 200k-individual archive scale.

Two entry points share one tiling scheme:

``dominated_counts``
    dominated_count[i] = #{ j active : F_j dominates F_i }
      where "j dominates i"  <=>  all(F_j <= F_i) and any(F_j < F_i) (minimize).

``dominance_pass``
    The fused archive-scale sweep: ONE O(N^2) pass that emits both the counts
    and a packed dominance bitmap streamed to HBM —
      bit (j mod 32) of bitmap[i, j // 32] = 1  iff  row j of `cols` dominates
      row i of `rows` (and, when group ids are given, i and j share a group).
    Front peeling then becomes popcount decrements over the bitmap instead of
    one full pairwise pass per front (see evolution/nsga2.nondominated_ranks).

Layout. Grid = (row blocks, column blocks), columns innermost/sequential.
Rows arrive as (M, Ni, 1) so objective m of a row block is a (bi, 1) column
that broadcasts along lanes; columns arrive transposed as (M, Nj) so
objective m of 128 columns is a (1, 128) row that broadcasts along
sublanes. Every compare therefore runs on whole (bi, 128) vreg tiles, with
no (bi, bj, M) intermediate and no M-wide lane axis.

A column block is ``COLS`` = 4096 columns = 128 output words, so the bitmap
block (bi, 128) is lane-aligned. The kernel packs word w of a block from
lanes {w, 128 + w, ..., 31*128 + w}: bit k comes from the 128-lane slice k,
an aligned slice. The wrapper permutes the columns of each block so that
lane k*128 + w holds column 32*w + k, which makes this the standard
``ref.pack_words_u32`` convention (bit j % 32 of word j // 32) with no
in-kernel reshape of the lane axis.

    VMEM per step ~ 2 * (M * bi * 512 B  +  M * 4096 * 4 B)   input blocks
                  + 2 * bi * 512 B                            bitmap block
                  + bi * 512 B                                counter scratch
                  ~ 1.3 MiB at bi = 256, M = 3

Padding. Rows pad up to a block multiple and columns up to a ``COLS``
multiple with +BIG sentinels (group -1): all-BIG rows never strictly
dominate anything (<= holds but < fails on every objective), so padding adds
exactly zero to every count and never sets a bitmap bit; the wrapper slices
it off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.0e38
LANES = 128
COLS = 32 * LANES          # columns per column block = 128 u32 words


def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def effective_block(n: int, block: int, mult: int) -> int:
    """Block size actually used for an n-row axis: `block` rounded to a
    multiple of `mult`, shrunk toward n for small inputs (the grid then has a
    single step instead of streaming empty padding)."""
    return max(mult, min(_ceil_to(block, mult), _ceil_to(n, mult)))


def _pad_rows(x, n_padded, value):
    n = x.shape[0]
    if n == n_padded:
        return x
    return jnp.concatenate(
        [x, jnp.full((n_padded - n,) + x.shape[1:], value, x.dtype)])


def _tile(fi_ref, gi_ref, fj_ref, gj_ref, k):
    """(bi, 128) bool: column lane-slice ``k`` of the block dominates row i
    (within the same group)."""
    cols = pl.ds(pl.multiple_of(k * LANES, LANES), LANES)
    le = lt = None
    for m in range(fi_ref.shape[0]):
        a = fj_ref[pl.ds(m, 1), cols]            # (1, 128) dominator obj m
        b = fi_ref[m]                            # (bi, 1) candidate obj m
        le = (a <= b) if le is None else le & (a <= b)
        lt = (a < b) if lt is None else lt | (a < b)
    return le & lt & (gj_ref[:, cols] == gi_ref[...])


def _kernel(fi_ref, gi_ref, fj_ref, gj_ref, *refs, bitmap: bool):
    if bitmap:
        cnt_ref, bm_ref, cnt_scr = refs
    else:
        cnt_ref, cnt_scr = refs
    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        cnt_scr[...] = jnp.zeros_like(cnt_scr)

    def slice_k(k, carry):
        cnt, words = carry
        dom = _tile(fi_ref, gi_ref, fj_ref, gj_ref, k)
        bit = jnp.left_shift(jnp.uint32(1), k.astype(jnp.uint32))
        return (cnt + dom.astype(jnp.int32),
                words | jnp.where(dom, bit, jnp.uint32(0)))

    cnt, words = jax.lax.fori_loop(
        0, fj_ref.shape[1] // LANES, slice_k,
        (jnp.zeros(cnt_scr.shape, jnp.int32),
         jnp.zeros(cnt_scr.shape, jnp.uint32)))
    cnt_scr[...] += cnt
    if bitmap:
        bm_ref[...] = words

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finish():
        cnt_ref[...] = cnt_scr[...].sum(axis=1, keepdims=True)


def _sweep(rows, cols, groups, groups_cols, *, block, bitmap, interpret):
    ni, m = rows.shape
    nj = cols.shape[0]
    if groups is None:
        groups = jnp.zeros((ni,), jnp.int32)
    if groups_cols is None:
        groups_cols = jnp.zeros((nj,), jnp.int32)
    bi = effective_block(ni, block, 8)
    ni_p, nj_p = _ceil_to(ni, bi), _ceil_to(nj, COLS)
    fi = _pad_rows(rows.astype(jnp.float32), ni_p, BIG).T[:, :, None]
    gi = _pad_rows(groups.astype(jnp.int32), ni_p, -1)[:, None]
    fj = _pad_rows(cols.astype(jnp.float32), nj_p, BIG)
    gj = _pad_rows(groups_cols.astype(jnp.int32), nj_p, -1)
    if bitmap:
        # lane k*128 + w of each column block holds column 32*w + k
        def perm(x):
            nb = nj_p // COLS
            x = x.reshape((nb, LANES, 32) + x.shape[1:])
            return jnp.swapaxes(x, 1, 2).reshape((nj_p,) + x.shape[3:])
        fj, gj = perm(fj), perm(gj)
    fj, gj = fj.T, gj[None, :]
    out_specs = [pl.BlockSpec((bi, 1), lambda i, j: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((ni_p, 1), jnp.int32)]
    if bitmap:
        out_specs.append(pl.BlockSpec((bi, LANES), lambda i, j: (i, j)))
        out_shape.append(
            jax.ShapeDtypeStruct((ni_p, nj_p // 32), jnp.uint32))
    out = pl.pallas_call(
        lambda *refs: _kernel(*refs, bitmap=bitmap),
        grid=(ni_p // bi, nj_p // COLS),
        in_specs=[
            pl.BlockSpec((m, bi, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bi, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((m, COLS), lambda i, j: (0, j)),
            pl.BlockSpec((1, COLS), lambda i, j: (0, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bi, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dominance_pass" if bitmap else "dominated_counts",
    )(fi, gi, fj, gj)
    cnt = out[0][:ni, 0]
    if not bitmap:
        return cnt
    return cnt, out[1][:ni, :_ceil_to(nj, 32) // 32]


def dominated_counts(objectives, *, block=256, interpret=False):
    """objectives: (N, M) f32 (inactive rows pre-masked to +BIG).
    Returns (N,) i32 dominated counts — the per-front peeling baseline."""
    return _sweep(objectives, objectives, None, None, block=block,
                  bitmap=False, interpret=interpret)


def dominance_pass(rows, cols=None, groups=None, groups_cols=None, *,
                   block=256, interpret=False):
    """One fused O(Ni*Nj) sweep of `rows` (candidates) against `cols`
    (potential dominators). cols=None means the square self-sweep.

    Returns ``(counts, bitmap)``:
      counts: (Ni,) i32 — number of cols rows dominating each rows row,
      bitmap: (Ni, ceil32(Nj)/32) u32 — bit (j%32) of word j//32 set iff
              cols[j] dominates rows[i]; bits past Nj are always 0.

    The rows/cols split is what the mesh-sharded sweep uses: each device takes
    a row block against the full column set (runtime/sharding.py)."""
    if cols is None:
        cols = rows
        groups_cols = groups
    return _sweep(rows, cols, groups, groups_cols, block=block, bitmap=True,
                  interpret=interpret)
