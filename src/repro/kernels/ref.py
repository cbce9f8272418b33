"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# Kernel-vs-oracle tolerances for f32 outputs, shared by the tests and
# chip_smoke.py (``np.testing.assert_allclose(got, want, **TOL[name])``).
# A kernel and its jitted oracle evaluate the same expression, but a
# compiler may contract a*b+c into one FMA on one side only or reassociate
# a reduction, and on the TPU the MXU and the VPU accumulate in their own
# orders: the two agree to f32 rounding, not bit for bit. Each bound is a
# small multiple of f32 rounding at the magnitude compared, and at least
# 8x below bf16's ulp at that magnitude (2^-7 = 7.8e-3 relative), so a
# kernel computing in bf16 fails it. Integer outputs (dominance counts
# and bitmaps) are compared exactly.
TOL = {
    # chemical fields of magnitude ~1; same terms added in the same order
    "diffusion": dict(rtol=1e-6, atol=1e-6),
    # squared distances up to 4 d; n1 + n2 - 2 x.y cancels, so absolute
    "gp_sqdist": dict(rtol=1e-5, atol=1e-5),
    # covariances in [0, variance]
    "gp_matrix": dict(rtol=1e-5, atol=4e-6),
    # a fitted GP: factor, weights, posterior mean and covariance
    "gp_posterior": dict(rtol=1e-4, atol=1e-6),
    # factor of K + 1e-2 I for 1000 unit-square points (condition ~2e4,
    # entries <= 1): forward error ~ condition x f32 rounding
    "chol": dict(rtol=0.0, atol=5e-4),
    # L X = B against that factor, |X| <= ~50
    "tri_solve": dict(rtol=1e-5, atol=1e-4),
}


def flash_attention_ref(q, k, v, *, causal=True):
    """q: (B,H,S,D); k,v: (B,KH,S,D). Plain softmax attention with GQA."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    g = h // kh
    qg = q.reshape(b, kh, g, s, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qg, kf) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", probs, vf)
    return out.reshape(b, h, s, d).astype(q.dtype)


def diffuse_evaporate_ref(chem, rate, evap):
    """chem: (N,W,W) f32; NetLogo bounded-world diffuse + evaporate."""
    n, w, _ = chem.shape
    rate = rate[:, None, None]
    share = chem * rate / 8.0
    padded = jnp.pad(share, ((0, 0), (1, 1), (1, 1)))
    acc = jnp.zeros_like(chem)
    ncount = jnp.zeros_like(chem)
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            if di == 0 and dj == 0:
                continue
            acc = acc + padded[:, 1 + di:1 + di + w, 1 + dj:1 + dj + w]
            nb = jnp.ones((n, w, w))
            nb = jnp.pad(nb, ((0, 0), (1, 1), (1, 1)))
            ncount = ncount + nb[:, 1 + di:1 + di + w, 1 + dj:1 + dj + w]
    kept = chem - share * ncount
    return (kept + acc) * (1.0 - evap[:, None, None])


def dominated_counts_ref(objectives):
    """(N, M) f32 -> (N,) i32; minimization dominance counts."""
    le = (objectives[None, :, :] <= objectives[:, None, :]).all(-1)
    lt = (objectives[None, :, :] < objectives[:, None, :]).any(-1)
    dom = jnp.logical_and(le, lt)        # dom[i, j] = j dominates i
    return dom.astype(jnp.int32).sum(axis=1)


def pack_words_u32(bits):
    """(..., W, 32) bool -> (..., W) u32 with bit k of word w = bits[..., w, k]
    — THE bit convention of the dominance bitmap; the kernel, this oracle,
    and the peeling engine all pack through this one helper."""
    shift = jax.lax.broadcasted_iota(jnp.uint32, bits.shape, bits.ndim - 1)
    return jnp.sum(bits.astype(jnp.uint32) << shift, axis=-1,
                   dtype=jnp.uint32)


def dominance_pass_ref(rows, cols=None, groups=None, groups_cols=None):
    """Oracle for the fused sweep: (counts (Ni,) i32, bitmap (Ni, W) u32)
    with bit (j%32) of bitmap[i, j//32] set iff cols[j] dominates rows[i]
    (within the same group when group ids are given). W = ceil32(Nj)/32."""
    if cols is None:
        cols = rows
        groups_cols = groups
    ni, nj = rows.shape[0], cols.shape[0]
    le = (cols[None, :, :] <= rows[:, None, :]).all(-1)
    lt = (cols[None, :, :] < rows[:, None, :]).any(-1)
    dom = jnp.logical_and(le, lt)                      # (Ni, Nj)
    if groups is not None:
        dom = jnp.logical_and(
            dom, groups_cols[None, :].astype(jnp.int32)
            == groups[:, None].astype(jnp.int32))
    counts = dom.astype(jnp.int32).sum(axis=1)
    w = -(-nj // 32)
    padded = jnp.pad(dom, ((0, 0), (0, w * 32 - nj)))
    bitmap = pack_words_u32(padded.reshape(ni, w, 32))
    return counts, bitmap


def gp_sqdist_ref(x1, x2):
    """(N1, D), (N2, D) -> (N1, N2) f32 squared Euclidean distances via the
    expanded form ||a||^2 + ||b||^2 - 2 a.b, clamped at 0 — THE formulation
    of the fused GP covariance kernel; the Pallas tiles, this oracle, and
    the surrogate posterior all assemble distances through this exact
    sequence of ops, which is what makes them bit-identical.

    The cross term is an explicit sum of products (not ``jnp.dot``): XLA
    specializes dot-general FMA patterns per shape, so a tiled matmul is
    NOT bitwise-stable against the full-matrix one, while an elementwise
    multiply + trailing-axis reduce is. D is tiny (genome dims), so the
    (tile, tile, D) product intermediate stays tile-local and small."""
    n1 = (x1 * x1).sum(-1)
    n2 = (x2 * x2).sum(-1)
    cross = (x1[:, None, :] * x2[None, :, :]).sum(-1)
    d2 = n1[:, None] + n2[None, :] - 2.0 * cross
    return jnp.maximum(d2, 0.0)


def gp_kernel_fn(kind, d2, lengthscale, variance):
    """Map squared distances through a stationary covariance function.
    Shared elementwise helper (same pack_words_u32 discipline): the Pallas
    kernel body and every jnp path call this one function, so a fixed
    (kind, lengthscale, variance) gives bitwise-identical covariances."""
    if kind == "rbf":
        return variance * jnp.exp(-0.5 * d2 / (lengthscale * lengthscale))
    if kind == "matern52":
        s5 = jnp.sqrt(jnp.float32(5.0))
        # safe sqrt: identical forward values (sqrt(0) == 0), but the
        # where() blocks the d/d(d2) = inf branch at d2 == 0 so the
        # acquisition optimizer can differentiate through k(x, x) diagonals
        d2p = jnp.maximum(d2, 0.0)
        r = jnp.where(d2p > 0.0, jnp.sqrt(jnp.where(d2p > 0.0, d2p, 1.0)),
                      0.0) / lengthscale
        return variance * (1.0 + s5 * r + (5.0 / 3.0) * (r * r)) \
            * jnp.exp(-s5 * r)
    raise ValueError(f"unknown GP kernel kind: {kind}")


def gp_matrix_ref(x1, x2, *, kind="matern52", lengthscale=0.2, variance=1.0):
    """Oracle for the fused covariance assembly: expanded-form distances +
    covariance map in one jnp expression (no (N1, N2, D) intermediate)."""
    return gp_kernel_fn(kind, gp_sqdist_ref(x1, x2), lengthscale, variance)


def gp_matrix_naive_ref(x1, x2, *, kind="matern52", lengthscale=0.2,
                        variance=1.0):
    """The textbook broadcast assembly: materializes the (N1, N2, D)
    difference tensor. Numerically close to (but not bitwise equal with)
    the expanded form — the benchmark baseline, not the exactness oracle."""
    d2 = ((x1[:, None, :] - x2[None, :, :]) ** 2).sum(-1)
    return gp_kernel_fn(kind, d2, lengthscale, variance)


# ---------------------------------------------------------------------------
# Blocked Cholesky / triangular solve (the archive-scale GP factorization)
# ---------------------------------------------------------------------------
# Shared tile helpers: the Pallas kernel bodies in kernels/cholesky.py and
# the blocked jnp oracles below compute through THESE functions with THE SAME
# tile shapes, which is the whole bitwise-equality contract (pack_words_u32 /
# gp_sqdist_ref discipline). Two non-negotiable rules follow from how XLA
# specializes dot-general FMA patterns per shape (see gp_sqdist_ref):
#
#   1. every matmul is a (block, block) x (block, block) tile dot — never a
#      full-panel dot — so the oracle's dots have the kernel's shapes;
#   2. trailing/accumulation updates subtract tile products one at a time in
#      increasing tile order, so the float op sequence per element is
#      identical between the right-looking kernel schedule and the
#      left-looking oracle schedule (subtracting an exact 0.0 — the masked
#      lanes of the kernel's uniform loops — is a bitwise no-op).
#
# Consequence: the factor is bit-reproducible per (shape, block) pair but
# block-size-DEPENDENT at the last bit (different tile dots round
# differently); callers pin block= where bitwise stability matters.

def tile_dot(a, b):
    """THE tile matmul of the blocked factor and solve, kernel and oracle
    alike. Full f32 precision: on the TPU a default-precision f32 dot
    rounds its operands to bf16 (XLA and Mosaic differently), which would
    leave the Cholesky factor of a GP covariance with ~3 correct digits.
    On the CPU the precision flag changes nothing."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


CHOL_BASE = 64   # fori-loop base-case tile edge (all blocks are multiples)


def chol_base_ref(a):
    """Unblocked Cholesky–Crout of one (b, b) SPD tile, b <= CHOL_BASE.

    One fori_loop step per column, all indexing via onehot masks (no
    dynamic slicing — the same code lowers inside a Pallas kernel body):
    pivot sqrt (guarded for the padded-identity lanes), column scale, then
    a rank-1 outer-product downdate of the trailing submatrix."""
    b = a.shape[0]
    idx = jnp.arange(b)

    def body(j, acc):
        onehot = (idx == j).astype(acc.dtype)
        ajj = (acc * onehot[None, :] * onehot[:, None]).sum()
        d = jnp.sqrt(jnp.maximum(ajj, 1e-30))
        col = (acc * onehot[None, :]).sum(1)
        below = (idx > j).astype(acc.dtype)
        lcol = jnp.where(idx > j, col / d, 0.0) + onehot * d
        acc = acc - jnp.outer(lcol * below, lcol * below)
        return acc * (1.0 - onehot[None, :]) + jnp.outer(lcol, onehot)

    return jnp.tril(jax.lax.fori_loop(0, b, body, a))


def tri_inv_base_ref(l):
    """Inverse of one (b, b) lower-triangular tile by forward substitution
    on the identity — onehot-masked fori_loop, Pallas-safe like
    chol_base_ref. Turning the diag tile into an explicit inverse makes
    every triangular panel solve a tile DOT (gemm-bound), not an
    elementwise substitution sweep — the core of the blocked speedup."""
    b = l.shape[0]
    idx = jnp.arange(b)
    eye = jnp.eye(b, dtype=l.dtype)

    def body(i, inv):
        onehot = (idx == i).astype(l.dtype)
        lrow = (l * onehot[:, None]).sum(0)
        dii = (lrow * onehot).sum()
        partial = ((lrow * (idx < i).astype(l.dtype))[:, None] * inv).sum(0)
        bi = (eye * onehot[:, None]).sum(0)
        xi = (bi - partial) / dii
        return inv * (1.0 - onehot[:, None]) + onehot[:, None] * xi[None, :]

    return jax.lax.fori_loop(0, b, body, jnp.zeros_like(l))


def chol_tile_ref(a):
    """Factor one (block, block) diagonal tile: recursive halving down to
    CHOL_BASE so the fori base case touches only (64, 64) tiles and
    everything above is tile dots (the base case is elementwise-bound and
    would dominate at block size — measured 260x slower than the dot path
    at 512)."""
    b = a.shape[0]
    if b <= CHOL_BASE:
        return chol_base_ref(a)
    h = b // 2
    a11, a21, a22 = a[:h, :h], a[h:, :h], a[h:, h:]
    l11 = chol_tile_ref(a11)
    l21 = tile_dot(a21, tri_inv_tile_ref(l11).T)
    l22 = chol_tile_ref(a22 - tile_dot(l21, l21.T))
    z = jnp.zeros((h, b - h), a.dtype)
    return jnp.block([[l11, z], [l21, l22]])


def tri_inv_tile_ref(l):
    """Inverse of one (block, block) lower-triangular tile, recursive like
    chol_tile_ref: inv([[L11, 0], [L21, L22]]) has lower-left block
    -L22^-1 L21 L11^-1, so only the CHOL_BASE leaves substitute."""
    b = l.shape[0]
    if b <= CHOL_BASE:
        return tri_inv_base_ref(l)
    h = b // 2
    i11 = tri_inv_tile_ref(l[:h, :h])
    i22 = tri_inv_tile_ref(l[h:, h:])
    z = jnp.zeros((h, b - h), l.dtype)
    return jnp.block([[i11, z],
                      [-tile_dot(i22, tile_dot(l[h:, :h], i11)), i22]])


def gp_tile_ref(x1, x2, row0, col0, n, *, kind, lengthscale, nugget):
    """One masked covariance tile of the fused assemble+factor path:
    K[row0:row0+b1, col0:col0+b2] of the n-point kernel matrix with
    ``nugget`` on the true diagonal, and the PADDED region (index >= n)
    replaced by identity rows/columns — so the padded matrix factors as
    blkdiag(L, I) and the pad never perturbs the valid block. Shared by
    the Pallas assembly kernels (row0/col0 from program_id) and the
    blocked oracle (python ints): integer masking is exact either way."""
    k = gp_kernel_fn(kind, gp_sqdist_ref(x1, x2), lengthscale, 1.0)
    r = row0 + jnp.arange(x1.shape[0])
    c = col0 + jnp.arange(x2.shape[0])
    eye = (r[:, None] == c[None, :]).astype(jnp.float32)
    pad = (r[:, None] >= n) | (c[None, :] >= n)
    return jnp.where(pad, eye, k + nugget * eye)


def chol_blocked_ref(a, *, block=256):
    """Blocked Cholesky oracle: a (n_p, n_p) f32 with n_p % block == 0 ->
    lower L (n_p, n_p). LEFT-looking schedule — each block column is
    computed once from already-finished columns and never updated again,
    so the jitted oracle is pure dataflow (no in-place trailing updates
    for XLA to copy around: this exact restructuring took the CPU engine
    route from 4.6 s to the dot-bound regime at n=4096). Bitwise equal to
    the right-looking Pallas kernel per the tile-dot contract above."""
    n_p = a.shape[0]
    nb = n_p // block
    tiles = {(i, j): jax.lax.slice(
        a, (i * block, j * block), ((i + 1) * block, (j + 1) * block))
        for i in range(nb) for j in range(i + 1)}
    return _chol_left_tiles(tiles, nb, block)


def _chol_left_tiles(tiles, nb, block):
    """Left-looking factor of a dict of lower tiles -> assembled (n_p, n_p)
    L. Shared by chol_blocked_ref and gp_chol_blocked_ref."""
    out = {}
    for k in range(nb):
        col = {}
        for i in range(k, nb):
            s = tiles[(i, k)]
            for j in range(k):
                s = s - tile_dot(out[(i, j)], out[(k, j)].T)
            col[i] = s
        lkk = chol_tile_ref(col[k])
        out[(k, k)] = lkk
        if k < nb - 1:
            linv_t = tri_inv_tile_ref(lkk).T
            for i in range(k + 1, nb):
                out[(i, k)] = tile_dot(col[i], linv_t)
    z = jnp.zeros((block, block), jnp.float32)
    return jnp.concatenate(
        [jnp.concatenate([out[(i, j)] if j <= i else z for j in range(nb)],
                         axis=1) for i in range(nb)], axis=0)


def gp_chol_blocked_ref(x, n, *, kind, lengthscale, nugget, block=256):
    """Fused assemble+factor oracle: x (n_p, d) zero-padded unit-cube
    inputs (n_p % block == 0, true count n) -> lower Cholesky factor of
    [K(x, x) + nugget I] padded with identity. The covariance tiles are
    assembled per (block, d) tile pair through gp_tile_ref exactly where
    the factorization first touches them — K never exists as an
    unfactored (n_p, n_p) intermediate."""
    n_p = x.shape[0]
    nb = n_p // block
    xt = [jax.lax.slice(x, (i * block, 0), ((i + 1) * block, x.shape[1]))
          for i in range(nb)]
    tiles = {(i, j): gp_tile_ref(xt[i], xt[j], i * block, j * block, n,
                                 kind=kind, lengthscale=lengthscale,
                                 nugget=nugget)
             for i in range(nb) for j in range(i + 1)}
    return _chol_left_tiles(tiles, nb, block)


def tri_solve_blocked_ref(l, b, *, trans=False, block=256, rhs_block=256):
    """Blocked triangular solve oracle: L (n_p, n_p) lower (identity-padded
    past the true size), B (n_p, m_p), n_p % block == m_p % rhs_block == 0
    -> X with L X = B (trans=False, forward) or L^T X = B (trans=True,
    backward). RHS columns split into independent (block, rhs_block)
    panels — the Pallas kernel's parallel grid dimension — and row blocks
    substitute sequentially within each; every update is a (block, block)
    x (block, rhs_block) tile dot against the already-solved blocks plus
    one dot with the diagonal tile's explicit inverse (tri_inv_tile_ref).
    Gemm-bound, and bitwise the kernel's schedule: its masked uniform
    j-loop subtracts exact zeros where this oracle subtracts nothing."""
    n_p = l.shape[0]
    m_p = b.shape[1]
    nb = n_p // block
    ncb = m_p // rhs_block

    def ltile(i, j):
        return jax.lax.slice(l, (i * block, j * block),
                             ((i + 1) * block, (j + 1) * block))

    linv = [tri_inv_tile_ref(ltile(i, i)) for i in range(nb)]
    cols = []
    for c in range(ncb):
        bt = [jax.lax.slice(b, (i * block, c * rhs_block),
                            ((i + 1) * block, (c + 1) * rhs_block))
              for i in range(nb)]
        xs = [None] * nb
        order = range(nb) if not trans else range(nb - 1, -1, -1)
        for i in order:
            s = bt[i]
            js = range(i) if not trans else range(i + 1, nb)
            for j in js:
                lij = ltile(i, j) if not trans else ltile(j, i).T
                s = s - tile_dot(lij, xs[j])
            di = linv[i] if not trans else linv[i].T
            xs[i] = tile_dot(di, s)
        cols.append(jnp.concatenate(xs, axis=0))
    return jnp.concatenate(cols, axis=1)


def nondominated_ranks_ref(objectives, valid=None):
    """Front-peeling reference for non-dominated sorting: a host-python loop
    that reruns the full O(N^2) pairwise pass once *per front* (the shape of
    the pre-engine implementation). (N, M) -> (N,) i32 front index."""
    import numpy as np
    obj = np.asarray(objectives, np.float32)
    n = obj.shape[0]
    valid = np.ones(n, bool) if valid is None else np.asarray(valid, bool)
    big = 1.0e30
    obj = np.where(valid[:, None], obj, big)
    ranks = np.full(n, n, np.int32)
    active = valid.copy()
    r = 0
    while active.any():
        masked = np.where(active[:, None], obj, big)
        counts = np.asarray(dominated_counts_ref(jnp.asarray(masked)))
        front = active & (counts == 0)
        ranks[front] = r
        active &= ~front
        r += 1
    return ranks
