"""Archive-scale GP fits: past the O(N^3) wall of ``gp_fit``.

The PR-4 surrogate refactorizes the full dense covariance every round —
cubic in history size, dead at a few thousand observations, while the GA
archives it should steer hold 10k-200k (ROADMAP: the paper's EGI run).
This module adds the two standard large-N escapes, selected automatically
by ``gp_fit`` once history crosses ``cfg.n_max_exact`` (the small-N dense
path stays byte-for-byte the code it was):

- **Inducing points** (``fit_inducing`` / ``update_inducing``): an
  SGPR-style sparse fit on m = ``cfg.n_inducing`` deterministically
  strided history points. With A = L_m^-1 K_mn / sigma the posterior
  needs only B = I + A A^T and c = L_B^-1 A ys / sigma — every per-round
  quantity is (m,) or (m, m), so after the one O(n m^2) cold fit a tell
  round appends with a rank-q update of the RUNNING sufficient statistics
  (A A^T, A y, A 1, count/sum/sq/min) and one (m, m) refactorization:
  O(m^2 q + m^3), independent of n. The (m, n) cross-covariance solve runs
  through the blocked triangular-solve engine (kernels/ops.tri_solve).
- **Local ensemble** (``fit_ensemble``): kd-style alternating-dimension
  median splits partition history into E equal cells of
  ``cfg.expert_size``; one exact GP per cell (vmapped factorization), and
  prediction merges the ``cfg.n_experts_predict`` nearest experts by
  generalized product-of-experts (precision-weighted, weights 1/k). E = 1
  reduces exactly to the dense GP — the test anchor.

Determinism: every fit here is a pure function of (cfg, history) — the
inducing set, the lengthscale subsample, and the kd partition are all
index arithmetic, no RNG. The incremental path re-associates the A A^T
accumulation, so an interrupted+resumed run (which cold-refits) agrees
with the uninterrupted one to float tolerance, not bitwise — the
small-N exact path keeps its bitwise guarantees (tests/test_bigfit.py).

Standardization under growth: y is standardized from RUNNING sums
(count, sum, sum-of-squares, min), recomputed exactly at every update —
the model never goes stale against a drifting y scale.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref


class InducingGPState(NamedTuple):
    """SGPR sufficient statistics + factors. Everything a tell round
    touches is (m,) or (m, m); history size enters only through the
    running scalars."""
    z: jnp.ndarray            # (m, d) inducing inputs (unit cube)
    l_m: jnp.ndarray          # (m, m) chol(K_mm + jitter I)
    l_b: jnp.ndarray          # (m, m) chol(I + A A^T)
    c: jnp.ndarray            # (m,)   L_B^-1 (A ys) / sigma
    aat: jnp.ndarray          # (m, m) running A A^T
    ay: jnp.ndarray           # (m,)   running A @ y_raw
    a1: jnp.ndarray           # (m,)   running A @ 1
    count: jnp.ndarray        # ()     observations folded in
    y_sum: jnp.ndarray        # ()
    y_sq: jnp.ndarray         # ()
    y_min: jnp.ndarray        # ()
    y_mean: jnp.ndarray       # ()     derived standardization
    y_std: jnp.ndarray        # ()
    lengthscale: jnp.ndarray  # ()
    best: jnp.ndarray         # ()     standardized incumbent


class EnsembleGPState(NamedTuple):
    """E local experts over a kd partition of history (equal cells, pad
    rows decoupled to identity), merged at prediction by gPoE."""
    x: jnp.ndarray            # (E, s, d) cell inputs
    valid: jnp.ndarray        # (E, s) f32 row validity
    chol: jnp.ndarray         # (E, s, s)
    alpha: jnp.ndarray        # (E, s)
    centroid: jnp.ndarray     # (E, d) valid-row centroids
    y_mean: jnp.ndarray       # ()
    y_std: jnp.ndarray        # ()
    lengthscale: jnp.ndarray  # ()
    best: jnp.ndarray         # ()


def _standardize(y_sum, y_sq, y_min, count):
    mean = y_sum / count
    var = jnp.maximum(y_sq / count - mean * mean, 0.0)
    std = jnp.maximum(jnp.sqrt(var), 1e-8)
    return mean, std, (y_min - mean) / std


def select_lengthscale(cfg, x, y):
    """Lengthscale by exact NLL on a strided history subsample of at most
    ``cfg.n_max_exact`` points — the dense grid sweep the small-N path
    runs, on a slice the dense path can afford. Pure index arithmetic:
    the same (cfg, history) always picks the same value."""
    grid = jnp.asarray(cfg.lengthscales, jnp.float32)
    if grid.shape[0] == 1:
        return grid[0]
    n = x.shape[0]
    ns = min(n, cfg.n_max_exact)
    idx = (jnp.arange(ns) * n) // ns
    xs, ys_raw = x[idx], y[idx]
    mean = ys_raw.mean()
    std = jnp.maximum(ys_raw.std(), 1e-8)
    ys = (ys_raw - mean) / std
    d2 = kops.gp_sqdist(xs, xs)
    eye = jnp.eye(ns, dtype=jnp.float32)

    def nll(ls):
        k = kref.gp_kernel_fn(cfg.kernel, d2, ls, 1.0) \
            + (cfg.noise + cfg.jitter) * eye
        chol = jnp.linalg.cholesky(k)
        alpha = jax.scipy.linalg.cho_solve((chol, True), ys)
        return 0.5 * ys @ alpha + jnp.log(jnp.diagonal(chol)).sum()

    return grid[jnp.argmin(jax.vmap(nll)(grid))]


# ---------------------------------------------------------------------------
# inducing-point (SGPR) path
# ---------------------------------------------------------------------------
def _cross_cov(cfg, xa, xb, ls):
    # assembled through the gated sqdist kernel + the shared kernel fn
    # (gp_matrix's static-lengthscale route can't take a traced ls)
    return kref.gp_kernel_fn(cfg.kernel, kops.gp_sqdist(xa, xb), ls, 1.0)


def _refresh_factors(cfg, state: InducingGPState) -> InducingGPState:
    """Recompute the derived pieces (standardization, L_B, c, best) from
    the running sufficient statistics — shared by cold fit and update."""
    m = state.z.shape[0]
    y_mean, y_std, best = _standardize(state.y_sum, state.y_sq,
                                       state.y_min, state.count)
    l_b = jnp.linalg.cholesky(jnp.eye(m, dtype=jnp.float32) + state.aat)
    ays = (state.ay - y_mean * state.a1) / y_std
    sigma = jnp.sqrt(jnp.float32(cfg.noise + cfg.jitter))
    c = jax.scipy.linalg.solve_triangular(l_b, ays, lower=True) / sigma
    return state._replace(l_b=l_b, c=c, y_mean=y_mean, y_std=y_std,
                          best=best)


def fit_inducing(cfg, x, y, *, z=None, lengthscale=None) -> InducingGPState:
    """Cold SGPR fit on the full history x (n, d), y (n,): O(n m^2) once.
    z defaults to a deterministic strided subset of history (tests pass
    it explicitly to pin the model across incremental comparisons)."""
    n = x.shape[0]
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if z is None:
        m = min(cfg.n_inducing, n)
        z = x[(jnp.arange(m) * n) // m]
    m = z.shape[0]
    ls = select_lengthscale(cfg, x, y) if lengthscale is None \
        else jnp.asarray(lengthscale, jnp.float32)
    # 10x jitter on K_mm: the strided inducing set can carry near-duplicate
    # history points (same guard propose_batch uses on acquisition chols)
    kmm = _cross_cov(cfg, z, z, ls) \
        + 10.0 * cfg.jitter * jnp.eye(m, dtype=jnp.float32)
    l_m = jnp.linalg.cholesky(kmm)
    sigma = jnp.sqrt(jnp.float32(cfg.noise + cfg.jitter))
    kmn = _cross_cov(cfg, z, x, ls)                      # (m, n)
    a = kops.tri_solve(l_m, kmn) / sigma                 # blocked engine
    state = InducingGPState(
        z=z, l_m=l_m, l_b=l_m, c=jnp.zeros((m,), jnp.float32),
        aat=a @ a.T, ay=a @ y, a1=a.sum(axis=1),
        count=jnp.float32(n), y_sum=y.sum(), y_sq=(y * y).sum(),
        y_min=y.min(), y_mean=jnp.float32(0.0), y_std=jnp.float32(1.0),
        lengthscale=ls, best=jnp.float32(0.0))
    return _refresh_factors(cfg, state)


def update_inducing(cfg, state: InducingGPState, x_new, y_new, mask=None
                    ) -> InducingGPState:
    """Incremental tell: fold a completed batch (q, d)/(q,) into the
    running statistics — a rank-q update of A A^T plus one (m, m)
    refactorization. O(m^2 q + m^3), independent of history size; the
    inducing set and lengthscale stay pinned to the cold fit. ``mask``
    (q,) zero-weights padded rows, which makes the same jitted program
    serve the mid-round fantasy updates of ``SurrogateExplorer.rescore``
    (masked columns of A_new are exactly zero — a no-op on every sum)."""
    x_new = x_new.astype(jnp.float32)
    y_new = y_new.astype(jnp.float32)
    mask = jnp.ones_like(y_new) if mask is None \
        else mask.astype(jnp.float32)
    sigma = jnp.sqrt(jnp.float32(cfg.noise + cfg.jitter))
    kzn = _cross_cov(cfg, state.z, x_new, state.lengthscale) \
        * mask[None, :]                                        # (m, q)
    a_new = jax.scipy.linalg.solve_triangular(
        state.l_m, kzn, lower=True) / sigma
    state = state._replace(
        aat=state.aat + a_new @ a_new.T,
        ay=state.ay + a_new @ y_new,
        a1=state.a1 + a_new.sum(axis=1),
        count=state.count + mask.sum(),
        y_sum=state.y_sum + (y_new * mask).sum(),
        y_sq=state.y_sq + (y_new * y_new * mask).sum(),
        y_min=jnp.minimum(state.y_min, jnp.where(
            mask > 0.5, y_new, jnp.float32(jnp.inf)).min()))
    return _refresh_factors(cfg, state)


def posterior_inducing(cfg, state: InducingGPState, xq):
    """Joint SGPR posterior of xq (q, d), standardized units: mean (q,)
    and full covariance (q, q). Differentiable — the acquisition ascent
    runs through it (assembly via ref helpers, no Pallas in the VJP)."""
    kqm = kref.gp_kernel_fn(
        cfg.kernel, kref.gp_sqdist_ref(xq, state.z), state.lengthscale, 1.0)
    w = jax.scipy.linalg.solve_triangular(state.l_m, kqm.T, lower=True)
    u = jax.scipy.linalg.solve_triangular(state.l_b, w, lower=True)
    mean = u.T @ state.c
    kq = kref.gp_kernel_fn(
        cfg.kernel, kref.gp_sqdist_ref(xq, xq), state.lengthscale, 1.0)
    cov = kq - w.T @ w + u.T @ u
    return mean, 0.5 * (cov + cov.T)


def mean_var_inducing(cfg, state: InducingGPState, xq):
    """Marginal mean/variance (q,) — the cheap per-point view."""
    kqm = kref.gp_kernel_fn(
        cfg.kernel, kref.gp_sqdist_ref(xq, state.z), state.lengthscale, 1.0)
    w = jax.scipy.linalg.solve_triangular(state.l_m, kqm.T, lower=True)
    u = jax.scipy.linalg.solve_triangular(state.l_b, w, lower=True)
    mean = u.T @ state.c
    var = jnp.maximum(1.0 - (w * w).sum(0) + (u * u).sum(0), cfg.jitter)
    return mean, var


# ---------------------------------------------------------------------------
# local-GP ensemble path
# ---------------------------------------------------------------------------
def _kd_order(x, valid, levels: int):
    """Deterministic kd-style ordering: ``levels`` rounds of alternating-
    dimension median splits (argsort halving). Invalid (pad) rows sort
    last, so cells are contiguous spatially-coherent runs with the pads
    collected at the tail. Returns a permutation of arange(n_p)."""
    n_p, d = x.shape
    idx = jnp.arange(n_p)
    for lvl in range(levels):
        groups = idx.reshape(2 ** lvl, -1)
        key = jnp.where(valid[groups] > 0.5,
                        x[groups, lvl % d], jnp.float32(jnp.inf))
        order = jnp.argsort(key, axis=1, stable=True)
        idx = jnp.take_along_axis(groups, order, axis=1).reshape(-1)
    return idx


def fit_ensemble(cfg, x, y, *, lengthscale=None) -> EnsembleGPState:
    """Partition history into E = 2^ceil(log2(n / expert_size)) equal
    cells of ``cfg.expert_size`` by kd median splits and fit one exact GP
    per cell (vmapped). Pad rows are decoupled to identity covariance
    rows with zero targets, so alpha there is exactly zero and they never
    leak into predictions. n <= expert_size gives E = 1: the dense GP."""
    n = x.shape[0]
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    s = cfg.expert_size
    levels = max(0, (max(1, -(-n // s)) - 1).bit_length())
    e = 2 ** levels
    n_p = e * s
    xp = jnp.zeros((n_p, x.shape[1]), jnp.float32).at[:n].set(x)
    yp = jnp.zeros((n_p,), jnp.float32).at[:n].set(y)
    valid = (jnp.arange(n_p) < n).astype(jnp.float32)

    ls = select_lengthscale(cfg, x, y) if lengthscale is None \
        else jnp.asarray(lengthscale, jnp.float32)
    y_mean = y.mean()
    y_std = jnp.maximum(y.std(), 1e-8)

    order = _kd_order(xp, valid, levels)
    xe = xp[order].reshape(e, s, x.shape[1])
    ye = ((yp[order] - y_mean) / y_std).reshape(e, s)
    ve = valid[order].reshape(e, s)
    nugget = cfg.noise + cfg.jitter

    def fit_cell(xc, yc, vc):
        k = kref.gp_kernel_fn(cfg.kernel, kref.gp_sqdist_ref(xc, xc),
                              ls, 1.0)
        eye = jnp.eye(s, dtype=jnp.float32)
        pair = vc[:, None] * vc[None, :]
        k = jnp.where(pair > 0.5, k + nugget * eye, eye)
        chol = jnp.linalg.cholesky(k)
        alpha = jax.scipy.linalg.cho_solve((chol, True), yc * vc)
        cnt = jnp.maximum(vc.sum(), 1.0)
        centroid = (xc * vc[:, None]).sum(0) / cnt
        return chol, alpha, centroid

    chol, alpha, centroid = jax.vmap(fit_cell)(xe, ye, ve)
    return EnsembleGPState(x=xe, valid=ve, chol=chol, alpha=alpha,
                           centroid=centroid, y_mean=y_mean, y_std=y_std,
                           lengthscale=ls,
                           best=((y.min() - y_mean) / y_std))


def posterior_ensemble(cfg, state: EnsembleGPState, xq):
    """Joint posterior of xq (q, d) from the k nearest experts (by batch
    centroid to cell centroid), merged by generalized product-of-experts
    with uniform weights 1/k: precision = mean of expert precisions, mean
    = precision-weighted. k = 1 (E = 1) is exactly the single expert."""
    e = state.x.shape[0]
    k_sel = min(cfg.n_experts_predict, e)
    qc = xq.mean(axis=0)
    d2 = ((state.centroid - qc[None, :]) ** 2).sum(-1)
    _, sel = jax.lax.top_k(-d2, k_sel)

    def expert(i):
        xc, vc = state.x[i], state.valid[i]
        ks = kref.gp_kernel_fn(cfg.kernel, kref.gp_sqdist_ref(xq, xc),
                               state.lengthscale, 1.0) * vc[None, :]
        mean = ks @ state.alpha[i]
        v = jax.scipy.linalg.solve_triangular(state.chol[i], ks.T,
                                              lower=True)
        kq = kref.gp_kernel_fn(cfg.kernel, kref.gp_sqdist_ref(xq, xq),
                               state.lengthscale, 1.0)
        cov = kq - v.T @ v
        return mean, 0.5 * (cov + cov.T)

    means, covs = jax.vmap(expert)(sel)
    q = xq.shape[0]
    eye = jnp.eye(q, dtype=jnp.float32)
    precs = jax.vmap(lambda c: jnp.linalg.inv(c + 10.0 * cfg.jitter * eye)
                     )(covs)
    prec = precs.mean(axis=0)
    cov = jnp.linalg.inv(prec + 10.0 * cfg.jitter * eye)
    mean = cov @ (precs @ means[..., None]).mean(axis=0)[:, 0]
    return mean, 0.5 * (cov + cov.T)


def mean_var_ensemble(cfg, state: EnsembleGPState, xq):
    """Marginal gPoE merge — per-point precisions only."""
    e = state.x.shape[0]
    k_sel = min(cfg.n_experts_predict, e)
    qc = xq.mean(axis=0)
    d2 = ((state.centroid - qc[None, :]) ** 2).sum(-1)
    _, sel = jax.lax.top_k(-d2, k_sel)

    def expert(i):
        xc, vc = state.x[i], state.valid[i]
        ks = kref.gp_kernel_fn(cfg.kernel, kref.gp_sqdist_ref(xq, xc),
                               state.lengthscale, 1.0) * vc[None, :]
        mean = ks @ state.alpha[i]
        v = jax.scipy.linalg.solve_triangular(state.chol[i], ks.T,
                                              lower=True)
        var = jnp.maximum(1.0 - (v * v).sum(0), cfg.jitter)
        return mean, var

    means, vars_ = jax.vmap(expert)(sel)
    prec = (1.0 / vars_).mean(axis=0)
    var = 1.0 / prec
    mean = (means / vars_).mean(axis=0) * var
    return mean, jnp.maximum(var, cfg.jitter)


def fit_big(cfg, x, y):
    """Route the archive-scale fit by ``cfg.big_method``."""
    if cfg.big_method == "ensemble":
        return fit_ensemble(cfg, x, y)
    if cfg.big_method != "inducing":
        raise ValueError(f"unknown big_method: {cfg.big_method!r}")
    return fit_inducing(cfg, x, y)
