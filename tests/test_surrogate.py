"""Surrogate-engine suite: the fused GP covariance kernel, the GP
posterior, the q-EI/q-UCB batch acquisition, and the ask/tell explorer
(ISSUE 5 tentpole).

Two tiers, following test_sampling_property.py:
- deterministic parametrized properties that always run (no extra deps);
- Hypothesis generalizations of the same properties, skipped with a reason
  when hypothesis is absent (CI installs it, so they run there).

Kernel-vs-oracle contract: the Pallas kernel (interpret mode here), the
ops-gated route, and the jnp reference all compute through the shared
helpers in kernels/ref.py and agree within ``ref.TOL`` (f32 rounding; a
compiler may form FMAs or reassociate sums differently on either side, so
they are not bit for bit equal). The same tolerances hold a chip run to its
references in chip_smoke.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.explore.surrogate import (GPState, SurrogateConfig,
                                     SurrogateExplorer, expected_improvement,
                                     gp_fit, gp_mean_var, gp_posterior, q_ei,
                                     q_ucb, run_surrogate)
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.gp import gp_matrix, gp_sqdist

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed; the deterministic "
    "tier of these properties still runs")

# the ONE shared tiny config/fitness (tests/conftest.py) -> the per-config
# jit cache is hit across this module, the chaos suite, and the golden
# suite
from conftest import surrogate_quadratic, surrogate_tiny_config

CFG = surrogate_tiny_config()

_jit_matrix_ref = jax.jit(
    lambda a, b, kind, ls, var: kref.gp_matrix_ref(
        a, b, kind=kind, lengthscale=ls, variance=var),
    static_argnums=(2, 3, 4))
_jit_sqdist_ref = jax.jit(kref.gp_sqdist_ref)


def _xy(key, n, d, scale=2.0):
    return jax.random.uniform(key, (n, d), jnp.float32) * scale


# ---------------------------------------------------------------------------
# deterministic tier: kernel bit-exactness
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n1,n2,d", [
    (7, 13, 2),       # prime x prime, padded
    (37, 53, 3),      # prime x prime
    (101, 101, 8),    # prime, square
    (64, 257, 16),    # block-aligned x prime, widest dims
    (31, 97, 4),      # prime x prime across tile boundary
    (128, 128, 2),    # exactly block-divisible
])
@pytest.mark.parametrize("kind", ["matern52", "rbf"])
def test_gp_matrix_bit_exact_vs_ref(n1, n2, d, kind):
    k1, k2 = jax.random.split(jax.random.key(n1 * 1000 + n2 + d))
    x1, x2 = _xy(k1, n1, d), _xy(k2, n2, d)
    got = gp_matrix(x1, x2, kind=kind, lengthscale=0.3, variance=1.7,
                    block=64, interpret=True)
    want = _jit_matrix_ref(x1, x2, kind, 0.3, 1.7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **kref.TOL["gp_matrix"])


@pytest.mark.parametrize("n1,n2,d", [(7, 13, 2), (101, 101, 8), (64, 257, 16)])
def test_gp_sqdist_bit_exact_vs_ref(n1, n2, d):
    k1, k2 = jax.random.split(jax.random.key(n1 + n2 + d))
    x1, x2 = _xy(k1, n1, d), _xy(k2, n2, d)
    got = gp_sqdist(x1, x2, block=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_jit_sqdist_ref(x1, x2)),
                               **kref.TOL["gp_sqdist"])


def test_gp_matrix_duplicate_rows_bit_exact_and_unit_diag():
    x = _xy(jax.random.key(3), 41, 3)
    x = x.at[7].set(x[3])                       # exact duplicate row
    got = np.asarray(gp_matrix(x, x, block=16, interpret=True))
    want = np.asarray(_jit_matrix_ref(x, x, "matern52", 0.2, 1.0))
    np.testing.assert_array_equal(got, want)
    # duplicates are zero-distance: covariance there is exactly `variance`
    np.testing.assert_array_equal(got[7, 3], 1.0)
    np.testing.assert_array_equal(np.diagonal(got), np.ones(41))


def test_ops_route_matches_ref_on_both_sides_of_the_gate():
    """The ops gate flips from interpret-mode kernel to jitted reference
    with size; both sides must be bitwise identical to the jitted ref."""
    small = _xy(jax.random.key(0), 33, 2)       # interpret side
    big = _xy(jax.random.key(1), 1100, 2)       # reference side (>16 steps)
    for x in (small, big):
        np.testing.assert_array_equal(
            np.asarray(kops.gp_matrix(x, x, kind="rbf", lengthscale=0.4)),
            np.asarray(_jit_matrix_ref(x, x, "rbf", 0.4, 1.0)))


def test_gp_matrix_symmetric_and_bounded():
    x = _xy(jax.random.key(5), 50, 4)
    for kind in ("matern52", "rbf"):
        k = np.asarray(gp_matrix(x, x, kind=kind, block=32, interpret=True))
        np.testing.assert_allclose(k, k.T, atol=0)
        # far-apart pairs may underflow to exactly 0 in f32 (rbf) — that is
        # fine; negative or >variance entries are not
        assert (k >= 0).all() and (k <= 1.0 + 1e-6).all()


# ---------------------------------------------------------------------------
# deterministic tier: GP posterior
# ---------------------------------------------------------------------------
def _ref_fit(cfg, x, y):
    """gp_fit with the distance assembly forced through the jnp reference
    (same math, no Pallas) — the posterior bit-exactness oracle."""
    n = x.shape[0]
    y_mean = y.mean()
    y_std = jnp.maximum(y.std(), 1e-8)
    ys = (y - y_mean) / y_std
    d2 = kref.gp_sqdist_ref(x, x)
    eye = jnp.eye(n, dtype=jnp.float32)

    def factor(ls):
        k = kref.gp_kernel_fn(cfg.kernel, d2, ls, 1.0) \
            + (cfg.noise + cfg.jitter) * eye
        chol = jnp.linalg.cholesky(k)
        return chol, jax.scipy.linalg.cho_solve((chol, True), ys)

    def nll(ls):
        chol, alpha = factor(ls)
        return 0.5 * ys @ alpha + jnp.log(jnp.diagonal(chol)).sum()

    grid = jnp.asarray(cfg.lengthscales, jnp.float32)
    ls = grid[jnp.argmin(jax.vmap(nll)(grid))]
    chol, alpha = factor(ls)
    return GPState(x=x, chol=chol, alpha=alpha, y_mean=y_mean, y_std=y_std,
                   lengthscale=ls, best=ys.min())


@pytest.mark.parametrize("n,d", [(13, 2), (31, 3), (47, 5)])
def test_gp_posterior_bit_exact_vs_jnp_reference(n, d):
    """The engine fit (fused kernel route) and the all-jnp reference fit
    agree within the posterior tolerance, and so does every posterior
    derived from them."""
    cfg = SurrogateConfig(bounds=((0., 1.),) * d, seed=0)
    kx, ky, kq = jax.random.split(jax.random.key(n * d), 3)
    x = jax.random.uniform(kx, (n, d), jnp.float32)
    y = jnp.sin(3.0 * x.sum(1)) + 0.1 * jax.random.normal(ky, (n,))
    st_eng = jax.jit(functools.partial(gp_fit, cfg))(x, y)
    st_ref = jax.jit(functools.partial(_ref_fit, cfg))(x, y)
    tol = kref.TOL["gp_posterior"]
    for a, b in zip(st_eng, st_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
    xq = jax.random.uniform(kq, (7, d), jnp.float32)
    post = jax.jit(functools.partial(gp_posterior, cfg))
    m_eng, c_eng = post(st_eng, xq)
    m_ref, c_ref = post(st_ref, xq)
    np.testing.assert_allclose(np.asarray(m_eng), np.asarray(m_ref), **tol)
    np.testing.assert_allclose(np.asarray(c_eng), np.asarray(c_ref), **tol)


def test_gp_posterior_bit_exact_with_duplicate_rows_and_prime_n():
    cfg = SurrogateConfig(bounds=((0., 1.),) * 2, seed=0)
    x = jax.random.uniform(jax.random.key(2), (23, 2), jnp.float32)
    x = x.at[11].set(x[5])
    y = (x ** 2).sum(1)
    st_eng = jax.jit(functools.partial(gp_fit, cfg))(x, y)
    st_ref = jax.jit(functools.partial(_ref_fit, cfg))(x, y)
    np.testing.assert_array_equal(np.asarray(st_eng.chol),
                                  np.asarray(st_ref.chol))
    np.testing.assert_array_equal(np.asarray(st_eng.alpha),
                                  np.asarray(st_ref.alpha))


def test_gp_posterior_interpolates_training_data():
    cfg = SurrogateConfig(bounds=((0., 1.),) * 2, noise=1e-6, seed=0)
    x = jax.random.uniform(jax.random.key(0), (20, 2), jnp.float32)
    y = jnp.cos(4.0 * x[:, 0]) + x[:, 1]
    state = gp_fit(cfg, x, y)
    mean, var = gp_mean_var(cfg, state, x)
    y_std = (y - state.y_mean) / state.y_std
    np.testing.assert_allclose(np.asarray(mean), np.asarray(y_std),
                               atol=5e-3)
    assert (np.asarray(var) < 1e-2).all()
    assert (np.asarray(var) >= cfg.jitter).all()


def test_gp_posterior_reverts_to_prior_far_away():
    cfg = SurrogateConfig(bounds=((0., 1.),) * 2, seed=0,
                          lengthscales=(0.05,))
    x = jax.random.uniform(jax.random.key(1), (16, 2), jnp.float32) * 0.2
    y = (x ** 2).sum(1)
    state = gp_fit(cfg, x, y)
    mean, var = gp_mean_var(cfg, state, jnp.ones((3, 2), jnp.float32))
    np.testing.assert_allclose(np.asarray(mean), 0.0, atol=1e-3)
    np.testing.assert_allclose(np.asarray(var), 1.0, atol=1e-2)


# ---------------------------------------------------------------------------
# deterministic tier: batch acquisition
# ---------------------------------------------------------------------------
def _random_mvn(key, q):
    km, kc = jax.random.split(key)
    mean = jax.random.normal(km, (q,), jnp.float32)
    a = jax.random.normal(kc, (q, q), jnp.float32)
    cov = a @ a.T + 0.1 * jnp.eye(q)
    return mean, cov


@pytest.mark.parametrize("q", [1, 2, 4, 8])
@pytest.mark.parametrize("seed", [0, 1, 7])
def test_qei_nonnegative(q, seed):
    mean, cov = _random_mvn(jax.random.key(seed), q)
    for best in (-2.0, 0.0, 3.0):
        v = float(q_ei(mean, cov, best, key=jax.random.key(seed + 1)))
        assert v >= 0.0


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_qei_monotone_in_q(seed):
    """Adding a point to a batch can never reduce Monte-Carlo q-EI: slot-
    keyed draws + nested Cholesky make the shared slots' samples identical,
    so the improvement is pointwise monotone — exactly, not just in
    expectation."""
    q_max = 6
    mean, cov = _random_mvn(jax.random.key(seed), q_max)
    key = jax.random.key(seed + 100)
    vals = [float(q_ei(mean[:q], cov[:q, :q], 0.5, key=key, n_samples=64))
            for q in range(1, q_max + 1)]
    for a, b in zip(vals, vals[1:]):
        assert b >= a, vals
    assert all(v >= 0.0 for v in vals)


def test_qei_known_certain_improvement():
    """A (nearly) deterministic batch point sitting `delta` below the
    incumbent has q-EI ~= delta."""
    mean = jnp.array([-1.0, 5.0], jnp.float32)
    cov = 1e-8 * jnp.eye(2, dtype=jnp.float32)
    v = float(q_ei(mean, cov, 0.0, key=jax.random.key(0), n_samples=128))
    np.testing.assert_allclose(v, 1.0, atol=1e-3)


def test_qucb_rewards_uncertainty():
    mean = jnp.zeros((2,), jnp.float32)
    tight = 1e-6 * jnp.eye(2, dtype=jnp.float32)
    wide = 4.0 * jnp.eye(2, dtype=jnp.float32)
    key = jax.random.key(0)
    assert float(q_ucb(mean, wide, 2.0, key=key)) \
        > float(q_ucb(mean, tight, 2.0, key=key))


def test_expected_improvement_closed_form_limits():
    # far below incumbent with tiny variance -> EI ~= best - mean
    ei = expected_improvement(jnp.array([-3.0]), jnp.array([1e-10]), 0.0)
    np.testing.assert_allclose(float(ei[0]), 3.0, rtol=1e-5)
    # far above incumbent with tiny variance -> EI ~= 0. The f32 normal
    # cdf 0.5 * (1 + erf(u / sqrt 2)) bottoms out near one ulp of 1 (2^-24
    # to 2^-23) instead of 0, and is scaled by |best - mean| = 3: allow
    # 3 * 2^-23 = 3.6e-7, far below what a bf16 evaluation would leave
    ei = expected_improvement(jnp.array([3.0]), jnp.array([1e-10]), 0.0)
    np.testing.assert_allclose(float(ei[0]), 0.0, atol=3 * 2.0 ** -23)


# ---------------------------------------------------------------------------
# deterministic tier: ask/tell explorer
# ---------------------------------------------------------------------------
_quadratic = surrogate_quadratic


def test_ask_returns_in_bounds_priority_batches():
    ex = SurrogateExplorer(CFG)
    for r in range(4):                     # 2 sobol rounds + 2 GP rounds
        xq = ex.ask()
        assert xq.shape == (CFG.q, CFG.dim)
        assert (xq >= 0.0).all() and (xq <= 100.0).all()
        keys = jax.random.split(jax.random.key(r), CFG.q)
        ex.tell(xq, np.asarray(_quadratic(keys, jnp.asarray(xq))))
    assert ex.round == 4 and len(ex.y) == 4 * CFG.q


def test_ask_tell_seed_deterministic():
    def trajectory(seed):
        import dataclasses
        ex = SurrogateExplorer(dataclasses.replace(CFG, seed=seed))
        out = []
        for r in range(3):
            xq = ex.ask()
            keys = jax.random.split(jax.random.key(1000 + r), CFG.q)
            ys = np.asarray(_quadratic(keys, jnp.asarray(xq)))
            ex.tell(xq, ys)
            out.append((xq.copy(), ys.copy()))
        return out

    a, b = trajectory(0), trajectory(0)
    for (xa, ya), (xb, yb) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    c = trajectory(1)
    assert not all(np.array_equal(xa, xc) for (xa, _), (xc, _) in zip(a, c))


def test_ask_tell_qucb_acquisition_path():
    import dataclasses
    ex = SurrogateExplorer(dataclasses.replace(CFG, acquisition="qucb",
                                               n_init=4))
    for r in range(2):                      # 1 sobol + 1 qucb round
        xq = ex.ask()
        assert xq.shape == (CFG.q, CFG.dim)
        assert (xq >= 0.0).all() and (xq <= 100.0).all()
        keys = jax.random.split(jax.random.key(r), CFG.q)
        ex.tell(xq, np.asarray(_quadratic(keys, jnp.asarray(xq))))
    assert np.isfinite(ex.y).all()


def test_sobol_seeding_matches_sampler_prefix():
    """The init phase IS the Sobol sampler: same points, bounds-mapped."""
    from repro.explore.sampling import _sobol_points
    ex = SurrogateExplorer(CFG)
    pts = _sobol_points(CFG.n_init_padded, CFG.dim, CFG.seed)
    batch = ex.ask()
    np.testing.assert_allclose(
        batch, 100.0 * pts[:CFG.q].astype(np.float32), rtol=1e-6)


def test_n_init_rounds_up_to_batch_multiple():
    cfg = SurrogateConfig(bounds=((0., 1.),), q=4, n_init=10)
    assert cfg.n_init_padded == 12


def test_run_surrogate_serial_improves_and_is_deterministic():
    res = run_surrogate(CFG, _quadratic, rounds=5)
    res2 = run_surrogate(CFG, _quadratic, rounds=5)
    assert not res.interrupted
    assert res.rounds_done == 5 and len(res.objectives) == 5 * CFG.q
    np.testing.assert_array_equal(res.objectives, res2.objectives)
    np.testing.assert_array_equal(res.genomes, res2.genomes)
    # the GP rounds must improve over the sobol-seeding incumbent
    sobol_best = res.objectives[:CFG.n_init_padded].min()
    assert res.best_objective <= sobol_best
    assert res.best_objective < 5.0      # converged near (30, 55)


def test_run_surrogate_checkpoint_resume_bit_exact(tmp_path):
    straight = run_surrogate(CFG, _quadratic, rounds=4)
    ckpt = str(tmp_path / "surr")
    part = run_surrogate(CFG, _quadratic, rounds=4, checkpoint_dir=ckpt,
                         stop_after_rounds=2)
    assert part.interrupted and part.rounds_done == 2
    assert part.genomes is None and part.objectives is None
    full = run_surrogate(CFG, _quadratic, rounds=4, checkpoint_dir=ckpt)
    assert not full.interrupted and full.resumed_rounds == 2
    np.testing.assert_array_equal(straight.objectives, full.objectives)
    np.testing.assert_array_equal(straight.genomes, full.genomes)


def test_rescore_orders_by_updated_posterior_without_mutation():
    ex = SurrogateExplorer(CFG)
    for r in range(2):
        xq = ex.ask()
        keys = jax.random.split(jax.random.key(r), CFG.q)
        ex.tell(xq, np.asarray(_quadratic(keys, jnp.asarray(xq))))
    before = (ex.x01.copy(), ex.y.copy(), ex.round)
    pending = np.random.default_rng(0).uniform(0, 1, (3, 2))
    scores = ex.rescore(np.array([[0.3, 0.55]]), [0.0], pending)
    assert scores.shape == (3,) and np.isfinite(scores).all()
    np.testing.assert_array_equal(before[0], ex.x01)
    np.testing.assert_array_equal(before[1], ex.y)
    assert before[2] == ex.round


# ---------------------------------------------------------------------------
# hypothesis tier (runs where hypothesis is installed — CI)
# ---------------------------------------------------------------------------
if HAS_HYPOTHESIS:

    @needs_hypothesis
    @settings(max_examples=15, deadline=None)
    @given(n1=st.integers(2, 48), n2=st.integers(2, 48),
           d=st.integers(2, 8), seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_gp_matrix_bit_exact(n1, n2, d, seed):
        k1, k2 = jax.random.split(jax.random.key(seed))
        x1, x2 = _xy(k1, n1, d), _xy(k2, n2, d)
        got = gp_matrix(x1, x2, block=32, interpret=True)
        want = _jit_matrix_ref(x1, x2, "matern52", 0.2, 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **kref.TOL["gp_matrix"])

    @needs_hypothesis
    @settings(max_examples=25, deadline=None)
    @given(q=st.integers(1, 6), seed=st.integers(0, 2 ** 31 - 1),
           best=st.floats(-3.0, 3.0))
    def test_hyp_qei_nonnegative_and_monotone(q, seed, best):
        mean, cov = _random_mvn(jax.random.key(seed % (2 ** 31)), q)
        key = jax.random.key((seed + 1) % (2 ** 31))
        vals = [float(q_ei(mean[:k], cov[:k, :k], best, key=key,
                           n_samples=48)) for k in range(1, q + 1)]
        assert all(v >= 0.0 for v in vals)
        # monotone up to f32 rounding of the Monte-Carlo mean: once the
        # extra slot never improves a sample, the two means are the same
        # sum reassociated by the compiler for another batch shape, and may
        # differ by a few ulps (2^-20 relative = 8 ulps)
        assert all(b >= a - 2.0 ** -20 * max(a, 1.0)
                   for a, b in zip(vals, vals[1:])), vals

    @needs_hypothesis
    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(3, 40), d=st.integers(1, 4),
           seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_gp_train_covariance_is_psd_with_jitter(n, d, seed):
        x = jax.random.uniform(jax.random.key(seed), (n, d), jnp.float32)
        k = np.asarray(kref.gp_matrix_ref(x, x)) + 1e-4 * np.eye(n)
        np.linalg.cholesky(k)          # raises if not PSD
        eig = np.linalg.eigvalsh(k)
        assert eig.min() > 0

    @needs_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_posterior_variance_shrinks_at_observations(seed):
        cfg = SurrogateConfig(bounds=((0., 1.),) * 2, seed=0)
        x = jax.random.uniform(jax.random.key(seed), (12, 2), jnp.float32)
        y = x.sum(1)
        state = gp_fit(cfg, x, y)
        _, var_at = gp_mean_var(cfg, state, x)
        far = jnp.clip(x + 0.5, 0.0, 1.5)
        _, var_far = gp_mean_var(cfg, state, far)
        assert float(var_at.mean()) < float(var_far.mean())
