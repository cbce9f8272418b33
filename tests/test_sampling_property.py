"""Property tests for explore/sampling.py — the design-of-experiments
generators behind exploration transitions (paper §4.4).

Two tiers:
- deterministic parametrized properties that always run (no extra deps);
- Hypothesis-driven generalizations of the same properties, skipped with a
  reason when hypothesis is absent (CI installs it, so they run there).

Properties pinned: points in-bounds and cardinality-exact (Sobol/LHS/
uniform), LHS stratification, factorial cross-product size, and
seed-sampling determinism.
"""
import itertools

import numpy as np
import pytest

from repro.core import Context, Val
from repro.explore import (GridSampling, LHSSampling, SeedSampling,
                           SobolSampling, UniformSampling)
from repro.explore.sampling import CrossSampling, _sobol_points

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed; the deterministic "
    "tier of these properties still runs")

x = Val("x", float)
y = Val("y", float)


def _points(sampling):
    return list(sampling.contexts(Context()))


# ---------------------------------------------------------------------------
# deterministic tier (always runs)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cls", [UniformSampling, LHSSampling, SobolSampling])
@pytest.mark.parametrize("n", [1, 7, 16, 33])
@pytest.mark.parametrize("seed", [0, 1, 12345])
def test_bounded_samplings_in_bounds_and_cardinality_exact(cls, n, seed):
    lo, hi = -2.5, 7.25
    s = cls({x: (lo, hi), y: (0.0, 1.0)}, n, seed=seed)
    pts = _points(s)
    assert len(pts) == n == len(s)
    for p in pts:
        assert lo <= p["x"] <= hi
        assert 0.0 <= p["y"] <= 1.0


@pytest.mark.parametrize("dim", [1, 2, 5, 16])
def test_sobol_points_shape_and_range(dim):
    pts = _sobol_points(64, dim, seed=3)
    assert pts.shape == (64, dim)
    assert (pts >= 0).all() and (pts < 1).all()


@pytest.mark.parametrize("seed", [0, 7, 99])
@pytest.mark.parametrize("n", [4, 10, 25])
def test_lhs_stratification_exact(seed, n):
    s = LHSSampling({x: (0.0, 1.0)}, n, seed=seed)
    pts = sorted(p["x"] for p in _points(s))
    for i, p in enumerate(pts):                 # exactly one per stratum
        assert i / n <= p <= (i + 1) / n


@pytest.mark.parametrize("shape", [(2,), (3, 4), (2, 3, 4), (1, 5, 1)])
def test_factorial_cross_product_size(shape):
    vals = [Val(f"v{i}", float) for i in range(len(shape))]
    samplings = [GridSampling({v: [float(j) for j in range(k)]})
                 for v, k in zip(vals, shape)]
    crossed = samplings[0]
    for s in samplings[1:]:
        crossed = crossed * s
    pts = _points(crossed)
    expect = int(np.prod(shape))
    assert len(crossed) == expect == len(pts)
    combos = {tuple(p[v.name] for v in vals) for p in pts}
    assert len(combos) == expect                # full factorial, no dupes
    assert combos == set(itertools.product(
        *[[float(j) for j in range(k)] for k in shape]))


@pytest.mark.parametrize("seed", [0, 3, 42])
def test_seed_sampling_determinism_and_range(seed):
    a = [p["seed"] for p in _points(SeedSampling(Val("seed"), 20, seed=seed))]
    b = [p["seed"] for p in _points(SeedSampling(Val("seed"), 20, seed=seed))]
    assert a == b
    assert all(0 <= s < 2 ** 31 - 1 for s in a)
    other = [p["seed"] for p in
             _points(SeedSampling(Val("seed"), 20, seed=seed + 1))]
    assert a != other


def test_sampling_determinism_across_calls():
    """contexts() must be replayable: two iterations, identical points —
    the property that makes exploration transitions memoizable."""
    for s in [UniformSampling({x: (0., 5.)}, 9, seed=2),
              LHSSampling({x: (0., 5.)}, 9, seed=2),
              SobolSampling({x: (0., 5.)}, 9, seed=2)]:
        assert [p["x"] for p in _points(s)] == [p["x"] for p in _points(s)]


# ---------------------------------------------------------------------------
# hypothesis tier (runs where hypothesis is installed — CI)
# ---------------------------------------------------------------------------
if HAS_HYPOTHESIS:
    bounds_st = st.tuples(
        st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False),
        st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False),
    ).map(sorted).filter(lambda b: b[1] - b[0] > 1e-6)

    @needs_hypothesis
    @settings(max_examples=30, deadline=None)
    @given(n=st.integers(1, 50), seed=st.integers(0, 2 ** 31 - 1),
           bounds=bounds_st)
    def test_hyp_bounded_samplings_cardinality_and_bounds(n, seed, bounds):
        lo, hi = bounds
        for cls in (UniformSampling, LHSSampling, SobolSampling):
            s = cls({x: (lo, hi)}, n, seed=seed)
            pts = [p["x"] for p in _points(s)]
            assert len(pts) == n == len(s)
            assert all(lo <= p <= hi for p in pts)

    @needs_hypothesis
    @settings(max_examples=30, deadline=None)
    @given(n=st.integers(2, 40), seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_lhs_one_point_per_stratum(n, seed):
        s = LHSSampling({x: (0.0, 1.0)}, n, seed=seed)
        strata = sorted(int(min(p["x"] * n, n - 1)) for p in _points(s))
        assert strata == list(range(n))

    @needs_hypothesis
    @settings(max_examples=30, deadline=None)
    @given(ks=st.lists(st.integers(1, 6), min_size=1, max_size=4),
           seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_cross_product_cardinality_law(ks, seed):
        vals = [Val(f"v{i}", float) for i in range(len(ks))]
        parts = [GridSampling({v: [float(j) for j in range(k)]})
                 for v, k in zip(vals, ks)]
        crossed = parts[0]
        for p in parts[1:]:
            crossed = CrossSampling(crossed, p)
        assert len(crossed) == int(np.prod(ks)) == len(_points(crossed))

    @needs_hypothesis
    @settings(max_examples=30, deadline=None)
    @given(n=st.integers(1, 64), seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_seed_sampling_deterministic(n, seed):
        a = [p["s"] for p in _points(SeedSampling(Val("s"), n, seed=seed))]
        b = [p["s"] for p in _points(SeedSampling(Val("s"), n, seed=seed))]
        assert a == b and len(a) == n

    @needs_hypothesis
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 128), dim=st.integers(1, 16),
           seed=st.integers(0, 2 ** 31 - 1))
    def test_hyp_sobol_unit_cube(n, dim, seed):
        pts = _sobol_points(n, dim, seed=seed)
        assert pts.shape == (n, dim)
        assert ((pts >= 0) & (pts < 1)).all()
