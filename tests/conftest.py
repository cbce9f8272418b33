import os

# Tests run on the host CPU; those that need several devices force them in
# their own subprocesses.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)


def surrogate_tiny_config(**overrides):
    """THE shared tiny surrogate config of the surrogate/chaos/golden
    suites. One definition so every module hits the same per-config jit
    cache (surrogate._jitted caches on config equality — a silently
    drifting copy would de-duplicate the cache and slow the whole run)."""
    from repro.explore.surrogate import SurrogateConfig
    base = dict(bounds=((0., 100.), (0., 100.)), q=4, n_init=8,
                mc_samples=32, n_starts=4, opt_steps=8, seed=0)
    base.update(overrides)
    return SurrogateConfig(**base)


def surrogate_quadratic(keys, genomes):
    """The noisy 2-d quadratic fitness those suites share: minimum near
    (30, 55), replicate noise keyed per evaluation."""
    import jax.numpy as jnp  # noqa: F401  (kept local: conftest stays light)
    noise = jax.vmap(lambda k: jax.random.normal(k))(keys)
    d, e = genomes[:, 0], genomes[:, 1]
    return (d - 30.) ** 2 / 100 + (e - 55.) ** 2 / 100 + 0.05 * noise
