"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.diffusion import diffuse_evaporate as diffuse_pallas
from repro.kernels.dominance import dominated_counts as dom_pallas
from repro.kernels.flash_attention import flash_attention as flash_pallas


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 2, 2, 64, 16),      # MHA
    (2, 4, 2, 128, 32),     # GQA group 2
    (1, 6, 1, 64, 64),      # MQA-ish
    (1, 8, 2, 256, 64),     # deeper blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, kh, s, d, dtype):
    ks = jax.random.split(jax.random.key(b * h + s), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kh, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kh, s, d), dtype)
    out = flash_pallas(q, k, v, causal=True, block_q=32, block_k=64,
                       interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 16))
    k = jax.random.normal(ks[1], (1, 2, 64, 16))
    v = jax.random.normal(ks[2], (1, 2, 64, 16))
    out = flash_pallas(q, k, v, causal=False, block_q=32, block_k=32,
                       interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_row_sums():
    """Attention of v=ones must return ones (softmax normalization)."""
    ks = jax.random.split(jax.random.key(3), 2)
    q = jax.random.normal(ks[0], (1, 2, 64, 16))
    k = jax.random.normal(ks[1], (1, 2, 64, 16))
    v = jnp.ones((1, 2, 64, 16))
    out = flash_pallas(q, k, v, causal=True, block_q=16, block_k=16,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# diffusion
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,w", [(1, 16), (4, 32), (8, 33), (3, 72)])
def test_diffusion_sweep(n, w):
    key = jax.random.key(n * w)
    chem = jax.random.uniform(key, (n, w, w), jnp.float32) * 10
    rate = jnp.linspace(0.05, 0.95, n)
    evap = jnp.linspace(0.0, 0.5, n)
    out = diffuse_pallas(chem, rate, evap, interpret=True)
    expect = ref.diffuse_evaporate_ref(chem, rate, evap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n,w", [(130, 72), (5, 17)])
def test_diffusion_within_shared_tolerance(n, w):
    """The chip's tolerance (ref.TOL, used by chip_smoke.py) holds in
    interpret mode too: 130 lanes pad to two 128-lane blocks at the
    paper's world width; W = 17 leaves a partial row strip."""
    ks = jax.random.split(jax.random.key(n + w), 3)
    chem = jax.random.uniform(ks[0], (n, w, w), jnp.float32)
    rate = jax.random.uniform(ks[1], (n,), jnp.float32)
    evap = jax.random.uniform(ks[2], (n,), jnp.float32)
    out = diffuse_pallas(chem, rate, evap, interpret=True)
    assert out.shape == (n, w, w)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.diffuse_evaporate_ref(chem, rate, evap)),
        **ref.TOL["diffusion"])


def test_diffusion_conserves_mass_without_evaporation():
    key = jax.random.key(5)
    chem = jax.random.uniform(key, (4, 24, 24), jnp.float32)
    out = diffuse_pallas(chem, jnp.full((4,), 0.7), jnp.zeros((4,)),
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out).sum((1, 2)),
                               np.asarray(chem).sum((1, 2)), rtol=1e-5)


def test_diffusion_nonnegative():
    key = jax.random.key(6)
    chem = jax.random.uniform(key, (2, 16, 16), jnp.float32)
    out = diffuse_pallas(chem, jnp.full((2,), 0.99), jnp.full((2,), 0.99),
                         interpret=True)
    assert (np.asarray(out) >= -1e-6).all()


# ---------------------------------------------------------------------------
# dominance
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m", [(8, 2), (64, 3), (100, 4), (256, 3), (33, 5)])
def test_dominance_sweep(n, m):
    f = jax.random.uniform(jax.random.key(n + m), (n, m), jnp.float32)
    out = dom_pallas(f, block=32, interpret=True)
    expect = ref.dominated_counts_ref(f)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_dominance_known_case():
    # 0 dominates 1 and 2; 1 dominates 2; 3 is incomparable (better in obj 2)
    f = jnp.array([[0., 0.], [1., 1.], [2., 2.], [3., -1.]])
    out = np.asarray(dom_pallas(f, interpret=True))
    np.testing.assert_array_equal(out, [0, 1, 2, 0])


def test_dominance_duplicates_do_not_dominate():
    f = jnp.ones((16, 3))
    out = np.asarray(dom_pallas(f, interpret=True))
    np.testing.assert_array_equal(out, np.zeros(16))


# ---------------------------------------------------------------------------
# flash attention backward (custom_vjp) vs autodiff of the oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 2, 2, 64, 16),
    (2, 4, 2, 128, 32),
    (1, 6, 3, 64, 16),
])
def test_flash_backward_matches_autodiff(b, h, kh, s, d):
    from repro.kernels.flash_attention_bwd import flash_attention_diff
    ks = jax.random.split(jax.random.key(b * 7 + s), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, kh, s, d))
    v = jax.random.normal(ks[2], (b, kh, s, d))

    def f_kern(q, k, v):
        return flash_attention_diff(q, k, v, True, 32, 32, True).sum()

    def f_ref(q, k, v):
        return ref.flash_attention_ref(q, k, v, causal=True).astype(
            jnp.float32).sum()

    gk = jax.grad(f_kern, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b2 in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   atol=2e-4, rtol=2e-4)


def test_flash_fwd_lse_matches_softmax():
    from repro.kernels.flash_attention_bwd import flash_attention_fwd
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 16))
    k = jax.random.normal(ks[1], (1, 2, 64, 16))
    v = jax.random.normal(ks[2], (1, 2, 64, 16))
    out, lse = flash_attention_fwd(q, k, v, causal=True, block_q=32,
                                   block_k=32, interpret=True)
    import math as _m
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / _m.sqrt(16)
    mask = jnp.tril(jnp.ones((64, 64), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    expect_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(expect_lse),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.flash_attention_ref(q, k, v)),
        atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the shared kernel-vs-oracle tolerances still catch reduced precision
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["diffusion", "gp_sqdist", "gp_matrix"])
def test_tolerance_rejects_a_bf16_computation(name):
    """``ref.TOL`` admits f32 rounding differences only: the same oracle
    evaluated in bfloat16 must fall outside it."""
    k = jax.random.split(jax.random.key(7), 3)
    if name == "diffusion":
        fn = ref.diffuse_evaporate_ref
        args = (jax.random.uniform(k[0], (4, 24, 24), jnp.float32),
                jax.random.uniform(k[1], (4,), jnp.float32),
                jax.random.uniform(k[2], (4,), jnp.float32))
    else:
        fn = ref.gp_sqdist_ref if name == "gp_sqdist" else ref.gp_matrix_ref
        x = jax.random.uniform(k[0], (64, 4), jnp.float32) * 2.0
        args = (x, x[::-1])
    want = np.asarray(fn(*args))
    low = np.asarray(fn(*[a.astype(jnp.bfloat16) for a in args]),
                     np.float32)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(low, want, **ref.TOL[name])
