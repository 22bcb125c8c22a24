"""Poisson-Binomial pmf (paper eq. 9) and expected duration (eq. 8)."""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (enables x64)
from repro.core.poibin import (bernoulli_factor, chi_size,
                               expected_duration, materialised_factor,
                               poibin_chi, poibin_chi_fold,
                               poibin_chi_suffixes,
                               poibin_mean, poibin_pmf, poibin_pmf_recursive,
                               symmetric_pmf, unit_roots)


def brute_force_pmf(p):
    n = len(p)
    pmf = np.zeros(n + 1)
    for bits in itertools.product([0, 1], repeat=n):
        prob = 1.0
        for b, pi in zip(bits, p):
            prob *= pi if b else (1 - pi)
        pmf[sum(bits)] += prob
    return pmf


@pytest.mark.parametrize("p", [
    [0.5], [0.2, 0.8], [0.1, 0.5, 0.9], [0.3, 0.3, 0.3, 0.3],
    [0.05, 0.2, 0.45, 0.7, 0.99],
])
def test_pmf_matches_brute_force(p):
    got = np.asarray(poibin_pmf(jnp.asarray(p)))
    want = brute_force_pmf(p)
    np.testing.assert_allclose(got, want, atol=1e-12)


def test_pmf_matches_recursion_large_n():
    rng = np.random.default_rng(0)
    p = rng.uniform(0.01, 0.99, size=50)
    dft = np.asarray(poibin_pmf(jnp.asarray(p)))
    rec = np.asarray(poibin_pmf_recursive(jnp.asarray(p)))
    np.testing.assert_allclose(dft, rec, atol=1e-10)


def test_pmf_normalizes_and_mean():
    p = jnp.asarray([0.12, 0.5, 0.77, 0.3, 0.9, 0.05])
    pmf = poibin_pmf(p)
    assert float(jnp.sum(pmf)) == pytest.approx(1.0, abs=1e-12)
    mean = float(jnp.sum(pmf * jnp.arange(7)))
    assert mean == pytest.approx(float(poibin_mean(p)), abs=1e-10)


def test_symmetric_is_binomial():
    from scipy import stats
    n, p = 50, 0.37
    got = np.asarray(symmetric_pmf(jnp.asarray(p), n))
    want = stats.binom.pmf(np.arange(n + 1), n, p)
    np.testing.assert_allclose(got, want, atol=1e-10)


def test_expected_duration_monte_carlo():
    rng = np.random.default_rng(1)
    p = jnp.asarray(rng.uniform(0.1, 0.9, size=12))
    d_of_k = jnp.asarray(100.0 / (1.0 + np.arange(13)))
    analytic = float(expected_duration(p, d_of_k))
    draws = rng.random((200_000, 12)) < np.asarray(p)
    k = draws.sum(axis=1)
    mc = float(np.mean(np.asarray(d_of_k)[k]))
    assert analytic == pytest.approx(mc, rel=2e-2)


def test_gradient_flows_through_pmf():
    def f(p):
        return expected_duration(p, jnp.arange(4.0))

    g = jax.grad(f)(jnp.asarray([0.3, 0.5, 0.7]))
    assert np.all(np.isfinite(np.asarray(g)))
    # E[D] = E[k] here, so gradient wrt each p_i is exactly 1
    np.testing.assert_allclose(np.asarray(g), 1.0, atol=1e-8)


def direct_pmf(p):
    """Pmf of sum of Bernoulli(p_k) by multiplying in one factor at a time."""
    pmf = np.array([1.0])
    for pk in p:
        pmf = np.convolve(pmf, [1.0 - pk, pk])
    return pmf


def inverse_dft(chi):
    """Real pmf over m = 0..size-1 from a characteristic function given
    at the ``size`` points exp(j 2 pi n/size)."""
    spec = np.asarray(chi[0]) + 1j * np.asarray(chi[1])
    size = spec.shape[0]
    m = np.arange(size)
    return (np.exp(-2j * np.pi * np.outer(m, m) / size) @ spec).real / size


@functools.partial(jax.jit, static_argnums=1)
def chi_leave_one_out(p, i, w):
    """Node i's leave-one-out characteristic function: the product of the
    factors before it times the suffix product of those after it."""
    one = jnp.ones(w[0].shape, p.dtype)
    suf_re, suf_im = poibin_chi_suffixes(p, w, (one, jnp.zeros_like(one)))
    pre_re, pre_im = poibin_chi(p[:i], w)
    return (pre_re * suf_re[i] - pre_im * suf_im[i],
            pre_re * suf_im[i] + pre_im * suf_re[i])


@pytest.mark.parametrize("n", [1, 2, 50])
@pytest.mark.parametrize("p_i", [0.0, 0.5, 1.0, None])
def test_chi_leave_one_out_matches_direct_pmf_of_others(n, p_i):
    """Prefix times suffix products leave out node i's factor: the
    inverse transform is the others' pmf, built directly."""
    rng = np.random.default_rng(7 + n)
    p = rng.uniform(0.0, 1.0, n)
    i = n // 2
    if p_i is not None:
        p[i] = p_i
    size = chi_size(n)
    assert size % 2 == 1 and size >= n + 1
    w = unit_roots(size)
    got = inverse_dft(chi_leave_one_out(jnp.asarray(p), i, w))
    want = direct_pmf(np.delete(p, i))
    np.testing.assert_allclose(got[:n], want, rtol=0, atol=1e-13)
    np.testing.assert_allclose(got[n:], 0.0, rtol=0, atol=1e-13)


@pytest.mark.parametrize("n", [1, 2, 50])
def test_chi_factor_at_zero_is_bitwise_identity(n):
    """p = 0's factor is exactly 1: folding it in leaves every bit of the
    characteristic function in place, and a suffix product steps over it
    unchanged."""
    rng = np.random.default_rng(11)
    w = unit_roots(chi_size(n))
    chi = poibin_chi(jnp.asarray(rng.uniform(0.0, 1.0, n)), w)
    zero = jnp.asarray(0.0)
    for factor in (bernoulli_factor(zero, w), materialised_factor(zero, w)):
        for a, b in zip(poibin_chi_fold(chi, factor), chi):
            np.testing.assert_array_equal(np.asarray(a).view(np.int64),
                                          np.asarray(b).view(np.int64))
    p = jnp.asarray(rng.uniform(0.0, 1.0, n + 1)).at[1].set(0.0)
    suf = poibin_chi_suffixes(p, w, chi)
    for part in suf:
        np.testing.assert_array_equal(np.asarray(part[0]).view(np.int64),
                                      np.asarray(part[1]).view(np.int64))


def test_pmf_keeps_its_chi_product():
    """poibin_pmf's DFT inverts the shared characteristic-function product."""
    p = jnp.asarray(np.random.default_rng(3).uniform(0.0, 1.0, 9))
    chi = poibin_chi(p, unit_roots(10))
    np.testing.assert_allclose(np.asarray(poibin_pmf(p)), inverse_dft(chi),
                               rtol=0, atol=1e-14)
