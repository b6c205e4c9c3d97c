"""Gaussian posterior algebra in natural parameters + Wishart sampling.

Posterior Propagation combines per-row Gaussian posteriors multiplicatively
and divides away multiply-counted priors. In natural parameters
(eta = Λ μ, Λ = precision) both operations are additions/subtractions:

    N(μ1,Λ1⁻¹)·N(μ2,Λ2⁻¹) ∝ N(Λ⁻¹η, Λ⁻¹),  Λ = Λ1+Λ2, η = η1+η2
    N1 / N2               ->  Λ = Λ1-Λ2, η = η1-η2   (valid if Λ ≻ 0)

All functions are batched over leading row axes: mu (N, K), Lambda (N, K, K).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# The NW hyperprior's products run in full f32: at default precision a TPU
# runs an f32 dot as one bf16 pass, and a Wishart draw from a scatter
# matrix rounded to bf16 takes the chain elsewhere within a few sweeps.
HI = jax.lax.Precision.HIGHEST


class RowGaussians(NamedTuple):
    """Per-row Gaussian beliefs over factor rows. eta = Λ μ."""
    eta: jnp.ndarray      # (N, K)
    Lambda: jnp.ndarray   # (N, K, K)

    @property
    def mean(self):
        return jnp.linalg.solve(self.Lambda, self.eta[..., None])[..., 0]

    @property
    def cov(self):
        return _chol_inverse(jnp.linalg.cholesky(self.Lambda))


def _chol_inverse(L):
    """inv(L Lᵀ) via two batched triangular solves — O(K³/3) factor reuse,
    no LU / explicit ``jnp.linalg.inv``."""
    K = L.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(K, dtype=L.dtype), L.shape)
    return jax.scipy.linalg.cho_solve((L, True), eye)


def from_moments(mu, Lambda) -> RowGaussians:
    eta = jnp.einsum("...ij,...j->...i", Lambda, mu)
    return RowGaussians(eta=eta, Lambda=Lambda)


def from_moments_cov(mu, cov, ridge: float = 0.0) -> RowGaussians:
    """Natural params from (mean, COVARIANCE) moments via one Cholesky
    factor + triangular solves: η = Σ⁻¹μ and Λ = Σ⁻¹ share the factor.
    This replaces the ``jnp.linalg.inv(cov)`` + matmul path in the Gibbs
    summarization hot loop."""
    K = mu.shape[-1]
    if ridge:
        cov = cov + ridge * jnp.eye(K, dtype=cov.dtype)
    L = jnp.linalg.cholesky(cov)
    eta = jax.scipy.linalg.cho_solve((L, True), mu[..., None])[..., 0]
    return RowGaussians(eta=eta, Lambda=_chol_inverse(L))


def broadcast_prior(mu, Lambda, n_rows: int) -> RowGaussians:
    """Shared prior (mu (K,), Lambda (K,K)) -> per-row natural params."""
    K = mu.shape[-1]
    eta = jnp.matmul(Lambda, mu, precision=HI)
    eta = jnp.repeat(eta[None, :], n_rows, axis=0)
    Lam = jnp.broadcast_to(Lambda, (n_rows, K, K))
    return RowGaussians(eta=eta, Lambda=Lam)


def product(a: RowGaussians, b: RowGaussians) -> RowGaussians:
    return RowGaussians(eta=a.eta + b.eta, Lambda=a.Lambda + b.Lambda)


def divide(a: RowGaussians, b: RowGaussians) -> RowGaussians:
    return RowGaussians(eta=a.eta - b.eta, Lambda=a.Lambda - b.Lambda)


def scale(a: RowGaussians, c: float) -> RowGaussians:
    return RowGaussians(eta=c * a.eta, Lambda=c * a.Lambda)


def from_samples(samples, ridge: float = 1e-4) -> RowGaussians:
    """Summarize MCMC draws (T, N, K) as per-row Gaussians.

    Precision = inv(sample covariance + ridge·I); the ridge keeps the
    estimate PD for small T (as in Qin et al. 2019).
    """
    T, N, K = samples.shape
    mean = samples.mean(0)                                # (N, K)
    centered = samples - mean
    cov = jnp.einsum("tnk,tnl->nkl", centered, centered) / max(T - 1, 1)
    return from_moments_cov(mean, cov, ridge=ridge)


def sample_rows_noise(g: RowGaussians, z: jnp.ndarray,
                      jitter: float = 1e-6):
    """``sample_rows`` with the standard-normal draw ``z`` (N, K) supplied
    by the caller. Row-local math — the data-sharded intra-block sweep
    feeds each shard the SLICE of the full replicated draw so its local
    rows match the single-device sample bit-for-bit. The Pallas kernel or
    XLA's batched Cholesky and solves, chosen from the platform, K and N
    (``kernels/bmf_sample``). Runs under the ``bmf_sample`` named scope
    (see ``gibbs._run_gibbs_impl``)."""
    from repro.kernels.bmf_sample import ops as SAMPLE   # imports Pallas
    with jax.named_scope("bmf_sample"):
        return SAMPLE.sample_rows_noise(g.Lambda, g.eta, z, jitter)


def sample_rows(key, g: RowGaussians, jitter: float = 1e-6):
    """Draw one row each: x_n ~ N(Λ_n⁻¹ η_n, Λ_n⁻¹), via Cholesky of Λ."""
    N, K = g.eta.shape
    z = jax.random.normal(key, (N, K), dtype=g.eta.dtype)
    return sample_rows_noise(g, z, jitter)


# ---------------------------------------------------------------------------
# Wishart / Normal-Wishart (BPMF hyperpriors)
# ---------------------------------------------------------------------------


class NormalWishart(NamedTuple):
    mu0: jnp.ndarray      # (K,)
    beta0: jnp.ndarray    # scalar
    W0: jnp.ndarray       # (K, K) scale matrix
    nu0: jnp.ndarray      # scalar degrees of freedom (> K-1)


def default_nw(K: int, dtype=jnp.float32) -> NormalWishart:
    return NormalWishart(
        mu0=jnp.zeros((K,), dtype),
        beta0=jnp.asarray(2.0, dtype),
        W0=jnp.eye(K, dtype=dtype),
        nu0=jnp.asarray(float(K), dtype),
    )


def sample_wishart(key, W: jnp.ndarray, nu, dtype=None):
    """Bartlett decomposition: X ~ W_K(W, nu)."""
    K = W.shape[-1]
    dtype = dtype or W.dtype
    kg, kn = jax.random.split(key)
    # diag: sqrt of chi2(nu - i) = 2*Gamma((nu-i)/2)
    i = jnp.arange(K, dtype=dtype)
    df = (nu - i) / 2.0
    chi2 = 2.0 * jax.random.gamma(kg, df, dtype=dtype)
    A = jnp.diag(jnp.sqrt(chi2))
    lower = jnp.tril(jax.random.normal(kn, (K, K), dtype=dtype), -1)
    A = A + lower
    L = jnp.linalg.cholesky(W + 1e-6 * jnp.eye(K, dtype=dtype))
    LA = jnp.matmul(L, A, precision=HI)
    return jnp.matmul(LA, LA.T, precision=HI)


def nw_posterior(prior: NormalWishart, X: jnp.ndarray) -> NormalWishart:
    """Conjugate NW update given rows X (N, K)."""
    N, K = X.shape
    xbar = X.mean(0)
    S = jnp.einsum("nk,nl->kl", X - xbar, X - xbar,
                   precision=HI)                        # N * sample cov
    beta_n = prior.beta0 + N
    nu_n = prior.nu0 + N
    mu_n = (prior.beta0 * prior.mu0 + N * xbar) / beta_n
    d = (xbar - prior.mu0)[:, None]
    W0_inv = _chol_inverse(jnp.linalg.cholesky(prior.W0))
    Wn_inv = W0_inv + S + (prior.beta0 * N / beta_n) * jnp.matmul(
        d, d.T, precision=HI)
    Wn = _chol_inverse(jnp.linalg.cholesky(Wn_inv))
    return NormalWishart(mu0=mu_n, beta0=beta_n, W0=Wn, nu0=nu_n)


def sample_nw(key, nw: NormalWishart):
    """Draw (mu, Lambda) ~ NW."""
    kw, km = jax.random.split(key)
    Lam = sample_wishart(kw, nw.W0, nw.nu0)
    K = Lam.shape[-1]
    # mu ~ N(mu0, (β Λ)⁻¹): with βΛ = L Lᵀ, x = L⁻ᵀ z has the right
    # covariance — one triangular solve, no inverse-then-Cholesky
    L = jnp.linalg.cholesky(nw.beta0 * Lam + 1e-6 * jnp.eye(K))
    z = jax.random.normal(km, (K,), dtype=Lam.dtype)
    mu = nw.mu0 + jax.scipy.linalg.solve_triangular(L.T, z, lower=False)
    return mu, Lam
