"""XLA row sampler: the batched Cholesky and triangular solves that
``ops.sample_rows_noise`` runs where the Pallas kernel does not (off TPU,
K > 128, or fewer rows than one lane tile), and the kernel's parity
reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_rows_noise_ref(Lambda, eta, z, jitter: float = 1e-6):
    """x_n = Λ_n⁻¹η_n + L_n⁻ᵀz_n with L_n L_nᵀ = Λ_n + jitter·I, for
    Lambda (N, K, K), eta and z (N, K)."""
    K = eta.shape[-1]
    Lam = Lambda + jitter * jnp.eye(K)
    chol = jnp.linalg.cholesky(Lam)
    mu = jax.scipy.linalg.cho_solve((chol, True), eta[..., None])[..., 0]
    # x = mu + L^-T z has covariance Λ⁻¹
    delta = jax.scipy.linalg.solve_triangular(
        jnp.swapaxes(chol, -1, -2), z[..., None], lower=False)[..., 0]
    return mu + delta
