"""Bayesian Probabilistic Matrix Factorization (Salakhutdinov & Mnih 2008).

Model:
    r_nd ~ N(u_nᵀ v_d, τ⁻¹)                   observed entries only
    u_n  ~ N(μ_U, Λ_U⁻¹),  (μ_U, Λ_U) ~ NW    (likewise for v_d)

Gibbs conditionals per row (the compute hot-spot, see kernels/bmf_precision):
    Λ_n = Λ_prior_n + τ Σ_{d∈Ω_n} v_d v_dᵀ
    η_n = η_prior_n + τ Σ_{d∈Ω_n} r_nd v_d
    u_n ~ N(Λ_n⁻¹ η_n, Λ_n⁻¹)

Priors are per-row ``RowGaussians`` so the same code serves both the vanilla
NW-hyperprior case (broadcast) and Posterior-Propagation propagated
posteriors.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import posterior as POST
from repro.core.posterior import NormalWishart, RowGaussians
from repro.data.sparse import PaddedCSR


class BMFConfig(NamedTuple):
    K: int = 16
    tau: float = 2.0              # residual precision (paper: fixed alpha=2)
    n_samples: int = 60
    burnin: int = 20
    use_kernel: bool = False      # Pallas bmf_precision kernel vs jnp ref
    # beyond-paper (listed as future work in §4): shorter chains for PP
    # phases b/c, justified by the informative propagated priors.
    # None = paper-faithful (same n_samples everywhere).
    phase_bc_samples: Optional[int] = None
    # one-kernel Gibbs sweep (kernels/bmf_sweep): the whole factor step —
    # gather, Λ/η accumulate, Cholesky, triangular solves, noise add — as a
    # single pass (Pallas on TPU, bitwise-identical striped XLA elsewhere).
    # sweep_dtype: 'fp32', or 'bf16' for the mixed-precision mode (bf16
    # gather/accumulate, f32 factorization) gated by the conformance
    # suite's RMSE-parity check.
    sweep_fused: bool = False
    sweep_dtype: str = "fp32"


def sufficient_stats(csr: PaddedCSR, other: jnp.ndarray, tau: float,
                     use_kernel: bool = False):
    """Per-row likelihood contributions (Λ_contrib, η_contrib).

    csr: rows of R (N, M) padded; other: the *other* factor matrix (D, K).
    Returns (N, K, K), (N, K). This gather + masked rank-1 accumulation is
    O(nnz · K²).  use_kernel=True routes through the zero-materialization
    hot path (repro/kernels/bmf_precision): the fused-gather Pallas kernel
    on TPU, an N-striped symmetric matmul elsewhere — neither builds the
    (N, M, K) gathered tensor the jnp path below materializes.

    Runs under the ``bmf_stats`` named scope (see ``gibbs._run_gibbs_impl``).
    """
    with jax.named_scope("bmf_stats"):
        if use_kernel:
            from repro.kernels.bmf_precision import ops as KOPS
            return KOPS.precision_accum(csr.idx, csr.val, csr.mask, other,
                                        tau)
        V = other[csr.idx]                                  # (N, M, K)
        Vm = V * csr.mask[..., None]
        Lam = tau * jnp.einsum("nmk,nml->nkl", Vm, V)
        eta = tau * jnp.einsum("nm,nmk->nk", csr.val * csr.mask, V)
        return Lam, eta


def sample_factor(key, csr: PaddedCSR, other: jnp.ndarray, tau: float,
                  prior: RowGaussians, use_kernel: bool = False) -> jnp.ndarray:
    """Draw all rows of one factor from their Gibbs conditional."""
    Lam_c, eta_c = sufficient_stats(csr, other, tau, use_kernel)
    cond = RowGaussians(eta=prior.eta + eta_c, Lambda=prior.Lambda + Lam_c)
    return POST.sample_rows(key, cond)


def sample_hyper(key, X: jnp.ndarray, nw_prior: NormalWishart):
    """(μ, Λ) ~ NW posterior given current factor rows X."""
    post = POST.nw_posterior(nw_prior, X)
    return POST.sample_nw(key, post)


def predict(U: jnp.ndarray, V: jnp.ndarray, rows: jnp.ndarray,
            cols: jnp.ndarray) -> jnp.ndarray:
    """Pointwise predictions for test entries."""
    return jnp.einsum("ek,ek->e", U[rows], V[cols])


def init_factors(key, N: int, D: int, K: int, scale: float = 0.1):
    ku, kv = jax.random.split(key)
    U = scale * jax.random.normal(ku, (N, K), jnp.float32)
    V = scale * jax.random.normal(kv, (D, K), jnp.float32)
    return U, V
