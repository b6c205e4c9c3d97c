"""Correctness of the BMF core: conjugate math, Gibbs RMSE, PP parity.

These validate the paper's central claims at test scale:
  - the per-row Gibbs conditional matches the closed-form Gaussian posterior
    (linear-Gaussian conjugacy) when sampling noise is marginalized,
  - full BMF beats a mean predictor on synthetic low-rank data,
  - BMF+PP achieves RMSE close to full BMF (paper Table 2 claim),
  - natural-parameter algebra invariants (product/divide round-trip).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS
from repro.core import posterior as POST
from repro.core import pp as PP
from repro.core.partition import partition, suggest_grid
from repro.data import synthetic as SYN
from repro.data.sparse import COO, coo_to_padded_csr, train_test_split


def test_sufficient_stats_match_dense():
    """Λ/η contributions equal the dense masked computation."""
    rng = np.random.default_rng(0)
    N, D, K, M = 7, 5, 3, 4
    idx = rng.integers(0, D, (N, M)).astype(np.int32)
    val = rng.normal(size=(N, M)).astype(np.float32)
    mask = (rng.random((N, M)) < 0.7).astype(np.float32)
    V = rng.normal(size=(D, K)).astype(np.float32)
    csr = __import__("repro.data.sparse", fromlist=["PaddedCSR"]).PaddedCSR(
        idx=jnp.asarray(idx), val=jnp.asarray(val), mask=jnp.asarray(mask),
        n_cols=D)
    tau = 1.7
    Lam, eta = BMF.sufficient_stats(csr, jnp.asarray(V), tau)
    for n in range(N):
        lam_ref = np.zeros((K, K))
        eta_ref = np.zeros(K)
        for m in range(M):
            if mask[n, m]:
                v = V[idx[n, m]]
                lam_ref += tau * np.outer(v, v)
                eta_ref += tau * val[n, m] * v
        np.testing.assert_allclose(np.asarray(Lam[n]), lam_ref, rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(eta[n]), eta_ref, rtol=2e-4, atol=1e-4)


def test_gibbs_conditional_matches_closed_form():
    """With fixed V and fixed prior, the mean of many Gibbs draws of u_n
    approaches the closed-form posterior mean Λ⁻¹η."""
    rng = np.random.default_rng(1)
    D, K = 12, 3
    V = rng.normal(size=(D, K)).astype(np.float32)
    u_true = rng.normal(size=(K,)).astype(np.float32)
    tau = 4.0
    r = V @ u_true + rng.normal(0, 1 / np.sqrt(tau), D).astype(np.float32)

    from repro.data.sparse import PaddedCSR
    csr = PaddedCSR(idx=jnp.arange(D, dtype=jnp.int32)[None, :],
                    val=jnp.asarray(r)[None, :],
                    mask=jnp.ones((1, D), jnp.float32), n_cols=D)
    prior = POST.broadcast_prior(jnp.zeros(K), jnp.eye(K), 1)

    # closed form
    Lam = np.eye(K) + tau * V.T @ V
    eta = tau * V.T @ r
    mu_closed = np.linalg.solve(Lam, eta)
    cov_closed = np.linalg.inv(Lam)

    draws = []
    key = jax.random.key(0)
    for i in range(600):
        key, k = jax.random.split(key)
        draws.append(np.asarray(
            BMF.sample_factor(k, csr, jnp.asarray(V), tau, prior))[0])
    draws = np.stack(draws)
    np.testing.assert_allclose(draws.mean(0), mu_closed, atol=0.05)
    np.testing.assert_allclose(np.cov(draws.T), cov_closed, atol=0.05)


def test_posterior_algebra_roundtrip():
    rng = np.random.default_rng(2)
    K, N = 4, 6
    A = rng.normal(size=(N, K, K))
    LamA = jnp.asarray(A @ A.transpose(0, 2, 1) + 3 * np.eye(K))
    etaA = jnp.asarray(rng.normal(size=(N, K)))
    B = rng.normal(size=(N, K, K))
    LamB = jnp.asarray(B @ B.transpose(0, 2, 1) + 3 * np.eye(K))
    etaB = jnp.asarray(rng.normal(size=(N, K)))
    ga = POST.RowGaussians(etaA, LamA)
    gb = POST.RowGaussians(etaB, LamB)
    back = POST.divide(POST.product(ga, gb), gb)
    np.testing.assert_allclose(np.asarray(back.eta), np.asarray(ga.eta), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(back.Lambda), np.asarray(ga.Lambda), rtol=1e-5)


@pytest.fixture(scope="module")
def mini_data():
    coo, preset = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    return train, test, preset


def test_full_bmf_beats_mean(mini_data):
    train, test, p = mini_data
    cfg = BMF.BMFConfig(K=p.K, n_samples=40, burnin=15)
    rmse, secs, _ = PP.run_full_bmf(jax.random.key(0), train, test, cfg)
    base = float(np.sqrt(np.mean((test.val - train.val.mean()) ** 2)))
    assert rmse < 0.85 * base, (rmse, base)


def test_pp_rmse_close_to_full_bmf(mini_data):
    """Paper Table 2: BMF+PP ≈ BMF in RMSE."""
    train, test, p = mini_data
    cfg = BMF.BMFConfig(K=p.K, n_samples=40, burnin=15)
    rmse_full, _, _ = PP.run_full_bmf(jax.random.key(0), train, test, cfg)
    part = partition(train, 2, 2)
    res = PP.run_pp(jax.random.key(1), part, cfg, test)
    assert res.n_test > 0
    assert res.rmse < rmse_full * 1.15, (res.rmse, rmse_full)


def test_coo_to_padded_csr_vectorized_fill():
    """The numpy-scatter row fill must match a slot-by-slot loop, including
    truncation of rows beyond max_nnz and rows with zero ratings."""
    rng = np.random.default_rng(6)
    n_rows, n_cols, nnz = 23, 11, 150
    rows = rng.integers(0, n_rows - 2, nnz).astype(np.int32)  # last 2 empty
    coo = COO(row=rows, col=rng.integers(0, n_cols, nnz).astype(np.int32),
              val=rng.normal(size=nnz).astype(np.float32),
              n_rows=n_rows, n_cols=n_cols)
    for max_nnz in (None, 8):
        csr = coo_to_padded_csr(coo, max_nnz=max_nnz)
        M = csr.idx.shape[1]
        order = np.argsort(coo.row, kind="stable")
        r_s, c_s, v_s = coo.row[order], coo.col[order], coo.val[order]
        idx_ref = np.zeros((n_rows, M), np.int32)
        val_ref = np.zeros((n_rows, M), np.float32)
        mask_ref = np.zeros((n_rows, M), np.float32)
        fill = np.zeros(n_rows, np.int64)
        for r, c, v in zip(r_s, c_s, v_s):
            k = fill[r]
            if k < M:
                idx_ref[r, k], val_ref[r, k], mask_ref[r, k] = c, v, 1.0
            fill[r] += 1
        np.testing.assert_array_equal(np.asarray(csr.idx), idx_ref)
        np.testing.assert_array_equal(np.asarray(csr.val), val_ref)
        np.testing.assert_array_equal(np.asarray(csr.mask), mask_ref)


def test_occupancy_permutation_groups_heavy_rows():
    from repro.data.sparse import occupancy_permutation
    rng = np.random.default_rng(8)
    counts = np.array([5, 0, 9, 1, 9, 2])
    rows = np.repeat(np.arange(6), counts).astype(np.int32)
    coo = COO(row=rows, col=np.zeros(len(rows), np.int32),
              val=np.ones(len(rows), np.float32), n_rows=6, n_cols=1)
    perm = occupancy_permutation(coo, axis="row")
    # position of each row = its rank by descending count
    permuted_counts = np.empty(6, np.int64)
    permuted_counts[perm] = counts
    assert (np.diff(permuted_counts) <= 0).all(), permuted_counts


def test_sample_nw_moments_match_analytic():
    """Statistical correctness of the NW sampler + conjugate update (both
    rewritten onto Cholesky factor/solve in PR 1): empirical moments of
    ``sample_nw`` draws from ``nw_posterior(prior, X)`` must converge to
    the analytic Normal-Wishart values under a fixed seed —
      E[Λ] = ν·W,  E[μ] = μ0,  Cov(μ) = E[(βΛ)⁻¹] = W⁻¹ / (β(ν−K−1)).
    """
    K = 3
    prior = POST.NormalWishart(
        mu0=jnp.asarray([1.0, -2.0, 0.5]),
        beta0=jnp.asarray(2.0),
        W0=jnp.asarray([[1.0, 0.3, 0.0],
                        [0.3, 2.0, 0.2],
                        [0.0, 0.2, 0.5]]),
        nu0=jnp.asarray(float(K + 3)))      # ν−K−1 = 2 > 0: Cov(μ) finite
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(0.5, 1.2, (60, K)).astype(np.float32))
    post = POST.nw_posterior(prior, X)
    # conjugate bookkeeping is exact
    np.testing.assert_allclose(float(post.beta0), 2.0 + 60)
    np.testing.assert_allclose(float(post.nu0), K + 3 + 60)

    T = 4000
    keys = jax.random.split(jax.random.key(11), T)
    mus, lams = jax.vmap(lambda k: POST.sample_nw(k, post))(keys)
    mus, lams = np.asarray(mus), np.asarray(lams)

    E_lam = float(post.nu0) * np.asarray(post.W0)
    scale_lam = np.abs(E_lam).max()
    np.testing.assert_allclose(lams.mean(0), E_lam,
                               atol=0.02 * scale_lam)
    np.testing.assert_allclose(mus.mean(0), np.asarray(post.mu0), atol=0.02)
    Winv = np.linalg.inv(np.asarray(post.W0))
    cov_analytic = Winv / (float(post.beta0)
                           * (float(post.nu0) - K - 1))
    np.testing.assert_allclose(np.cov(mus.T), cov_analytic,
                               atol=0.15 * np.abs(cov_analytic).max())


def test_nw_hyperprior_products_are_pinned_to_highest():
    """On a TPU an f32 dot at default precision runs as one bf16 pass, and
    a Wishart draw from a scatter matrix rounded to bf16 moves the chain.
    Every product of the NW hyperprior draw and of its broadcast to rows
    (the chain's ``bmf_prior`` layer) is pinned to HIGHEST at the op."""
    from repro.roofline.jaxpr_cost import iter_eqns
    K, N = 10, 64
    hi = jax.lax.Precision.HIGHEST

    def prior(key, X):
        mu, Lam = BMF.sample_hyper(key, X, POST.default_nw(K))
        return POST.broadcast_prior(mu, Lam, N)

    jx = jax.make_jaxpr(prior)(jax.random.key(0), jnp.zeros((N, K)))
    dots = [e for e in iter_eqns(jx) if e.primitive.name == "dot_general"]
    # the scatter matrix, d d^T, L A, (L A)(L A)^T and Lambda mu
    assert len(dots) >= 5
    loose = [str(e) for e in dots
             if e.params["precision"] not in ((hi, hi), hi)]
    assert loose == []


def test_from_moments_cov_matches_inverse():
    """Cholesky factor/solve summarization == explicit-inverse natural
    params (the path it replaced)."""
    rng = np.random.default_rng(9)
    N, K = 6, 5
    A = rng.normal(size=(N, K, K)).astype(np.float32)
    cov = A @ A.transpose(0, 2, 1) + 2 * np.eye(K, dtype=np.float32)
    mu = rng.normal(size=(N, K)).astype(np.float32)
    g = POST.from_moments_cov(jnp.asarray(mu), jnp.asarray(cov))
    Lam_ref = np.linalg.inv(cov)
    eta_ref = np.einsum("nkl,nl->nk", Lam_ref, mu)
    np.testing.assert_allclose(np.asarray(g.Lambda), Lam_ref, rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(g.eta), eta_ref, rtol=2e-3,
                               atol=2e-3)


def test_block_shapes_per_phase_tighter(mini_data):
    """Per-phase occupancy buckets must never exceed the global bucket and
    must cover every block of their phase."""
    train, test, p = mini_data
    part = partition(train, 2, 2)
    global_s = PP.BlockShapes.of(part, test)
    by_phase = PP.BlockShapes.per_phase(part, test)
    assert set(by_phase) == {b.phase for b in part.all_blocks()}
    for ph, s in by_phase.items():
        assert s.m_rows <= global_s.m_rows
        assert s.n_rows <= global_s.n_rows
        for b in part.all_blocks():
            if b.phase != ph or not b.coo.nnz:
                continue
            assert len(b.row_ids) <= s.n_rows
            m = int(np.bincount(b.coo.row, minlength=len(b.row_ids)).max())
            assert m <= s.m_rows


def test_suggest_grid_squareish():
    I, J = suggest_grid(480_000, 17_000, 64)
    # netflix-like 27:1 aspect -> more row blocks than col blocks
    assert I > J
    assert I * J == 64


def test_gibbs_with_pallas_kernel(mini_data):
    """cfg.use_kernel=True routes the precision accumulation through the
    Pallas kernel (interpret mode on CPU) — RMSE must match the jnp path."""
    train, test, p = mini_data
    cfg_ref = BMF.BMFConfig(K=p.K, n_samples=15, burnin=5, use_kernel=False)
    cfg_ker = BMF.BMFConfig(K=p.K, n_samples=15, burnin=5, use_kernel=True)
    r_ref, _, _ = PP.run_full_bmf(jax.random.key(5), train, test, cfg_ref)
    r_ker, _, _ = PP.run_full_bmf(jax.random.key(5), train, test, cfg_ker)
    # identical keys + near-identical math -> near-identical chains
    assert abs(r_ref - r_ker) < 0.05, (r_ref, r_ker)
