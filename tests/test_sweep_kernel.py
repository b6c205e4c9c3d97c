"""One-kernel Gibbs sweep (kernels/bmf_sweep) conformance:

  * interpret-mode Pallas vs the striped-XLA fallback: both paths run the
    same tile helpers over the same padded operands in the same M-tile
    order, so in the single-stripe regime (eager dispatch on both sides)
    parity is BITWISE and asserted with assert_array_equal. Striped under
    ``lax.map`` the fallback compiles as one fused body and XLA CPU
    fast-math contraction shifts results a few ulps — same math, asserted
    at 1e-5 (see ref.py on the parity contract);
  * the in-register Cholesky/solve sampler is checked two ways: per-draw
    against ``posterior.sample_rows_noise`` (same z => same sample up to
    solver roundoff) and statistically (4000 draws reproduce the analytic
    Gibbs-conditional mean/covariance);
  * ``gibbs._summarize``'s relative ridge: the old ABSOLUTE 1e-4 ridge
    vanishes in f32 against rank-deficient moment estimates at 1e4 row
    scale (1e8-scale variances absorb the nudge), while the scaled ridge
    stays finite — and O(1)-scale rows remain bit-for-bit unchanged;
  * the dtype-promotion lint pass proves bf16 never reaches the
    factor/solve path of the traced fused step (and still fires on a
    planted bf16 sqrt).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS
from repro.core import posterior as POST
from repro.kernels.bmf_sweep import ops as SWEEP


def _case(rng, N, M, D, K, empty_rows=(), scale=1.0):
    """Random padded-CSR factor-step inputs with ragged left-contiguous
    occupancy and per-row PD priors."""
    idx = jnp.asarray(rng.integers(0, D, (N, M)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(N, M)) * scale, jnp.float32)
    nnz = rng.integers(0, M + 1, N)
    nnz[list(empty_rows)] = 0
    mask = jnp.asarray(np.arange(M)[None, :] < nnz[:, None], jnp.float32)
    other = jnp.asarray(rng.normal(size=(D, K)), jnp.float32)
    pe = jnp.asarray(rng.normal(size=(N, K)) * 0.3, jnp.float32)
    A = rng.normal(size=(N, K, K)) * 0.2
    pL = jnp.asarray(np.einsum("nij,nkj->nik", A, A)
                     + 1.5 * np.eye(K)[None], jnp.float32)
    z = jnp.asarray(rng.normal(size=(N, K)), jnp.float32)
    return idx, val, mask, pe, pL, z, other


# ---------------------------------------------------------------------------
# bitwise parity: interpret-mode Pallas vs striped-XLA fallback
# ---------------------------------------------------------------------------


# dims shaped like the engine's row buckets: ragged small and a
# TN-unaligned N, one M-tile each. n_stripe covers all rows => one eager
# dispatch per path => bitwise. The lanes128 case lays K=10 out at the
# TPU's 128-lane width (identity pad diagonal, as ops.py builds it), so
# both paths bound the epilogue by k=10 inside a 128-wide tile.
@pytest.mark.parametrize("N,M,D,K,lanes", [
    pytest.param(5, 17, 23, 8, None, id="5-17-23-8"),
    pytest.param(19, 40, 31, 12, None, id="19-40-31-12"),
    pytest.param(19, 40, 31, 10, 128, id="19-40-31-10-lanes128")])
@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_fused_vs_ref_bitwise(N, M, D, K, lanes, dtype, monkeypatch):
    if lanes is not None:
        monkeypatch.setattr(SWEEP, "HOST_LANES", lanes)
    rng = np.random.default_rng(3)
    idx, val, mask, pe, pL, z, other = _case(rng, N, M, D, K,
                                             empty_rows=(0, N - 1))
    kw = dict(dtype=dtype, tau=1.7, n_stripe=N)
    U_pal = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="pallas", interpret=True, **kw)
    U_ref = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="ref", **kw)
    assert U_pal.shape == (N, K)
    assert bool(jnp.all(jnp.isfinite(U_pal)))
    np.testing.assert_array_equal(np.asarray(U_pal), np.asarray(U_ref))


def _live_slot_case(rng, tm, D, K):
    """``_case``'s operands on a plane for the row pump's live-slot walk:
    4 row tiles over 2·tm + 3 slots — a heavy tile (degrees 0, 1, tm−1,
    tm, tm+1, 2·tm+3), a light one, an empty one, and one with holes (row
    24 extent 9 for degree 7; row 25 three holes across the first M-tile
    seam). Factor rows are multiples of 1/4 and ratings integers, so Λ
    and η are exact in any summation order and the multi-M-tile
    comparison is bitwise. Factor row D−1 is NaN and only live slot
    (6, 2·tm+1) gathers it; its NaN, left in the gather scratch, must
    reach no later tile. Returns the operands and the one NaN row."""
    deg = np.array([0, 1, tm - 1, tm, tm + 1, 2 * tm + 3, 2 * tm + 3, 5,
                    1, 0, 2, 3, 0, 1, 1, 2,
                    0, 0, 0, 0, 0, 0, 0, 0,
                    9, tm + 2, 3, 0, 0, 1, 0, 0])
    N, M = len(deg), 2 * tm + 3
    idx, _, _, pe, pL, z, _ = _case(rng, N, M, D - 1, K)
    idx = np.asarray(idx).copy()
    idx[6, 2 * tm + 1] = D - 1
    mask = (np.arange(M)[None, :] < deg[:, None]).astype(np.float32)
    mask[24, [2, 5]] = 0.0
    mask[25, tm - 2:tm + 1] = 0.0
    val = rng.integers(1, 6, (N, M)).astype(np.float32)
    other = rng.integers(-4, 5, (D, K)) / 4.0
    other[D - 1] = np.nan
    return (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(mask), pe, pL,
            z, jnp.asarray(other, jnp.float32)), 6


@pytest.mark.parametrize("lanes", [None, 128], ids=["host", "lanes128"])
@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_fused_vs_ref_live_slots_bitwise(lanes, dtype, monkeypatch):
    """The pump copies only the slots below each row's extent, and the
    slots it skips read as zero: interpret-mode Pallas vs the fallback
    (which gathers every slot), bitwise across three M-tiles, with ragged
    degrees, an empty row tile, holes, and a heavy tile's scratch left
    for the light tiles after it."""
    if lanes is not None:
        monkeypatch.setattr(SWEEP, "HOST_LANES", lanes)
    rng = np.random.default_rng(13)
    (idx, val, mask, pe, pL, z, other), nan_row = _live_slot_case(
        rng, 128, 37, 10)
    N = idx.shape[0]
    kw = dict(dtype=dtype, tau=2.0, tm=128, n_stripe=N)
    U_pal = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="pallas", interpret=True, **kw)
    U_ref = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="ref", **kw)
    np.testing.assert_array_equal(np.asarray(U_pal), np.asarray(U_ref))
    finite = np.arange(N) != nan_row
    assert np.isfinite(np.asarray(U_pal)[finite]).all()
    assert np.isnan(np.asarray(U_pal)[nan_row]).all()


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_fused_vs_ref_multi_m_tile(dtype):
    """M=300 pads to two tm=256 tiles: the kernel's scratch-accumulate
    revisits the row block across grid steps while the fallback loops in
    one trace — an extra fused-rounding context, so this leg is deep-ulp
    allclose rather than bitwise."""
    rng = np.random.default_rng(3)
    idx, val, mask, pe, pL, z, other = _case(rng, 16, 300, 48, 8,
                                             empty_rows=(0, 15))
    kw = dict(dtype=dtype, tau=1.7, n_stripe=16)
    U_pal = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="pallas", interpret=True, **kw)
    U_ref = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="ref", **kw)
    np.testing.assert_allclose(np.asarray(U_pal), np.asarray(U_ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_fused_vs_ref_forced_striping(dtype):
    """Tiny SMEM/tile budgets force BOTH paths to stripe the N axis into
    several dispatches; parity must hold across the stripe seams (and the
    dead M-tiles the kernel's occupancy counts skip must contribute exact
    zeros in the fallback, which processes them). The striped fallback
    body is XLA-fused (fast-math contraction), so this leg is ulp-level,
    not bitwise — 1e-5 against draws of O(1) magnitude."""
    rng = np.random.default_rng(11)
    idx, val, mask, pe, pL, z, other = _case(rng, 40, 50, 29, 8,
                                             empty_rows=(7, 21))
    kw = dict(dtype=dtype, tau=2.0, tm=128,
              smem_idx_budget=4096, tile_elems=4096)
    U_pal = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="pallas", interpret=True, **kw)
    U_ref = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="ref", **kw)
    np.testing.assert_allclose(np.asarray(U_pal), np.asarray(U_ref),
                               rtol=1e-5, atol=1e-5)
    # the striped and single-stripe fallbacks agree bitwise with each
    # other per row regardless of stripe seams (row-local math)
    U_one = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other,
                              force="ref", dtype=dtype, tau=2.0, tm=128,
                              n_stripe=40)
    np.testing.assert_allclose(np.asarray(U_one), np.asarray(U_ref),
                               rtol=1e-5, atol=1e-5)


def _lane_padded_tile(rng, B, K, Kp):
    """One row tile's epilogue operands at rank K — the accumulated Λ and
    η, a PD prior, the noise — and the same operands laid out at Kp lanes
    as ``ops.fused_sweep`` lays them out: zeros past K, and an identity on
    the prior Λ's pad diagonal."""
    V = rng.normal(size=(B, 30, K))
    lam = np.einsum("bmi,bmj->bij", V, V)
    A = rng.normal(size=(B, K, K)) * 0.2
    pL = np.einsum("nij,nkj->nik", A, A) + 1.5 * np.eye(K)[None]
    eta, pe, z = (rng.normal(size=(B, K)) for _ in range(3))
    small = (lam, eta, pL, pe, z)
    pad = lambda x: np.pad(x, [(0, 0)] + [(0, Kp - K)] * (x.ndim - 1))
    padded = [pad(x) for x in small]
    padded[2] = padded[2] + np.diag((np.arange(Kp) >= K).astype(float))
    f32 = lambda xs: [jnp.asarray(x, jnp.float32) for x in xs]
    return f32(small), f32(padded)


@pytest.mark.parametrize("K,Kp", [(10, 128), (12, 16), (3, 8)])
def test_sample_tile_bounded_by_true_k(K, Kp):
    """The epilogue's Cholesky and solves bounded by the true K inside a
    lane-padded tile: the first K lanes are bitwise what the loops over
    all Kp lanes give on the same operands (the Cholesky is left-looking
    and the pad block is zero off the diagonal), and the pad lanes are
    exactly zero. The live corner (``live_width``) the kernel reads, and
    the unpadded K-wide tile, agree to rounding: XLA's lane reductions sum
    a narrower row in another order."""
    from repro.kernels.bmf_sweep.kernel import live_width, sample_tile
    small, padded = _lane_padded_tile(np.random.default_rng(K), 8, K, Kp)
    u_all = np.asarray(sample_tile(*padded, 1e-6))
    u_k = np.asarray(sample_tile(*padded, 1e-6, K))
    np.testing.assert_array_equal(u_k[:, :K], u_all[:, :K])
    assert np.all(u_k[:, K:] == 0) and np.all(u_all[:, K:] == 0)

    ks = live_width(K, Kp)
    corner = [x[:, :ks, :ks] if x.ndim == 3 else x[:, :ks] for x in padded]
    u_corner = np.asarray(sample_tile(*corner, 1e-6, K))
    assert u_corner.shape == (8, ks) and np.all(u_corner[:, K:] == 0)
    u_small = np.asarray(sample_tile(*small, 1e-6))
    for u in (u_k, u_corner):
        np.testing.assert_allclose(u[:, :K], u_small, rtol=1e-6, atol=1e-6)


def test_empty_rows_reduce_to_prior_sample():
    """A row with no observations must sample from its PRIOR conditional —
    the fused path's answer matches sample_rows_noise on the bare prior."""
    rng = np.random.default_rng(5)
    idx, val, mask, pe, pL, z, other = _case(rng, 6, 20, 13, 8,
                                             empty_rows=(2,))
    U = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other, 1.3,
                          force="ref")
    want = POST.sample_rows_noise(POST.RowGaussians(eta=pe, Lambda=pL), z)
    np.testing.assert_allclose(np.asarray(U[2]), np.asarray(want[2]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# in-kernel sampler: per-draw + statistical agreement with posterior.py
# ---------------------------------------------------------------------------


def _conditional(idx, val, mask, pe, pL, other, tau):
    """Analytic Gibbs conditional per row: Λ = Λ0 + τ Σ v vᵀ, η = η0 + τ Σ r v."""
    V = np.asarray(other)[np.asarray(idx)]
    m = np.asarray(mask)
    Lam = np.asarray(pL) + tau * np.einsum("nm,nmk,nml->nkl", m, V, V)
    eta = np.asarray(pe) + tau * np.einsum("nm,nm,nmk->nk",
                                           m, np.asarray(val), V)
    return eta, Lam


def test_in_kernel_sampler_matches_sample_rows_noise():
    """Same conditional, same z: the masked-lane Cholesky/solve chain and
    LAPACK's agree to solver roundoff on every draw."""
    rng = np.random.default_rng(23)
    idx, val, mask, pe, pL, z, other = _case(rng, 12, 30, 17, 8)
    tau = 1.9
    U = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other, tau, force="ref")
    eta, Lam = _conditional(idx, val, mask, pe, pL, other, tau)
    want = POST.sample_rows_noise(
        POST.RowGaussians(eta=jnp.asarray(eta), Lambda=jnp.asarray(Lam)), z)
    np.testing.assert_allclose(np.asarray(U), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_in_kernel_sampler_moments():
    """4000 fused draws reproduce the analytic conditional moments: mean
    within standard-error bars, covariance within a Frobenius-relative
    tolerance of Λ⁻¹."""
    rng = np.random.default_rng(31)
    N, K, T = 6, 6, 4000
    idx, val, mask, pe, pL, _, other = _case(rng, N, 24, 15, K)
    tau = 2.2
    zs = jax.random.normal(jax.random.key(9), (T, N, K))

    draw = jax.jit(lambda zz: SWEEP.fused_sweep(
        zz, idx, val, mask, pe, pL, other, tau, force="ref"))
    samples = np.asarray(jax.lax.map(draw, zs, batch_size=500))   # (T, N, K)

    eta, Lam = _conditional(idx, val, mask, pe, pL, other, tau)
    Sig = np.linalg.inv(Lam + 1e-6 * np.eye(K))
    mu = np.einsum("nkl,nl->nk", Sig, eta)

    se = np.sqrt(np.diagonal(Sig, axis1=-2, axis2=-1) / T)
    assert np.all(np.abs(samples.mean(0) - mu) < 5 * se)
    c = samples - samples.mean(0)
    cov = np.einsum("tnk,tnl->nkl", c, c) / (T - 1)
    rel = (np.linalg.norm(cov - Sig, axis=(1, 2))
           / np.linalg.norm(Sig, axis=(1, 2)))
    assert np.all(rel < 0.15), rel


def test_sample_factor_fused_preserves_noise_stream():
    """Flipping the fused path on must not perturb the chain's random
    stream: same key => the legacy sample_factor and the fused step draw
    the SAME z and agree to solver roundoff."""
    rng = np.random.default_rng(41)
    idx, val, mask, pe, pL, _, other = _case(rng, 10, 25, 19, 8)
    from repro.data.sparse import PaddedCSR
    csr = PaddedCSR(idx=idx, val=val, mask=mask, n_cols=19)
    prior = POST.RowGaussians(eta=pe, Lambda=pL)
    key = jax.random.key(77)
    legacy = BMF.sample_factor(key, csr, other, 1.4, prior)
    fused = SWEEP.sample_factor_fused(key, csr, other, 1.4, prior)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(legacy),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# _summarize relative ridge
# ---------------------------------------------------------------------------


def _moments(samples):
    T = samples.shape[0]
    sum_ = samples.sum(0)
    outer = jnp.einsum("tnk,tnl->nkl", samples, samples)
    return sum_, outer, jnp.asarray(float(T))


def test_summarize_relative_ridge_ill_conditioned():
    """Rank-deficient draws (T-1 < K) at 1e4 row scale: variances sit at
    ~1e8, where the old absolute 1e-4 ridge is below f32 resolution
    (1e8 + 1e-4 == 1e8) — the Cholesky sees a singular matrix and the
    old path goes non-finite. The scaled ridge must stay finite and PD."""
    rng = np.random.default_rng(53)
    T, N, K = 4, 5, 8
    samples = jnp.asarray(rng.normal(size=(T, N, K)) * 1e4, jnp.float32)
    sum_, outer, cnt = _moments(samples)

    mean = sum_ / cnt
    cov = outer / cnt - jnp.einsum("nk,nl->nkl", mean, mean)
    old = POST.from_moments_cov(mean, cov, ridge=1e-4)       # pre-fix path
    assert not bool(jnp.all(jnp.isfinite(old.Lambda)))

    g = GIBBS._summarize(sum_, outer, cnt)
    assert bool(jnp.all(jnp.isfinite(g.Lambda)))
    assert bool(jnp.all(jnp.isfinite(g.eta)))
    ev = np.linalg.eigvalsh(np.asarray(g.Lambda))
    assert np.all(ev > 0), ev.min()


def test_summarize_relative_ridge_small_scale_bitwise_compat():
    """O(1)-scale rows (every existing chain): the floor pins the scaled
    ridge at exactly the old absolute 1e-4, so the summarization is
    bit-for-bit what from_moments_cov(ridge=1e-4) produced."""
    rng = np.random.default_rng(59)
    samples = jnp.asarray(rng.normal(size=(9, 7, 6)) * 0.3, jnp.float32)
    sum_, outer, cnt = _moments(samples)
    mean = sum_ / cnt
    cov = outer / cnt - jnp.einsum("nk,nl->nkl", mean, mean)
    assert float(jnp.abs(jnp.diagonal(cov, axis1=-2, axis2=-1)).max()) < 1.0

    old = POST.from_moments_cov(mean, cov, ridge=1e-4)
    new = GIBBS._summarize(sum_, outer, cnt)
    np.testing.assert_array_equal(np.asarray(new.eta), np.asarray(old.eta))
    np.testing.assert_array_equal(np.asarray(new.Lambda),
                                  np.asarray(old.Lambda))


# ---------------------------------------------------------------------------
# dtype-promotion pass over the fused lowering
# ---------------------------------------------------------------------------


def test_dtype_pass_proves_bf16_never_reaches_solver():
    """The traced bf16 fused step must carry NO low-precision operand into
    cholesky/triangular_solve/sqrt — the lint-side proof that mixed
    precision stays on the gather/accumulate side."""
    from repro.analysis.registry import JaxprArtifact, get_pass
    tc = SWEEP.trace_sweep(8, 16, 24, 48, dtype="bf16")
    art = JaxprArtifact(label="sweep[bf16]", jaxpr=tc.traced.jaxpr)
    assert get_pass("dtype-promotion").run(art) == []
    # the jaxpr really is the mixed-precision lowering, not an all-f32 one
    from repro.roofline import jaxpr_cost as JCOST
    assert any(str(getattr(a, "dtype", "")) == "bfloat16"
               for a in JCOST.iter_avals(tc.traced.jaxpr))


def test_dtype_pass_catches_bf16_sqrt():
    """Negative control: a planted bf16 sqrt (a half-precision in-register
    Cholesky diagonal) trips the pass."""
    from repro.analysis.registry import JaxprArtifact, get_pass
    bad = jax.make_jaxpr(
        lambda x: jnp.sqrt(x.astype(jnp.bfloat16)))(jnp.ones((4, 4)))
    art = JaxprArtifact(label="planted", jaxpr=bad)
    vs = get_pass("dtype-promotion").run(art)
    assert any("sqrt" in v.message for v in vs), vs


# ---------------------------------------------------------------------------
# route predicate and the TPU lane-width limit
# ---------------------------------------------------------------------------


def test_pallas_route_predicate():
    """One predicate of (platform, K) decides both kernels' routes: XLA off
    TPU; on TPU the precision kernel always, the sweep up to SWEEP_K_MAX."""
    from repro.kernels.route import SWEEP_K_MAX, pallas_route
    for kernel in ("precision", "sweep"):
        assert not pallas_route(kernel, 10, platform="cpu")
        assert pallas_route(kernel, 10, platform="tpu")
    assert pallas_route("precision", 100, platform="tpu")
    assert pallas_route("sweep", SWEEP_K_MAX, platform="tpu")
    assert not pallas_route("sweep", SWEEP_K_MAX + 1, platform="tpu")
    assert pallas_route("sweep", 10) == (jax.default_backend() == "tpu")
    with pytest.raises(ValueError, match="kernel must be one of"):
        pallas_route("gather", 10, platform="tpu")


def test_compiled_kernels_reject_k_beyond_one_lane_tile():
    """K > 128 pads past one lane tile, whose one-row DMA Mosaic cannot
    lower: the compiled (non-interpret) route refuses it up front, naming
    the limit, while interpret mode still runs it."""
    from repro.kernels.bmf_precision import ops as PREC
    rng = np.random.default_rng(7)
    idx, val, mask, pe, pL, z, other = _case(rng, 8, 16, 11, 130)
    with pytest.raises(ValueError, match="K <= 128"):
        SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other, 2.0,
                          force="pallas", interpret=False)
    with pytest.raises(ValueError, match="K <= 128"):
        PREC.precision_accum_fused(idx, val, mask, other, 2.0,
                                   interpret=False)
    U = SWEEP.fused_sweep(z, idx, val, mask, pe, pL, other, 2.0,
                          force="pallas", interpret=True, n_stripe=8)
    assert U.shape == (8, 130) and bool(jnp.all(jnp.isfinite(U)))
