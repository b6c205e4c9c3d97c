"""The row sampler kernel (kernels/bmf_sample) against the XLA sampler it
replaces on TPU, in interpret mode: x = Λ⁻¹η + L⁻ᵀz for a tile of rows,
pad rows and pad K exactly zero, the ``vmap`` form the store's Thompson
draws use, and the route that picks kernel or XLA."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import posterior as POST
from repro.kernels import route
from repro.kernels.bmf_sample import ops
from repro.kernels.bmf_sample.kernel import sample_rows_kernel
from repro.kernels.bmf_sample.ref import sample_rows_noise_ref


def _rel(x, ref):
    return float(np.abs(np.asarray(x) - np.asarray(ref)).max()
                 / np.abs(np.asarray(ref)).max())


def _conditional(seed, N, K):
    """Gibbs-like conditionals: a PD prior plus τ Σ v vᵀ over a few rows."""
    rng = np.random.default_rng(seed)
    V = rng.standard_normal((N, 2 * K, K)).astype(np.float32)
    Lam = 0.5 * np.eye(K, dtype=np.float32) + np.einsum(
        "nmi,nmj->nij", V, V) / K
    eta = rng.standard_normal((N, K)).astype(np.float32)
    z = rng.standard_normal((N, K)).astype(np.float32)
    return jnp.asarray(Lam), jnp.asarray(eta), jnp.asarray(z)


@pytest.mark.parametrize("N", [130, 300])
@pytest.mark.parametrize("K", [10, 100])
def test_kernel_matches_xla_sampler(K, N):
    """N is no multiple of 128, so the last tile runs past the rows, and
    K is no multiple of 8 at K=100, so the factor carries pad columns."""
    Lam, eta, z = _conditional(K + N, N, K)
    x = sample_rows_kernel(Lam, eta, z, interpret=True)
    assert x.shape == (N, K) and x.dtype == jnp.float32
    assert _rel(x, sample_rows_noise_ref(Lam, eta, z)) <= 1e-5


def _ill_conditioned(kind, seed=7, N=130, K=100, cond=1e4):
    """Condition number ``cond``: 'scaled' from row/column scales spread
    over sqrt(cond) (rows of very unequal degree), 'spread' from eigenvalues
    log-spaced over [1, cond] in a random basis."""
    rng = np.random.default_rng(seed)
    if kind == "scaled":
        Lam, _, _ = _conditional(seed, N, K)
        s = np.logspace(0, np.log10(cond) / 2, K).astype(np.float32)
        Lam = np.asarray(Lam) * s[:, None] * s[None, :]
    else:
        Q, _ = np.linalg.qr(rng.standard_normal((N, K, K)))
        Lam = np.einsum("nij,j,nkj->nik", Q, np.logspace(0, np.log10(cond), K),
                        Q)
        Lam = (0.5 * (Lam + np.swapaxes(Lam, 1, 2))).astype(np.float32)
    eta = rng.standard_normal((N, K)).astype(np.float32)
    z = rng.standard_normal((N, K)).astype(np.float32)
    return Lam, eta, z


@pytest.mark.parametrize("kind", ["scaled", "spread"])
def test_kernel_on_ill_conditioned_rows(kind):
    Lam, eta, z = _ill_conditioned(kind)
    assert np.median(np.linalg.cond(Lam.astype(np.float64))) > 5e3
    args = tuple(map(jnp.asarray, (Lam, eta, z)))
    x = sample_rows_kernel(*args, interpret=True)
    xla = sample_rows_noise_ref(*args)
    if kind == "scaled":
        # Cholesky is blind to diagonal scaling: f32 stays f32-accurate
        assert _rel(x, xla) <= 1e-5
        return
    # A spread spectrum costs any f32 factorization about cond·eps of
    # forward accuracy: XLA's own sampler reads ~4e-5 against float64
    # here, so two f32 samplers cannot agree to 1e-5. The kernel is held
    # to float64 instead: the residual of Λx = η + Lz (L the float64
    # factor, unique) relative to ‖Λ‖‖x‖ + ‖η + Lz‖, as small as f32
    # allows (both samplers read ~3e-8–6e-8), and a forward error within
    # twice XLA's.
    L64 = Lam.astype(np.float64) + 1e-6 * np.eye(Lam.shape[-1])
    C = np.linalg.cholesky(L64)
    rhs = eta + np.einsum("nij,nj->ni", C, z)

    def backward(x):
        x = np.asarray(x, np.float64)
        r = np.linalg.norm(np.einsum("nij,nj->ni", L64, x) - rhs, axis=1)
        scale = (np.linalg.norm(L64, 2, axis=(1, 2))
                 * np.linalg.norm(x, axis=1) + np.linalg.norm(rhs, axis=1))
        return float((r / scale).max())

    assert backward(x) <= 1e-6 and backward(xla) <= 1e-6
    x64 = np.linalg.solve(L64, rhs[..., None])[..., 0]
    assert _rel(x, x64) <= 2 * _rel(xla, x64)


def test_vmap_over_noise_matches_per_draw():
    """The store's Thompson slots: one conditional, a batch of draws."""
    Lam, eta, _ = _conditional(3, 130, 10)
    zs = jax.random.normal(jax.random.PRNGKey(0), (3, 130, 10))
    xs = jax.vmap(lambda z: sample_rows_kernel(Lam, eta, z,
                                                   interpret=True))(zs)
    for x, z in zip(xs, zs):
        assert _rel(x, sample_rows_noise_ref(Lam, eta, z)) <= 1e-5


def test_route_off_tpu_and_small_batches(monkeypatch):
    for K in (4, 10, 100, 128):
        assert not route.pallas_route("sample", K)
        assert not route.pallas_route("sample", K, platform="cpu")
        assert route.pallas_route("sample", K, platform="tpu")
    assert not route.pallas_route("sample", 129, platform="tpu")

    # on TPU a call with fewer rows than a lane tile (the serving router's
    # fold-in batches) stays on XLA; a full tile takes the kernel
    monkeypatch.setattr(ops, "pallas_route",
                        lambda k, K: route.pallas_route(k, K, platform="tpu"))
    taken = []
    monkeypatch.setattr(ops, "sample_rows_kernel",
                        lambda *a, **k: taken.append(a[1].shape) or a[1])
    for N in (32, 127, 128):
        Lam, eta, z = _conditional(N, N, 10)
        x = POST.sample_rows_noise(POST.RowGaussians(eta, Lam), z)
        if N < 128:
            assert np.array_equal(x, sample_rows_noise_ref(Lam, eta, z))
    assert taken == [(128, 10)]
