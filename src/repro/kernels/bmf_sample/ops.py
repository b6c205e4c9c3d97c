"""Dispatch for the row sampler x = Λ⁻¹η + L⁻ᵀz.

``sample_rows_noise`` is what ``posterior.sample_rows_noise`` calls.  It
runs the Pallas kernel (kernel.py) where ``route.pallas_route('sample',
K)`` holds and the call has at least one lane tile (128) of rows, and the
XLA sampler (ref.py) otherwise: off TPU, for K > 128, and for the serving
router's batches of 32 rows or fewer.  The kernel takes Λ, η and z as
they are, so the kernel path adds no pass over Λ in XLA.
"""
from __future__ import annotations

from repro.kernels.bmf_sample.kernel import LANES, sample_rows_kernel
from repro.kernels.bmf_sample.ref import sample_rows_noise_ref
from repro.kernels.route import pallas_route


def sample_rows_noise(Lambda, eta, z, jitter: float = 1e-6):
    """x_n ~ N(Λ_n⁻¹η_n, Λ_n⁻¹) given the standard-normal draw z: Lambda
    (N, K, K), eta and z (N, K).  Kernel or XLA, chosen from the platform,
    K and N."""
    N, K = eta.shape
    if N >= LANES and pallas_route("sample", K):
        return sample_rows_kernel(Lambda, eta, z, jitter)
    return sample_rows_noise_ref(Lambda, eta, z, jitter)
