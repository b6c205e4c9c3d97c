"""Dispatch layer for the one-kernel Gibbs sweep.

``fused_sweep`` is the factor-step entry point used by
``core.gibbs`` when ``BMFConfig.sweep_fused`` is set: it pads the CSR
planes / priors / noise to tile shapes and routes to

  - the Pallas kernel (kernel.py) where ``route.pallas_route('sweep', K)``
    holds: on TPU for K ≤ ``SWEEP_K_MAX`` — the in-register Cholesky is a
    column loop, so beyond small K its O(K²) masked-lane overhead stops
    paying for the saved HBM round-trips;
  - the striped-XLA fallback (ref.py) everywhere else — same tile math,
    same padded operands, same M-tile order (bitwise-identical in the
    single-stripe regime; a few ulps once XLA fuses the striped body —
    see ref.py on the parity contract).

Lane padding follows the backend: K pads to the 128-lane MXU width on
TPU, to 8 sublanes on hosts (interpret mode has no lane constraint, and
padding the CPU fallback 16× wide would be pure waste).  Both paths get
the unpadded K as their static ``k``: the Cholesky and solves stop at
lane K and read only the corner of the padded tile it needs, so pad-lane
samples are exactly zero and trimming is lossless.  Pad lanes carry an
identity diagonal in the prior Λ, which keeps the padded Λ positive
definite for a caller that factors all of it (``k`` left at its
default); the bounded loops never pivot on it.

``sample_factor_fused`` is the drop-in for ``bmf.sample_factor``: it
draws the SAME z = normal(key, (N, K)) that ``posterior.sample_rows``
would, so switching ``sweep_fused`` on or off never perturbs the chain's
random stream.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp

from repro.data.sparse import row_extent
from repro.kernels.bmf_precision.ops import SMEM_IDX_BUDGET, _on_tpu, _pad_to
from repro.kernels.bmf_sweep.kernel import (
    LANES, TM, TN, fused_sweep_padded)
from repro.kernels.bmf_sweep.ref import sweep_ref_padded
from repro.kernels.route import check_lane_width, pallas_route

SWEEP_DTYPES = ("fp32", "bf16")

# host-side lane padding granularity (f32 sublane count); TPU uses LANES
HOST_LANES = 8

# fallback gather-tile budget (elements): the N axis is striped so each
# stripe's (ns, tm, K) gather stays near ~1 MB f32 — big enough to keep
# the batched matmuls fat, small enough that XLA's per-dispatch peak is
# a stripe, not the plane
SWEEP_TILE_ELEMS = 1 << 18


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def fused_sweep(z, idx, val, mask, prior_eta, prior_lam, other, tau: float, *,
                dtype: str = "fp32", jitter: float = 1e-6, tm=None,
                interpret=None, force=None, n_stripe=None,
                tile_elems: int = SWEEP_TILE_ELEMS,
                smem_idx_budget: int = SMEM_IDX_BUDGET):
    """One-pass factor step: returns U (N, K) sampled from the Gibbs
    conditional, given the padded CSR planes (N, M), per-row prior natural
    params (N, K)/(N, K, K), the caller's noise draw z (N, K), and the
    other factor (D, K).

    dtype: 'fp32', or 'bf16' for the mixed-precision mode (bf16 Λ
    accumulate with f32 MXU accumulation; the gather, η, priors, Cholesky,
    and solves stay f32).  force: 'pallas' / 'ref' pins the path, and n_stripe pins
    the N-stripe width, for the parity tests (a stripe covering all of N
    keeps both paths in the single-dispatch regime where agreement is
    bitwise, not just ulp-level — see ref.py).

    Runs under the ``bmf_sweep`` named scope, on every caller's path (the
    chain's seam and the data-sharded sweep of ``core.distributed``); the
    padding of the operands to tile shapes under ``bmf_sweep_layout``
    inside it."""
    with jax.named_scope("bmf_sweep"):
        if dtype not in SWEEP_DTYPES:
            raise ValueError(
                f"sweep dtype must be one of {SWEEP_DTYPES}, got {dtype!r}")
        N, M = idx.shape
        K = other.shape[-1]
        use_pallas = force == "pallas" or (
            force is None and pallas_route("sweep", K))
        if interpret is None:
            interpret = not _on_tpu()
        if use_pallas and not interpret:
            check_lane_width(K)
        if force is None and not use_pallas and _on_tpu():
            warnings.warn(f"fused_sweep: K={K} takes the striped-XLA path on "
                          f"TPU (route.pallas_route('sweep', K) is False)",
                          stacklevel=2)
        tm_eff = tm or min(TM, _ceil_to(max(M, 1), LANES))
        lanes = LANES if _on_tpu() else HOST_LANES
        Kp = _ceil_to(K, lanes)
        Mp = _ceil_to(M, tm_eff)
        if n_stripe is not None:
            ns = _ceil_to(n_stripe, TN)
        elif use_pallas:
            # the scalar-prefetched index plane lives in SMEM: stripe N
            # under it
            ns = max(TN, (smem_idx_budget // (Mp * 4)) // TN * TN)
        else:
            raw = min(max(N * M // tm_eff, 1),
                      max(tile_elems // (tm_eff * Kp), 1))
            ns = max(TN, raw // TN * TN)
        Np = _ceil_to(N, ns)

        # the operands laid out in tiles, scoped apart from the kernel's own
        # loop; pad slots gather row 0 but are masked out
        with jax.named_scope("bmf_sweep_layout"):
            idxp = _pad_to(_pad_to(idx, Mp, 1), Np, 0)
            valp = _pad_to(_pad_to(val, Mp, 1), Np, 0)
            maskp = _pad_to(_pad_to(mask, Mp, 1), Np, 0)
            pe = _pad_to(_pad_to(prior_eta.astype(jnp.float32), Kp, 1), Np,
                         0)
            pL = prior_lam.astype(jnp.float32)
            pL = _pad_to(_pad_to(_pad_to(pL, Kp, 1), Kp, 2), Np, 0)
            if Kp > K:
                # identity on the pad diagonal keeps the padded Λ PD; the
                # epilogue below stops at lane K and never pivots on it
                pad_diag = (jnp.arange(Kp) >= K).astype(jnp.float32)
                pL = pL + jnp.diag(pad_diag)[None]
            zp = _pad_to(_pad_to(z.astype(jnp.float32), Kp, 1), Np, 0)
            otherp = _pad_to(other.astype(jnp.float32), Kp, 1)
            if use_pallas:
                # the pump's per-row live extents, scalar-prefetched
                extp = row_extent(maskp)
        mm_dtype = jnp.bfloat16 if dtype == "bf16" else jnp.float32

        if not use_pallas:
            U = sweep_ref_padded(idxp, valp, maskp, pe, pL, zp, otherp, tau,
                                 tm=tm_eff, jitter=jitter, dtype=mm_dtype,
                                 n_stripe=ns, k=K)
            return U[:N, :K]

        def stripe(args):
            ix, ex, vl, mk, pe1, pL1, zz = args
            return fused_sweep_padded(
                ix, ex, vl, mk, pe1, pL1, zz, otherp, tau, tm=tm_eff,
                jitter=jitter, dtype=mm_dtype, k=K, interpret=interpret)

        if Np == ns:
            U = stripe((idxp, extp, valp, maskp, pe, pL, zp))
        else:
            nsp = Np // ns
            U = jax.lax.map(stripe, (idxp.reshape(nsp, ns, Mp),
                                     extp.reshape(nsp, ns),
                                     valp.reshape(nsp, ns, Mp),
                                     maskp.reshape(nsp, ns, Mp),
                                     pe.reshape(nsp, ns, Kp),
                                     pL.reshape(nsp, ns, Kp, Kp),
                                     zp.reshape(nsp, ns, Kp)))
            U = U.reshape(Np, Kp)
        return U[:N, :K]


def sample_factor_fused(key, csr, other, tau: float, prior, *,
                        dtype: str = "fp32", jitter: float = 1e-6):
    """Drop-in for ``bmf.sample_factor``: same signature shape, same noise
    stream (z is exactly ``posterior.sample_rows``'s draw), one fused pass
    instead of sufficient-stats → Cholesky → sample round-trips."""
    N = csr.idx.shape[0]
    K = other.shape[-1]
    z = jax.random.normal(key, (N, K), dtype=prior.eta.dtype)
    return fused_sweep(z, csr.idx, csr.val, csr.mask,
                       prior.eta, prior.Lambda, other, tau,
                       dtype=dtype, jitter=jitter)


@partial(jax.jit, static_argnames=("tau", "dtype"))
def _fused_sweep_jit(z, idx, val, mask, prior_eta, prior_lam, other,
                     tau: float, dtype: str):
    return fused_sweep(z, idx, val, mask, prior_eta, prior_lam, other, tau,
                       dtype=dtype)


def trace_sweep(K: int, n_rows: int, m_rows: int, n_other: int, *,
                dtype: str = "fp32"):
    """Lowering hook for the static analyzer (launch.bmf_lint), shaped like
    ``gibbs.trace_chain``: trace the jitted fused factor step at abstract
    shapes so the materialization-budget and dtype-promotion passes run
    over the EXACT op-level jaxpr (both precision modes)."""
    from repro.core.gibbs import TracedChain, _flat_param_labels
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    named = [("z", S((n_rows, K), f32)),
             ("csr_idx", S((n_rows, m_rows), i32)),
             ("csr_val", S((n_rows, m_rows), f32)),
             ("csr_mask", S((n_rows, m_rows), f32)),
             ("prior_eta", S((n_rows, K), f32)),
             ("prior_Lambda", S((n_rows, K, K), f32)),
             ("other", S((n_other, K), f32))]
    traced = _fused_sweep_jit.trace(*(t for _, t in named),
                                    tau=2.0, dtype=dtype)
    return TracedChain(traced=traced, param_labels=_flat_param_labels(named),
                       donated_labels=(), must_alias=())
