"""Striped-XLA fallback for the fused Gibbs sweep — the off-TPU PRODUCTION
path, not just a test oracle.

It consumes the SAME padded planes as the Pallas kernel and runs the SAME
tile math (``kernel.accum_tile`` / ``kernel.sample_tile``) in the SAME
M-tile order, so parity with interpret-mode Pallas is by construction:
in the single-stripe regime (one eager dispatch per helper on both sides)
the two paths agree bit-for-bit, and the parity suite asserts exact
equality there.  Once the N axis stripes under ``lax.map``, XLA compiles
the stripe body as one fused computation and CPU fast-math contraction
(FMA / add reassociation across fusion boundaries) can shift results by
a few ulps relative to the op-by-op interpreter — same math, tighter
rounding, asserted at 1e-5.  (The dead M-tiles the kernel skips, and the
slots past each row's extent its pump never copies, are gathered and
processed here — their masked contribution is exactly zero, which the
parity suite pins down.)

Zero-materialization shape discipline matches bmf_precision's fallback:
the N axis is striped under ``lax.map`` (one program regardless of N) and
each stripe gathers one (ns, tm, K) tile at a time, so peak live memory is
O(stripe) — no (N, M, K) tensor and, unlike the legacy sufficient-stats
path, no (N, K, K) precision round-trip either: Λ exists only as the
per-stripe accumulator inside the map body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bmf_sweep.kernel import accum_tile, live_width, sample_tile


def sweep_ref_padded(idx, val, mask, prior_eta, prior_lam, z, other,
                     tau: float, *, tm: int, jitter: float = 1e-6,
                     dtype=jnp.float32, n_stripe: int, k: int | None = None):
    """Same contract as ``kernel.fused_sweep_padded`` (minus the extent
    plane — every slot of every tile is processed; dead ones add exact
    zeros).  N must be a multiple of ``n_stripe``; M a multiple of
    ``tm``."""
    N, M = idx.shape
    K = other.shape[-1]
    k = K if k is None else k
    ks = live_width(k, K)
    assert N % n_stripe == 0 and M % tm == 0, (N, M, n_stripe, tm)

    def stripe(args):
        ix, vl, mk, pe, pL, zz = args
        lam = jnp.zeros((n_stripe, K, K), jnp.float32)
        eta = jnp.zeros((n_stripe, K), jnp.float32)
        # static unrolled M-tile loop, SAME order as the kernel grid's
        # innermost axis — the rounding-order half of the parity contract
        for lo in range(0, M, tm):
            v = other[ix[:, lo:lo + tm]]                # (ns, tm, K) gather
            lam, eta = accum_tile(lam, eta, v, mk[:, lo:lo + tm],
                                  vl[:, lo:lo + tm], tau, dtype)
        # (no optimization_barrier between the phases even though the
        # kernel has a hard VMEM-scratch boundary there: the stacked
        # executors vmap this whole chain and the barrier primitive has
        # no batching rule — the ulp-level fusion drift it would prevent
        # is already inside the parity contract above)
        # the kernel's epilogue: the live corner, zeros past it
        u = sample_tile(lam[:, :ks, :ks], eta[:, :ks], pL[:, :ks, :ks],
                        pe[:, :ks], zz[:, :ks], jitter, k)
        return jnp.pad(u, ((0, 0), (0, K - ks)))

    if N == n_stripe:
        return stripe((idx, val, mask, prior_eta, prior_lam, z))
    nsp = N // n_stripe
    U = jax.lax.map(stripe, (idx.reshape(nsp, n_stripe, M),
                             val.reshape(nsp, n_stripe, M),
                             mask.reshape(nsp, n_stripe, M),
                             prior_eta.reshape(nsp, n_stripe, K),
                             prior_lam.reshape(nsp, n_stripe, K, K),
                             z.reshape(nsp, n_stripe, K)))
    return U.reshape(N, K)
