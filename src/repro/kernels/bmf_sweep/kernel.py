"""Pallas TPU kernel: ONE-pass fused Gibbs sweep for a BMF factor step.

kernels/bmf_precision fused the gather + Λ/η accumulation but still returned
the (N, K, K)/(N, K) sufficient stats to HBM, where XLA ran the Cholesky
solve and the noise draw as separate kernels — three HBM round-trips per
factor step.  This kernel chains the whole per-row conditional

    gather v_d rows → Λ/η accumulate → small-K Cholesky → two triangular
    solves + noise add   (u = Λ⁻¹η + L⁻ᵀ z, the ``sample_rows_noise`` split)

inside one pallas_call: the (TN, K, K) precision block lives ONLY in VMEM
scratch, and the single HBM-resident output is the sampled factor block
(TN, K).  The grid, the scalar-prefetched CSR index plane and per-row
live extents, the nnz-aware tile skip and the DMA row pump are
bmf_precision's — the pump is imported (``gather_live_rows``: one copy
per live slot, the slots past a row's extent never copied and read as
zero); what is new is the ``m == last`` epilogue that factors and
samples in registers instead of writing Λ/η out.

Small-K linear algebra without dynamic lane indexing: TPU vector layouts
forbid addressing individual lanes, so the Cholesky and the triangular
solves are written as fori_loops over columns where every "element access"
is a masked broadcasted-iota reduction and every "element write" is a
masked add into a zero lane.  Each column step costs a few such ops over
the whole tile it is given, so the epilogue is sized by the true rank k,
not by the lane padding: its loops run k steps, on the live (TN, ks, ks)
corner of the 128-lane tile (ks = k rounded up to 8 sublanes,
``live_width``), and the sample is written into the first ks lanes of the
output block, whose other lanes stay zero.  At K=10 that is 10 steps on
16 vregs per tile instead of 128 steps on 128.  The K ≤ 32 route
(ops.py falls back above it) is where this pays for the saved HBM
round-trips.

Noise contract: the caller supplies z = normal(key, (N, K)) — the SAME
draw ``posterior.sample_rows`` makes — so the chain's random stream is
bitwise-preserved no matter which path (kernel / fallback / legacy
unfused) executes the sweep.

Mixed precision: the Λ accumulate runs in bf16 in mixed mode with f32
MXU accumulation (``preferred_element_type``); the gather scratch, the
Λ/η scratches, priors, Cholesky, and solves are f32 ALWAYS — bf16 never
reaches the factorization (the bmf_lint dtype pass proves this over the
lowered jaxpr).

Bitwise parity with the off-TPU fallback is BY CONSTRUCTION: ref.py runs
``accum_tile``/``sample_tile`` — the same helpers below — over the same
padded planes in the same M-tile order, so interpret-mode Pallas and the
striped-XLA fallback agree bit-for-bit (tests/test_sweep_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bmf_precision.kernel import (
    LANES, TM, TN, gather_live_rows, live_block, tile_extent)

__all__ = ["accum_tile", "sample_tile", "chol_tile", "solve_lower_tile",
           "solve_upper_tile", "live_width", "fused_sweep_padded",
           "TN", "TM", "LANES"]


# ---------------------------------------------------------------------------
# Shared tile math — called by BOTH the Pallas kernel body and the striped
# XLA fallback (ref.py).  Everything here is per-row batched (leading axis B)
# with no cross-row reductions, so results are independent of how rows are
# batched into tiles — the property the bitwise parity tests rely on.
# ---------------------------------------------------------------------------

# Matmuls here are pinned to full f32: XLA's default TPU precision runs an
# f32 dot as one bf16 pass, which would make the striped-XLA path on TPU
# (K > SWEEP_K_MAX, and the on-chip parity check) a different sampler from
# the kernel's.  No effect on the CPU backend.
F32_DOT = jax.lax.Precision.HIGHEST


def accum_tile(lam, eta, v, w, r, tau, dtype=jnp.float32):
    """Fold one M-tile of gathered factor rows into the (Λ, η) accumulators.

    lam (B, K, K) f32, eta (B, K) f32; v (B, tm, K) f32 gathered rows;
    w/r (B, tm) f32 mask/value planes.  The Λ matmul runs on ``dtype``
    (f32, or bf16 in mixed mode) with f32 accumulation — the
    mixed-precision contract; η stays f32.

    Mosaic constraints shape three lines: rows are gathered in f32 and
    cast here, because a one-row DMA of a packed bf16 tile does not lower;
    the mask is broadcast in f32 and the product cast back (w is 0/1, so
    exact), because a bf16 (B, tm) -> (B, tm, 1) reshape does not lower;
    and η is a masked sum over the slot axis, because the batched mat-vec
    einsum has no LHS non-contracting dim, which Mosaic's dot rejects."""
    vc = v.astype(dtype)
    vm = (vc * w[..., None]).astype(dtype)
    lam = lam + tau * jax.lax.dot_general(
        vm, vc, (((1,), (1,)), ((0,), (0,))),
        precision=F32_DOT if vc.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)
    eta = eta + tau * jnp.sum((r * w)[..., None] * v, axis=1)
    return lam, eta


def _kk_iota(K, dtype=jnp.float32):
    rows = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    return rows, cols


def live_width(k: int, K: int) -> int:
    """Width of the corner that loops bounded by ``k`` read: ``k`` rounded up
    to the 8-row sublane tile, at most the operands' own width ``K``."""
    return min(-(-k // 8) * 8, K)


def chol_tile(A, k=None):
    """Batched left-looking Cholesky of (B, K, K) SPD tiles, over the
    leading ``k`` columns (default all K).

    Column j of L needs only columns < j — which are the only nonzeros of
    the running factor — so the cross-term Σ_{p<j} L[i,p]·L[j,p] is the
    FULL-K contraction against row j (zeros beyond p<j contribute exactly
    nothing).  For the same reason the leading k columns do not depend on
    the rest: where A is zero off the diagonal past k, stopping at k gives
    them unchanged and leaves columns ≥ k zero.  Element reads/writes are
    masked-iota reductions/adds: no dynamic lane indexing anywhere."""
    B, K, _ = A.shape
    k = K if k is None else k
    rows, cols = _kk_iota(K)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def col(j, L):
        colsel = (cols == j).astype(A.dtype)            # one-hot column j
        rowsel = (rows == j).astype(A.dtype)            # one-hot row j
        a_col = jnp.sum(A * colsel[None], axis=2)       # (B, K) = A[:, :, j]
        l_row = jnp.sum(L * rowsel[None], axis=1)       # (B, K) = L[:, j, :]
        # s_i = Σ_p L[i, p] · L[j, p]; at i = j this is Σ L[j, p]²
        s = jax.lax.dot_general(L, l_row,
                                (((2,), (1,)), ((0,), (0,))),
                                precision=F32_DOT)
        a_jj = jnp.sum(a_col * (lane == j).astype(A.dtype), axis=1)
        sq = jnp.sum(l_row * l_row, axis=1)
        ljj = jnp.sqrt(a_jj - sq)                       # (B,)
        below = (lane > j).astype(A.dtype)              # strictly-lower mask
        at_j = (lane == j).astype(A.dtype)
        newcol = (a_col - s) / ljj[:, None] * below + ljj[:, None] * at_j
        return L + newcol[:, :, None] * colsel[None]    # write column j

    return jax.lax.fori_loop(0, k, col, jnp.zeros_like(A))


def solve_lower_tile(L, b, k=None):
    """Forward substitution y = L⁻¹ b for (B, K, K) lower tiles, over the
    leading ``k`` lanes (default all K); lanes ≥ k stay zero."""
    B, K = b.shape
    k = K if k is None else k
    rows, _ = _kk_iota(K)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def step(j, y):
        rowsel = (rows == j).astype(L.dtype)
        l_row = jnp.sum(L * rowsel[None], axis=1)       # (B, K) = L[:, j, :]
        s = jnp.sum(l_row * y, axis=1)                  # y zeroed for p ≥ j
        at_j = (lane == j).astype(L.dtype)
        bj = jnp.sum(b * at_j, axis=1)
        ljj = jnp.sum(l_row * at_j, axis=1)
        return y + ((bj - s) / ljj)[:, None] * at_j

    return jax.lax.fori_loop(0, k, step, jnp.zeros_like(b))


def solve_upper_tile(L, b, k=None):
    """Backward substitution x = L⁻ᵀ b (solve against the TRANSPOSE of the
    lower factor — the covariance half of the ``sample_rows_noise`` split),
    from lane k - 1 down (default k = K); lanes ≥ k stay zero."""
    B, K = b.shape
    k = K if k is None else k
    _, cols = _kk_iota(K)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def step(t, x):
        j = k - 1 - t
        colsel = (cols == j).astype(L.dtype)
        l_col = jnp.sum(L * colsel[None], axis=2)       # (B, K) = L[:, :, j]
        s = jnp.sum(l_col * x, axis=1)                  # x zeroed for p ≤ j
        at_j = (lane == j).astype(L.dtype)
        bj = jnp.sum(b * at_j, axis=1)
        ljj = jnp.sum(l_col * at_j, axis=1)
        return x + ((bj - s) / ljj)[:, None] * at_j

    return jax.lax.fori_loop(0, k, step, jnp.zeros_like(b))


def sample_tile(lam, eta, prior_lam, prior_eta, z, jitter, k=None):
    """Finish one row tile: add the prior, factor, and draw the sample.

    Mirrors ``posterior.sample_rows_noise`` exactly — Λ += jitter·I,
    μ = Λ⁻¹η via forward+backward solve, δ = L⁻ᵀ z — with the in-register
    solvers above, over the true rank ``k`` (default the operands' K).
    Lanes ≥ k of the sample are exactly zero.  All f32: bf16 stops at the
    accumulate."""
    K = eta.shape[-1]
    rows, cols = _kk_iota(K)
    eye = (rows == cols).astype(jnp.float32)
    A = lam + prior_lam + jitter * eye[None]
    b = eta + prior_eta
    L = chol_tile(A, k)
    mu = solve_upper_tile(L, solve_lower_tile(L, b, k), k)
    delta = solve_upper_tile(L, z, k)
    return mu + delta


# ---------------------------------------------------------------------------
# The Pallas kernel
# ---------------------------------------------------------------------------


def _sweep_kernel(idx_ref, ext_ref, val_ref, mask_ref, peta_ref, plam_ref,
                  z_ref, other_ref, u_ref, lam_ref, eta_ref, vg_ref, sem, *,
                  tau: float, tm: int, jitter: float, dtype, k: int):
    n = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        lam_ref[...] = jnp.zeros_like(lam_ref)
        eta_ref[...] = jnp.zeros_like(eta_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(m * tm < tile_extent(ext_ref, n))
    def _accumulate():
        v = gather_live_rows(idx_ref, ext_ref, other_ref, vg_ref, sem,
                             mask_ref[...], n, m, tm=tm)
        lam, eta = accum_tile(lam_ref[...], eta_ref[...], v,
                              mask_ref[...], val_ref[...], tau, dtype)
        lam_ref[...] = lam
        eta_ref[...] = eta

    @pl.when(m == pl.num_programs(1) - 1)
    def _solve_and_sample():
        # epilogue: Λ/η never leave VMEM — prior add, in-register Cholesky,
        # triangular solves, and the noise add all happen here, and the only
        # HBM write of the whole factor step is this (TN, K) sample block.
        # It reads only the live (ks, ks) corner; the lanes past it keep the
        # zeros written at m == 0
        ks = live_width(k, u_ref.shape[-1])
        u_ref[:, :ks] = sample_tile(
            lam_ref[:, :ks, :ks], eta_ref[:, :ks], plam_ref[:, :ks, :ks],
            peta_ref[:, :ks], z_ref[:, :ks], jitter, k)


def fused_sweep_padded(idx, ext, val, mask, prior_eta, prior_lam, z,
                       other, tau: float, *, tm: int = TM,
                       jitter: float = 1e-6, dtype=jnp.float32,
                       k: int | None = None, interpret: bool = False):
    """idx/val/mask: (N, M) with N % TN == 0, M % tm == 0; ext: (N,) int32
    per-row live extents (``data.sparse.row_extent``); prior_eta/z:
    (N, K), prior_lam: (N, K, K) f32 with pad lanes carrying an identity
    diagonal; other: (D, K) f32, HBM-resident; dtype: the Λ matmul
    operand dtype (``accum_tile``);
    k: the true rank inside the lane padding (default K), which bounds the
    epilogue's Cholesky and solves.  Returns the sampled factor U (N, K),
    zero past lane k — no (N, K, K) HBM intermediate."""
    N, M = idx.shape
    D, K = other.shape
    k = K if k is None else k
    assert N % TN == 0 and M % tm == 0, (N, M, tm)
    grid = (N // TN, M // tm)

    def row_block(n, m, *_):
        return (n, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TN, tm), live_block(tm)),         # val
            pl.BlockSpec((TN, tm), live_block(tm)),         # mask
            pl.BlockSpec((TN, K), row_block),               # prior eta
            pl.BlockSpec((TN, K, K), lambda n, m, *_: (n, 0, 0)),
            pl.BlockSpec((TN, K), row_block),               # noise z
            pl.BlockSpec(memory_space=pl.ANY),              # other: HBM
        ],
        out_specs=pl.BlockSpec((TN, K), row_block),
        scratch_shapes=[
            pltpu.VMEM((TN, K, K), jnp.float32),            # Λ accumulator
            pltpu.VMEM((TN, K), jnp.float32),               # η accumulator
            pltpu.VMEM((TN * tm, K), other.dtype),          # gathered rows
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_sweep_kernel, tau=tau, tm=tm, jitter=jitter,
                               dtype=dtype, k=k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        interpret=interpret,
    )(idx, ext, val, mask, prior_eta, prior_lam, z, other)
