"""Pallas TPU kernel: fused-gather per-row precision/linear-term accumulation
for the BMF Gibbs conditional — the paper's compute hot-spot (O(nnz·K²),
§3.4 "compute intensity is O(K³) per row").

Zero-materialization design (vs the old wrapper that gathered
``Vg = other[idx]`` into a dense (N, M, K) HBM array *before* the kernel):

  - the factor matrix ``other`` (D, K) stays resident in HBM
    (``memory_space=ANY``); nothing of shape (N, M, K) ever exists.
  - the padded-CSR column indices are **scalar-prefetched**
    (``pltpu.PrefetchScalarGridSpec``) so they are available in SMEM before
    the kernel body runs; each grid step DMAs exactly the TN·TM factor rows
    it needs into a VMEM scratch (row-granular ``make_async_copy`` with a
    fixed lookahead window so copies overlap the index reads).
  - the per-row rank-1 accumulation Σ_m v vᵀ then runs as a batched
    (K, TM) × (TM, K) matmul on the MXU exactly as before, with the η
    accumulation fused into the same pass.
  - nnz-aware grid: the second scalar-prefetch operand gives, per TN-row
    tile, the number of M-tiles that contain any live slot
    (``data.sparse.tile_occupancy``).  All-padding M-tiles are skipped —
    no DMA, no matmul — and their input-block index maps clamp to the last
    live tile so the pipeline re-uses the already-resident block instead of
    fetching a dead one.

Grid: (N/TN, M/TM) with M innermost, so the (TN, K, K) output block stays
resident in VMEM and accumulates across M tiles (revisited-output pattern).

VMEM budget per step: TN·TM·K·4 (gather scratch) + TN·TM·4·2 (val/mask) +
TN·K·K·4 + TN·K·4 (outputs) ≈ 8·256·128·4 + 16 KB + 0.5 MB ≈ 1.6 MB for
K=128 — comfortably inside the ~16 MB VMEM.  SMEM holds this call's
(N_stripe, M) int32 index plane; the ops.py wrapper stripes the N axis so
that plane stays under its SMEM_IDX_BUDGET per pallas_call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TN = 8       # rows per tile
TM = 256     # nnz slots per tile
LANES = 128  # MXU/VPU lane width; K is padded to a multiple of this
DMA_LOOKAHEAD = 16   # outstanding row copies kept in flight


def _fused_kernel(idx_ref, ntiles_ref, val_ref, mask_ref, other_ref,
                  lam_ref, eta_ref, vg_ref, sem, *, tau: float, tm: int):
    n = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        lam_ref[...] = jnp.zeros_like(lam_ref)
        eta_ref[...] = jnp.zeros_like(eta_ref)

    @pl.when(m < ntiles_ref[n])
    def _accumulate():
        G = TN * tm

        def row_copy(s):
            # slot s of this tile gathers factor row idx[r, c]
            r = n * TN + s // tm
            c = m * tm + s % tm
            row = idx_ref[r, c]
            return pltpu.make_async_copy(other_ref.at[pl.ds(row, 1)],
                                         vg_ref.at[pl.ds(s, 1)], sem)

        def warmup(s, carry):
            row_copy(s).start()
            return carry

        jax.lax.fori_loop(0, DMA_LOOKAHEAD, warmup, None)

        def pump(s, carry):
            @pl.when(s + DMA_LOOKAHEAD < G)
            def _():
                row_copy(s + DMA_LOOKAHEAD).start()
            row_copy(s).wait()
            return carry

        jax.lax.fori_loop(0, G, pump, None)

        v = vg_ref[...].astype(jnp.float32).reshape(TN, tm, -1)
        w = mask_ref[...].astype(jnp.float32)       # (TN, TM)
        r = val_ref[...].astype(jnp.float32)        # (TN, TM)

        vm = v * w[..., None]
        # batched (K, TM) x (TM, K) matmuls on the MXU
        lam_ref[...] += tau * jax.lax.dot_general(
            vm, v, (((1,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        # fused η accumulation — same pass, same gathered rows; a masked
        # sum, since Mosaic rejects the batched mat-vec dot (no LHS
        # non-contracting dim)
        eta_ref[...] += tau * jnp.sum((r * w)[..., None] * v, axis=1)


def precision_accum_fused_padded(idx, ntiles, val, mask, other, tau: float, *,
                                 tm: int = TM, interpret: bool = False):
    """idx/val/mask: (N, M) with N % TN == 0, M % tm == 0; ntiles: (N/TN,)
    live-M-tile counts; other: (D, K) with K % LANES == 0, resident in HBM.
    Returns (Lam (N, K, K), eta (N, K)) — no (N, M, K) intermediate."""
    N, M = idx.shape
    D, K = other.shape
    assert N % TN == 0 and M % tm == 0, (N, M, tm)
    assert K % LANES == 0, K
    grid = (N // TN, M // tm)

    def live_block(n, m, idx_ref, ntiles_ref):
        # skipped steps re-point at the tile's last live block: the pipeline
        # sees the same block index and elides the copy entirely
        return (n, jnp.minimum(m, jnp.maximum(ntiles_ref[n], 1) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TN, tm), live_block),     # val
            pl.BlockSpec((TN, tm), live_block),     # mask
            pl.BlockSpec(memory_space=pl.ANY),      # other: stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((TN, K, K), lambda n, m, *_: (n, 0, 0)),
            pl.BlockSpec((TN, K), lambda n, m, *_: (n, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((TN * tm, K), other.dtype),  # gathered rows
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_fused_kernel, tau=tau, tm=tm)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((N, K, K), jnp.float32),
            jax.ShapeDtypeStruct((N, K), jnp.float32),
        ],
        interpret=interpret,
    )(idx, ntiles, val, mask, other)
