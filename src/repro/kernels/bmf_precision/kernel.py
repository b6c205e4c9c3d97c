"""Pallas TPU kernel: fused-gather per-row precision/linear-term accumulation
for the BMF Gibbs conditional — the paper's compute hot-spot (O(nnz·K²),
§3.4 "compute intensity is O(K³) per row").

Zero-materialization design (vs the old wrapper that gathered
``Vg = other[idx]`` into a dense (N, M, K) HBM array *before* the kernel):

  - the factor matrix ``other`` (D, K) stays resident in HBM
    (``memory_space=ANY``); nothing of shape (N, M, K) ever exists.
  - the padded-CSR column indices are **scalar-prefetched**
    (``pltpu.PrefetchScalarGridSpec``) so they are available in SMEM before
    the kernel body runs, beside the second prefetch operand, each row's
    live extent (last live slot + 1, ``data.sparse.row_extent``).
  - the DMA row pump (``gather_live_rows``, shared with kernels/bmf_sweep)
    issues one row-granular ``make_async_copy`` per *live* slot of the
    tile — slots below the row's extent — into a VMEM scratch, with
    DMA_LOOKAHEAD copies in flight across row boundaries.  The padding
    slots past a row's extent are never copied and read as zero, so a
    left-filled CSR plane costs one copy per rating, not TN·TM per live
    tile.
  - the per-row rank-1 accumulation Σ_m v vᵀ then runs as a batched
    (K, TM) × (TM, K) matmul on the MXU exactly as before, with the η
    accumulation fused into the same pass.
  - nnz-aware grid: an M-tile at or past its row tile's largest extent is
    all padding and skipped — no DMA, no matmul — and the input-block
    index maps clamp it to the last live tile so the pipeline re-uses the
    already-resident block instead of fetching a dead one.

Grid: (N/TN, M/TM) with M innermost, so the (TN, K, K) output block stays
resident in VMEM and accumulates across M tiles (revisited-output pattern).

VMEM budget per step: TN·TM·K·4 (gather scratch) + TN·TM·4·2 (val/mask) +
TN·K·K·4 + TN·K·4 (outputs) ≈ 8·256·128·4 + 16 KB + 0.5 MB ≈ 1.6 MB for
K=128 — comfortably inside the ~16 MB VMEM.  SMEM holds this call's
(N_stripe, M) int32 index plane and its (N_stripe,) extents; the ops.py
wrapper stripes the N axis so the index plane stays under its
SMEM_IDX_BUDGET per pallas_call.
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


def tile_extent(ext_ref, n):
    """The largest live extent among the TN rows of row tile ``n``: M-tile
    m holds a live slot iff m·tm < tile_extent."""
    last = ext_ref[n * TN]
    for i in range(1, TN):
        last = jnp.maximum(last, ext_ref[n * TN + i])
    return last


def live_block(tm: int):
    """Index map of the (TN, tm) val/mask blocks: dead M-tiles re-point at
    the row tile's last live block, so the pipeline sees the same block
    index and elides the copy entirely."""
    def index(n, m, idx_ref, ext_ref, *_):
        last_live = (jnp.maximum(tile_extent(ext_ref, n), 1) - 1) // tm
        return (n, jnp.minimum(m, last_live))
    return index


def gather_live_rows(idx_ref, ext_ref, other_ref, vg_ref, sem, mask, n, m, *,
                     tm: int):
    """The DMA row pump both kernels share: gather the factor rows of the
    live slots of M-tile m of row tile n into the (TN·tm, K) VMEM scratch,
    and return them as (TN, tm, K) f32 with every masked slot exactly zero.

    Row i's live slots in this tile are [m·tm, min(extent_i, (m+1)·tm)):
    one ``make_async_copy`` each, issued by a cursor that walks (row,
    slot) and keeps DMA_LOOKAHEAD copies in flight across row boundaries
    — all copies are one row of ``other`` on one semaphore, so waits are
    interchangeable: one per copy started beyond the lookahead, then a
    drain.  Slots past a row's extent are not copied, so their scratch
    rows hold whatever an earlier tile (or nothing) left there; the
    select on the mask reads every masked slot as zero, so a masked
    product is exactly 0 and a stale NaN never reaches Λ."""
    base = m * tm

    def wait_one():
        pltpu.make_async_copy(other_ref.at[pl.ds(0, 1)],
                              vg_ref.at[pl.ds(0, 1)], sem).wait()

    def row(i, started):
        r = n * TN + i
        live = jnp.clip(ext_ref[r] - base, 0, tm)

        def slot(j, started):
            @pl.when(started >= DMA_LOOKAHEAD)
            def _():
                wait_one()
            pltpu.make_async_copy(
                other_ref.at[pl.ds(idx_ref[r, base + j], 1)],
                vg_ref.at[pl.ds(i * tm + j, 1)], sem).start()
            return started + 1

        return jax.lax.fori_loop(0, live, slot, started)

    started = jax.lax.fori_loop(0, TN, row, jnp.int32(0))

    def drain(_, carry):
        wait_one()
        return carry

    jax.lax.fori_loop(0, jnp.minimum(started, DMA_LOOKAHEAD), drain, None)

    v = vg_ref[...].astype(jnp.float32).reshape(TN, tm, -1)
    live = jnp.broadcast_to(mask.astype(jnp.float32)[..., None], v.shape)
    return jnp.where(live != 0, v, 0.0)


def _fused_kernel(idx_ref, ext_ref, val_ref, mask_ref, other_ref,
                  lam_ref, eta_ref, vg_ref, sem, *, tau: float, tm: int):
    n = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        lam_ref[...] = jnp.zeros_like(lam_ref)
        eta_ref[...] = jnp.zeros_like(eta_ref)

    @pl.when(m * tm < tile_extent(ext_ref, n))
    def _accumulate():
        v = gather_live_rows(idx_ref, ext_ref, other_ref, vg_ref, sem,
                             mask_ref[...], n, m, tm=tm)
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


def precision_accum_fused_padded(idx, ext, val, mask, other, tau: float, *,
                                 tm: int = TM, interpret: bool = False):
    """idx/val/mask: (N, M) with N % TN == 0, M % tm == 0; ext: (N,) int32
    per-row live extents (``data.sparse.row_extent``); other: (D, K) with
    K % LANES == 0, resident in HBM.
    Returns (Lam (N, K, K), eta (N, K)) — no (N, M, K) intermediate."""
    N, M = idx.shape
    D, K = other.shape
    assert N % TN == 0 and M % tm == 0, (N, M, tm)
    assert K % LANES == 0, K
    grid = (N // TN, M // tm)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TN, tm), live_block(tm)),     # val
            pl.BlockSpec((TN, tm), live_block(tm)),     # mask
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
    )(idx, ext, val, mask, other)
