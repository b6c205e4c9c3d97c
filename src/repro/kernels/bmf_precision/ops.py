"""Jit'd public wrappers for the bmf_precision kernel.

``precision_accum`` is the hot-path entry point used by
``core.bmf.sufficient_stats(use_kernel=True)``.  Neither implementation it
dispatches to ever materializes the gathered (N, M, K) factor tensor:

  - on TPU: the fused-gather Pallas kernel (kernel.py) — column indices are
    scalar-prefetched, factor rows are DMA'd from HBM into VMEM per tile.
  - off TPU: an N-striped XLA fallback gathering only (n_stripe, M, K) per
    stripe, in the symmetric one-operand form (interpret-mode Pallas is
    orders of magnitude slower than XLA on CPU, so it is reserved for
    parity tests).

``precision_accum_fused`` exposes the Pallas path directly (interpret mode
off-TPU) for parity testing; ``precision_accum_reference`` is the dense
full-gather oracle — it is the ONLY path that builds (N, M, K).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bmf_precision.kernel import (
    LANES, TM, TN, precision_accum_fused_padded)
from repro.kernels.bmf_precision.ref import precision_accum_ref
from repro.kernels.route import check_lane_width, pallas_route
from repro.data.sparse import row_extent


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# peak-gather budget (elements) of the chunked XLA fallback: the N axis is
# striped so each stripe's (n_stripe, M, K) gather stays near this budget
# (~8 MB f32).  Stripes are independent row blocks — full-M matmuls, no
# accumulator chain — which measured faster than M-tiling at every shape
# tried (thin M-tiles serialize; fat ones just re-create the blowup)
CHUNK_BUDGET_ELEMS = 2 << 20

# scalar-prefetch operands live in SMEM, which is KB-scale: cap the (N, M)
# int32 index plane per pallas_call and stripe the N axis above it (each
# stripe is an independent call; outputs concatenate along N)
SMEM_IDX_BUDGET = 256 * 1024


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("tau",))
def precision_accum(idx, val, mask, other, tau: float):
    """idx/val/mask: padded CSR (N, M); other: (D, K) factor matrix.
    Returns (Lam (N, K, K), eta (N, K)) likelihood contributions."""
    if pallas_route("precision", other.shape[-1]):
        return precision_accum_fused(idx, val, mask, other, tau,
                                     interpret=False)
    return precision_accum_chunked(idx, val, mask, other, tau)


def precision_accum_fused(idx, val, mask, other, tau: float, *,
                          tm: int = TM, interpret=None,
                          smem_idx_budget: int = SMEM_IDX_BUDGET):
    """Fused-gather Pallas path: pads (N, M) to tile multiples and K to the
    LANES width, computes each row's live extent (the pump's copy bound),
    and dispatches.  The gather happens INSIDE the kernel — peak live
    memory here is O(N·M) CSR planes + O(D·K) factors + O(N·K²) outputs.

    The scalar-prefetched index plane sits in SMEM, so the N axis is
    striped such that each pallas_call's (n_stripe, M) int32 plane stays
    under ``smem_idx_budget`` bytes.  Stripes run under ``lax.map`` — ONE
    pallas_call in the program regardless of N (a Python loop would emit
    one call per stripe and blow up compile time at web-scale N), with
    ``other`` resident across all stripes."""
    if interpret is None:
        interpret = not _on_tpu()
    N, M = idx.shape
    D, K = other.shape
    if not interpret:
        check_lane_width(K)
    Kp = ((K + LANES - 1) // LANES) * LANES
    Mp = ((M + tm - 1) // tm) * tm
    ns = max(TN, (smem_idx_budget // (Mp * 4)) // TN * TN)
    Np = ((N + ns - 1) // ns) * ns                 # rows pad to whole stripes

    idxp = _pad_to(idx, Mp, 1)
    idxp = _pad_to(idxp, Np, 0)                    # padded slots gather row 0
    valp = _pad_to(_pad_to(val, Mp, 1), Np, 0)
    maskp = _pad_to(_pad_to(mask, Mp, 1), Np, 0)   # ... but are masked out
    otherp = _pad_to(other, Kp, 1)
    extp = row_extent(maskp)                       # the pump's live extents

    def stripe(args):
        ix, ex, vl, mk = args
        return precision_accum_fused_padded(ix, ex, vl, mk, otherp, tau,
                                            tm=tm, interpret=interpret)

    if Np == ns:
        Lam, eta = stripe((idxp, extp, valp, maskp))
    else:
        nsp = Np // ns
        Lam, eta = jax.lax.map(stripe, (idxp.reshape(nsp, ns, Mp),
                                        extp.reshape(nsp, ns),
                                        valp.reshape(nsp, ns, Mp),
                                        maskp.reshape(nsp, ns, Mp)))
        Lam = Lam.reshape(Np, Kp, Kp)
        eta = eta.reshape(Np, Kp)
    return Lam[:N, :K, :K], eta[:N, :K]


def precision_accum_chunked(idx, val, mask, other, tau: float, *,
                            budget_elems: int = CHUNK_BUDGET_ELEMS):
    """XLA fallback with the same zero-materialization property: the N axis
    is striped so only an (n_stripe, M, K) gather is ever live.  Stripes
    are independent (outputs concatenate along N), so each keeps the fat
    full-M batched matmul, and the loop is statically unrolled — a lax
    loop would wall off the per-stripe gather+matmul from XLA fusion."""
    N, M = idx.shape
    K = other.shape[-1]
    n_stripe = max(8, budget_elems // max(M * K, 1) // 8 * 8)
    if N <= n_stripe:
        return _sym_tile(idx, val, mask, other, tau)
    lams, etas = [], []
    for lo in range(0, N, n_stripe):
        hi = min(lo + n_stripe, N)
        l, e = _sym_tile(idx[lo:hi], val[lo:hi], mask[lo:hi], other, tau)
        lams.append(l)
        etas.append(e)
    return jnp.concatenate(lams), jnp.concatenate(etas)


def _sym_tile(ix, vl, mk, other, tau):
    """Sufficient stats of one row stripe in the symmetric form: for 0/1
    masks, Σ w vvᵀ = (w⊙V)ᵀ(w⊙V), so ONE masked gather feeds both matmul
    operands (the two-operand ``einsum(Vm, V)`` form makes XLA keep a
    second gathered buffer live and is measurably slower)."""
    Vm = other[ix] * mk[..., None]
    # full f32 on TPU too, where this path is the kernel's on-chip check
    hi = jax.lax.Precision.HIGHEST
    lam = tau * jax.lax.dot_general(Vm, Vm, (((1,), (1,)), ((0,), (0,))),
                                    precision=hi,
                                    preferred_element_type=jnp.float32)
    eta = tau * jnp.einsum("nm,nmk->nk", vl, Vm, precision=hi,
                           preferred_element_type=jnp.float32)
    return lam, eta


def precision_accum_reference(idx, val, mask, other, tau: float):
    """Dense full-gather oracle — materializes (N, M, K); test/bench only."""
    Vg = other[idx]
    return precision_accum_ref(Vg, val, mask, tau)
