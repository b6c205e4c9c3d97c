"""Route decision for the BMF factor-step kernels, in one place.

``pallas_route(kernel, K)`` says whether ``bmf_precision.ops.precision_accum``
('precision'), ``bmf_sweep.ops.fused_sweep`` ('sweep') or
``bmf_sample.ops.sample_rows_noise`` ('sample') runs its Pallas kernel or
its XLA path, from the two things the code can observe: the platform and
K.  The dispatchers call it, and so does ``chip_smoke.py``, which prints
the route its steps took.

  - Off TPU every kernel takes its XLA path (interpret-mode Pallas is for
    the parity tests only).
  - On TPU 'precision' always runs Pallas; 'sweep' runs Pallas for
    K <= SWEEP_K_MAX.  Above that the in-register Cholesky's O(K²)
    masked-lane work outweighs the saved HBM round trips, and the sweep
    takes the striped-XLA path, with a warning at trace time.
  - On TPU 'sample' runs Pallas for K <= SAMPLE_K_MAX: that kernel keeps
    rows on lanes and K on the leading and sublane axes, so K is bounded
    by VMEM (3·K²·128·4 bytes per tile), not by the lane width.  Its
    dispatcher also needs a full lane tile of rows (128); the serving
    router's small fold-in batches stay on XLA.

The precision and sweep kernels on TPU pad K to one 128-lane tile.  Wider
rows are refused with a ValueError (``check_lane_width``): Mosaic cannot
lower the one-row factor DMA once a row spans more than one lane tile.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.bmf_precision.kernel import LANES

SWEEP_K_MAX = 32     # largest K the one-pass sweep kernel takes on TPU
SAMPLE_K_MAX = LANES  # largest K the row sampler kernel takes on TPU
KERNELS = ("precision", "sweep", "sample")


def pallas_route(kernel: str, K: int, platform: Optional[str] = None) -> bool:
    """True when ``kernel`` runs as a compiled Pallas kernel for this
    platform (default: ``jax.default_backend()``) and K; False means its
    XLA path."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
    if (platform or jax.default_backend()) != "tpu":
        return False
    if kernel == "precision":
        return True
    return K <= (SWEEP_K_MAX if kernel == "sweep" else SAMPLE_K_MAX)


def check_lane_width(K: int) -> None:
    """Refuse a K the compiled kernels cannot take (K pads past one lane
    tile), before Mosaic fails on it."""
    if K > LANES:
        raise ValueError(
            f"K={K} pads to {-(-K // LANES) * LANES} lanes; the Pallas BMF "
            f"kernels on TPU take K <= {LANES} (one lane tile): the one-row "
            f"factor DMA does not lower for wider rows")
