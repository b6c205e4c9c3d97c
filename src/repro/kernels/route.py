"""Route decision for the BMF factor-step kernels, in one place.

``pallas_route(kernel, K)`` says whether ``bmf_precision.ops.precision_accum``
('precision') or ``bmf_sweep.ops.fused_sweep`` ('sweep') runs its Pallas
kernel or its XLA path, from the two things the code can observe: the
platform and K.  Both dispatchers call it, and so does ``chip_smoke.py``,
which prints the route its steps took.

  - Off TPU both kernels take their XLA paths (interpret-mode Pallas is for
    the parity tests only).
  - On TPU 'precision' always runs Pallas; 'sweep' runs Pallas for
    K <= SWEEP_K_MAX.  Above that the in-register Cholesky's O(K²)
    masked-lane work outweighs the saved HBM round trips, and the sweep
    takes the striped-XLA path, with a warning at trace time.

Either kernel on TPU pads K to one 128-lane tile.  Wider rows are refused
with a ValueError (``check_lane_width``): Mosaic cannot lower the one-row
factor DMA once a row spans more than one lane tile.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.bmf_precision.kernel import LANES

SWEEP_K_MAX = 32     # largest K the one-pass sweep kernel takes on TPU
KERNELS = ("precision", "sweep")


def pallas_route(kernel: str, K: int, platform: Optional[str] = None) -> bool:
    """True when ``kernel`` runs as a compiled Pallas kernel for this
    platform (default: ``jax.default_backend()``) and K; False means its
    XLA path."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
    if (platform or jax.default_backend()) != "tpu":
        return False
    return kernel == "precision" or K <= SWEEP_K_MAX


def check_lane_width(K: int) -> None:
    """Refuse a K the compiled kernels cannot take (K pads past one lane
    tile), before Mosaic fails on it."""
    if K > LANES:
        raise ValueError(
            f"K={K} pads to {-(-K // LANES) * LANES} lanes; the Pallas BMF "
            f"kernels on TPU take K <= {LANES} (one lane tile): the one-row "
            f"factor DMA does not lower for wider rows")
