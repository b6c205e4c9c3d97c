"""Jaxpr-level passes: materialization budget, dtype promotion, host
callbacks. All three walk the FULL nested jaxpr (scan/while/cond bodies,
pjit sub-jaxprs, pallas kernels) via ``roofline.jaxpr_cost`` traversal —
a materialized (N, M, K) tensor hiding inside a scanned sweep body is
exactly the bug class these exist to catch."""
from __future__ import annotations

from typing import List

import numpy as np

from repro.analysis.registry import JaxprArtifact, Pass, Violation, register
from repro.roofline import jaxpr_cost as JCOST


def materialization_budget(n_rows: int, n_cols: int, m_rows: int,
                           m_cols: int, K: int, batch: int = 1,
                           slack: float = 2.0) -> int:
    """Largest buffer a fused block chain legitimately holds, from block
    dims: the per-observation factor gathers on the padded CSR planes
    (B*n*m*K f32 — U[idx] per plane slot) and the per-row outer-product
    accumulators (B*n*K*K f32), whichever is bigger, times ``slack`` for
    layout/padding headroom. The naive sufficient-stats formulation
    materializes the DENSE (N_block, M_block, K) factor tensor instead —
    a factor M_block/m_pad over the plane gather (full column dim vs the
    padded per-row observation width), so it trips the pass whenever the
    block is meaningfully sparse."""
    plane = max(n_rows * m_rows, n_cols * m_cols) * K
    outer = max(n_rows, n_cols) * K * K
    return int(slack * 4 * batch * max(plane, outer))


def _materialization(art: JaxprArtifact) -> List[Violation]:
    if art.bytes_budget is None:
        return []
    seen = set()
    out = []
    for aval in JCOST.iter_avals(art.jaxpr):
        nb = JCOST._nbytes(aval)
        if nb <= art.bytes_budget:
            continue
        sig = (str(getattr(aval, "dtype", "?")), tuple(aval.shape))
        if sig in seen:
            continue
        seen.add(sig)
        out.append(Violation(
            "materialization", art.label,
            f"aval {sig[0]}{list(sig[1])} is {nb} bytes, over the "
            f"{art.bytes_budget}-byte block budget",
            "a gathered/broadcast intermediate is being materialized — "
            "route the sufficient-stats accumulation through the fused "
            "gather kernel (core.kernels) or chunk the contraction so no "
            "buffer exceeds the padded CSR plane"))
    return out


register(Pass(
    "materialization", "jaxpr",
    "no aval anywhere in the (nested) jaxpr exceeds the block-dim byte "
    "budget — the no-(N,M,K)-tensor invariant",
    _materialization))


# fp32-required linear-algebra primitives: the Cholesky factor/solve path
# of the posterior update loses PD-ness in half precision.  ``sqrt`` is
# the in-register Cholesky diagonal of the fused sweep kernel
# (kernels/bmf_sweep hand-rolls the factorization, so no cholesky
# primitive appears in its jaxpr — the diagonal sqrt is the operand the
# mixed-precision mode must keep f32)
_FP32_REQUIRED = ("cholesky", "triangular_solve", "sqrt")
_LOW_PRECISION = ("bfloat16", "float16")


def _dtype_promotion(art: JaxprArtifact) -> List[Violation]:
    out = []
    seen = set()
    if not art.allow_f64:
        for aval in JCOST.iter_avals(art.jaxpr):
            dt = str(getattr(aval, "dtype", ""))
            if dt != "float64":
                continue
            sig = tuple(aval.shape)
            if sig in seen:
                continue
            seen.add(sig)
            out.append(Violation(
                "dtype-promotion", art.label,
                f"silent f64 upcast: f64{list(sig)} appears in the jaxpr",
                "a host-side numpy float64 leaked into the traced program "
                "— cast inputs to float32 at the data layer (or mark the "
                "artifact allow_f64 if the upcast is deliberate)"))
    for eqn in JCOST.iter_eqns(art.jaxpr):
        if eqn.primitive.name not in _FP32_REQUIRED:
            continue
        for v in eqn.invars:
            dt = str(getattr(getattr(v, "aval", None), "dtype", ""))
            if dt in _LOW_PRECISION:
                out.append(Violation(
                    "dtype-promotion", art.label,
                    f"{eqn.primitive.name} sees {dt} operand "
                    f"{list(v.aval.shape)} — the posterior factor/solve "
                    f"path requires fp32",
                    "keep mixed precision on the gather/accumulate side "
                    "only: upcast the Lambda accumulator to float32 "
                    "before from_moments_cov"))
    return out


register(Pass(
    "dtype-promotion", "jaxpr",
    "no silent f64 upcast; Cholesky/triangular-solve/sqrt operands are "
    "never bf16/f16",
    _dtype_promotion))


# primitives that punch through to the host from inside a jitted body —
# any of these inside a phase chain serializes the dispatch pipeline
# (``jax.debug.print`` lowers to ``debug_print``, ``jax.debug.callback`` to
# ``debug_callback``)
_HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
})


def _host_callback(art: JaxprArtifact) -> List[Violation]:
    out = []
    for eqn in JCOST.iter_eqns(art.jaxpr):
        if eqn.primitive.name in _HOST_PRIMS:
            out.append(Violation(
                "host-callback", art.label,
                f"host round-trip primitive {eqn.primitive.name!r} inside "
                f"a jitted phase body",
                "phase chains must stay device-resident end to end "
                "(guards.no_host_transfers is the runtime twin of this "
                "check) — move the callback outside the jitted chain or "
                "compute the quantity on device"))
    return out


register(Pass(
    "host-callback", "jaxpr",
    "no host-callback/transfer primitive inside a jitted phase body",
    _host_callback))
