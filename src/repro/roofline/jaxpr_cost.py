"""Jaxpr-level cost model: exact FLOP counting with scan trip-count
multiplication (XLA's ``cost_analysis`` counts while-loop bodies ONCE, which
undercounts a scanned-layer transformer by ~n_layers — see EXPERIMENTS
§Dry-run methodology).

``jaxpr_cost(jitted.trace(...).jaxpr)`` walks the closed jaxpr:
  - dot_general: 2 · prod(batch) · M · N · K
  - scan: recurse × length
  - while: recurse × 1 (trip unknown; we don't emit unbounded whiles)
  - pjit / remat / custom_*: recurse (remat'd recompute appears explicitly
    in the grad jaxpr, so backward recompute is counted faithfully)
  - everything else: 1 flop per output element (elementwise estimate)

Byte counting sums operand+result sizes of dots, gathers/scatters/
dynamic-slices and scan-carried streams — an un-fused upper bound for HBM
traffic (fusion reduces elementwise traffic; dots dominate the shapes we
care about). FLOPs/bytes here are GLOBAL (the jaxpr is the pre-SPMD
program); divide by chip count for per-device terms.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np
from jax import core as jcore


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> int:
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    k = 1
    for d in lc:
        k *= lhs.shape[d]
    return 2 * int(np.prod(out.shape)) * k


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    # flops = 2 * out_elems * (kernel spatial * in_features)
    k = int(np.prod(rhs.shape[:-1]))
    return 2 * int(np.prod(out.shape)) * k


_RECURSE_CALL = {"pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
                 "custom_vjp_call_jaxpr", "remat2", "checkpoint", "core_call",
                 "xla_call", "named_call", "custom_transpose_call"}


def jaxpr_cost(jaxpr, mult: int = 1) -> Dict[str, float]:
    """Returns {'flops', 'bytes', 'dot_flops', 'elem_flops'} for one jaxpr
    (pass ClosedJaxpr.jaxpr or Jaxpr)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    total = {"flops": 0.0, "bytes": 0.0, "bytes_min": 0.0,
             "dot_flops": 0.0, "elem_flops": 0.0}

    def add(key, v):
        total[key] += mult * v

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            f = _dot_flops(eqn)
            b = (sum(_nbytes(v.aval) for v in eqn.invars) +
                 sum(_nbytes(v.aval) for v in eqn.outvars))
            add("flops", f)
            add("dot_flops", f)
            add("bytes", b)
            add("bytes_min", b)
        elif prim in ("conv_general_dilated",):
            f = _conv_flops(eqn)
            b = (sum(_nbytes(v.aval) for v in eqn.invars) +
                 sum(_nbytes(v.aval) for v in eqn.outvars))
            add("flops", f)
            add("dot_flops", f)
            add("bytes", b)
            add("bytes_min", b)
        elif prim == "pallas_call":
            # cost the kernel body per grid step × grid product. FLOPs are
            # exact. Bytes: each ref's BLOCK (the inner aval) is fetched per
            # grid step — an upper bound on HBM traffic (Pallas skips
            # refetching blocks whose index is unchanged between consecutive
            # steps, e.g. the q tile across the kv axis of flash attention);
            # VMEM scratch (online-softmax state, pairwise score tiles)
            # correctly contributes nothing.
            inner_jaxpr = eqn.params.get("jaxpr")
            gm = eqn.params.get("grid_mapping")
            grid = tuple(getattr(gm, "grid", ())) if gm is not None else ()
            steps = 1
            for g in grid:
                steps *= int(g)
            if inner_jaxpr is not None:
                inner = jaxpr_cost(inner_jaxpr, mult=1)
                total["flops"] += mult * steps * inner["flops"]
                total["dot_flops"] += mult * steps * inner["dot_flops"]
                total["elem_flops"] += mult * steps * inner["elem_flops"]
                ij = (inner_jaxpr.jaxpr if hasattr(inner_jaxpr, "jaxpr")
                      else inner_jaxpr)
                block_bytes = sum(_nbytes(v.aval) for v in ij.invars
                                  if hasattr(v.aval, "shape"))
                add("bytes", steps * block_bytes)
                add("bytes_min", steps * block_bytes)
            else:
                b = (sum(_nbytes(v.aval) for v in eqn.invars) +
                     sum(_nbytes(v.aval) for v in eqn.outvars))
                add("bytes", b)
                add("bytes_min", b)
        elif prim == "scan":
            inner = jaxpr_cost(eqn.params["jaxpr"], mult=1)
            length = eqn.params["length"]
            n_unroll = eqn.params.get("unroll", 1) or 1
            trips = length
            for k in total:
                total[k] += mult * trips * inner[k]
        elif prim == "while":
            inner = jaxpr_cost(eqn.params["body_jaxpr"], mult=1)
            for k in total:
                total[k] += mult * inner[k]  # trip count unknown
        elif prim == "cond":
            branches = [jaxpr_cost(b, mult=1) for b in eqn.params["branches"]]
            worst = max(branches, key=lambda c: c["flops"])
            for k in total:
                total[k] += mult * worst[k]
        elif prim in _RECURSE_CALL or "jaxpr" in eqn.params or "call_jaxpr" in eqn.params:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                inner = jaxpr_cost(sub, mult=1)
                for k in total:
                    total[k] += mult * inner[k]
        elif prim in ("gather", "scatter", "scatter-add", "scatter_add",
                      "dynamic_slice", "dynamic_update_slice", "take"):
            b = sum(_nbytes(v.aval) for v in eqn.outvars) * 2
            add("bytes", b)
            add("bytes_min", b)
        else:
            out_elems = sum(int(np.prod(v.aval.shape)) for v in eqn.outvars
                            if hasattr(v.aval, "shape"))
            add("flops", out_elems)
            add("elem_flops", out_elems)
            add("bytes", sum(_nbytes(v.aval) for v in eqn.invars) +
                sum(_nbytes(v.aval) for v in eqn.outvars))
    return total


def traced_cost(jitted, *args) -> Dict[str, float]:
    """Cost of a jitted function at given (abstract) args."""
    tr = jitted.trace(*args)
    return jaxpr_cost(tr.jaxpr)


def iter_eqns(jaxpr):
    """Yield every eqn in a (closed) jaxpr, recursing into sub-jaxprs
    hiding in eqn params (scan/while bodies, cond branches, pjit
    sub-jaxprs, pallas kernel jaxprs) — the traversal the dtype and
    host-callback lint passes run on."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for q in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(q, (ClosedJaxpr, Jaxpr)):
                    yield from iter_eqns(q)


def iter_avals(jaxpr):
    """Yield every aval appearing anywhere in a (closed) jaxpr — eqn
    in/outvars plus all sub-jaxprs hiding in eqn params (scan bodies,
    pallas kernel jaxprs, cond branches, ...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for v in list(jaxpr.invars) + list(jaxpr.outvars) + list(jaxpr.constvars):
        aval = getattr(v, "aval", None)
        if hasattr(aval, "shape"):
            yield aval
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if hasattr(aval, "shape"):
                yield aval
        for p in eqn.params.values():
            for q in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(q, (ClosedJaxpr, Jaxpr)):
                    yield from iter_avals(q)


def peak_buffer_bytes(jaxpr) -> int:
    """Largest single buffer (aval) anywhere in the jaxpr, sub-jaxprs
    included — a cheap proxy for the materialization high-water mark (e.g.
    the (N, M, K) gathered-factor tensor of a naive BMF sufficient-stats
    formulation shows up here; the fused/chunked paths don't have it)."""
    return max((_nbytes(a) for a in iter_avals(jaxpr)), default=0)
