"""The Gibbs chain names its layers with ``jax.named_scope``.

A profiler trace names each device op by its ``op_name`` path, so the
chip benchmark reads a layer's time from the ops whose path holds the
layer's scope as a whole component. These tests lower the chain through
``gibbs.trace_chain`` at a tiny shape, on both factor-step routes and for
one block and a stacked batch, and check that every scope reaches the
lowered module and survives compilation into the optimized HLO's
``op_name`` metadata.
"""
import re

import pytest

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS

# scopes of the chain itself, on every route
CHAIN_SCOPES = ("bmf_prior", "bmf_u_step", "bmf_v_step", "bmf_accumulate",
                "bmf_predict", "bmf_summarize")
# scopes of the factor step, by route
ROUTE_SCOPES = {"kernel": ("bmf_stats", "bmf_sample"),
                "fused": ("bmf_sweep", "bmf_sweep_layout")}
ROUTE_CFG = {"kernel": dict(use_kernel=True, sweep_fused=False),
             "fused": dict(sweep_fused=True)}


def _components(text: str) -> set:
    """Every path component of every op name or location in ``text``; a
    component a transform wraps (``vmap(bmf_summarize)`` on the stacked
    path) counts as the scope it wraps. A file location (``loc("<path>":
    line:col)``) is no scope: ``kernels/bmf_sample/`` is a directory."""
    names = re.findall(r'op_name="([^"]*)"', text)
    names += re.findall(r'loc\("([^"]*)"(?!:)', text)
    return {re.sub(r"^\w+\((.*)\)$", r"\1", c)
            for n in names for c in n.split("/")}


@pytest.mark.parametrize("batch", [None, 2], ids=["single", "stacked"])
@pytest.mark.parametrize("route", ["kernel", "fused"])
@pytest.mark.parametrize("stage", ["lowered", "compiled"])
def test_chain_carries_every_layer_scope(stage, route, batch):
    cfg = BMF.BMFConfig(K=4, **ROUTE_CFG[route])
    # NW hyperpriors on both sides, so the prior scope holds ops
    tc = GIBBS.trace_chain(cfg, 12, 10, 8, 8, 6, batch=batch,
                           u_prior=False, v_prior=False)
    lowered = tc.traced.lower()
    text = (lowered.as_text(debug_info=True) if stage == "lowered"
            else lowered.compile().as_text())
    found = _components(text)
    want = CHAIN_SCOPES + ROUTE_SCOPES[route]
    assert [s for s in want if s not in found] == []
    other = "fused" if route == "kernel" else "kernel"
    assert [s for s in ROUTE_SCOPES[other] if s in found] == []
