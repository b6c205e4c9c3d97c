"""Device time per sweep of the V-step, whatever sampler the chain's seam
holds: ops under the ``bmf_v_step`` scope (see _scope.py)."""
from bench.metrics._scope import scope_ms


def read(r):
    return scope_ms(r, "bmf_v_step")
