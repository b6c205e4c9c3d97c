"""Device time per sweep of the XLA row sampler on both sides (jitter add,
batched Cholesky, solves, L^-T z): ops under the ``bmf_sample`` scope (see
_scope.py)."""
from bench.metrics._scope import scope_ms


def read(r):
    return scope_ms(r, "bmf_sample")
