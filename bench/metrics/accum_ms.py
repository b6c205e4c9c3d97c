"""Device time per sweep of the chain's accumulators (test predictions,
factor sums and (N, K, K) outer-product sums): ops under the
``bmf_accumulate`` scope (see _scope.py)."""
from bench.metrics._scope import scope_ms


def read(r):
    return scope_ms(r, "bmf_accumulate")
