"""Device time per chain call of the summary handed to Posterior
Propagation (ridge, Cholesky, inverse) and the health check: ops under
the ``bmf_summarize`` scope (see _scope.py)."""
from bench.metrics._scope import scope_ms


def read(r):
    return scope_ms(r, "bmf_summarize", per="calls")
