"""Device time per sweep of the chain's priors on both sides: the NW
hyperprior draw (scatter matrix, Wishart and mean draws) and its broadcast
to rows in phase a, the fixed-prior select in phases b and c. Ops under
the ``bmf_prior`` scope (see _scope.py); nothing where the priors are
fixed and no op runs under it."""
from bench.metrics._scope import scope_ms


def read(r):
    return scope_ms(r, "bmf_prior")
