"""Device time per sweep of the one-pass sweep kernel's operand layout on
both sides: the CSR planes, the prior padded to the kernel's lane width
with its identity pad diagonal, eta and the noise, padded to tile shapes
before the kernel runs. Ops under the ``bmf_sweep_layout`` scope (see
_scope.py); nothing on a route without the sweep kernel."""
from bench.metrics._scope import scope_ms


def read(r):
    return scope_ms(r, "bmf_sweep_layout")
