"""Share (%) of the row sampler's device time spent in a BMF Pallas kernel:
of the ops under the ``bmf_sample`` scope (see _scope.py), the union of
the intervals of those ``trace.is_kernel`` matches over the union of all
of them, summed over the chips the cell used. 0 when the sampler runs
XLA's Cholesky and solves; nothing when no op is under the scope."""
from bench import trace as TR
from bench.metrics._scope import _scopes_of


def read(r):
    devs = r.devices()
    if not devs or not any(d.ops for d in devs):
        return None
    names = _scopes_of(r)
    total = kernel = 0
    for d in devs:
        ops = [o for o in d.ops if "bmf_sample" in names[o.name]]
        total += TR.busy_ns(ops)
        kernel += TR.busy_ns([o for o in ops if TR.is_kernel(o)])
    if not total:
        return None
    return 100.0 * kernel / total
