"""Device busy time per sweep outside the BMF Pallas kernels (hyperprior,
prior broadcast, the XLA sampler, accumulators, predictions); mean over
the chips the cell used."""
from bench import trace as TR


def read(r):
    devs = r.devices()
    if not devs or not r.info.get("sweeps"):
        return None
    other = [TR.busy_ns(d.ops) - TR.class_ns(d, TR.is_kernel) for d in devs]
    return sum(other) / len(other) / r.info["sweeps"] / 1e6
