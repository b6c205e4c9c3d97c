"""Device time per sweep in the BMF Pallas kernels (the one-pass sweep
kernel and the precision kernel), from the trace; mean over the chips the
cell used. Nothing when no kernel op is in the trace."""
from bench import trace as TR


def read(r):
    devs = r.devices()
    ns = [TR.class_ns(d, TR.is_kernel) for d in devs]
    if not devs or not any(ns) or not r.info.get("sweeps"):
        return None
    return sum(ns) / len(ns) / r.info["sweeps"] / 1e6
