"""The least time of one sweep's algorithmic work (bench/roofline.py:
flops over peak or bytes over HBM bandwidth, whichever binds), spread
over the chips the cell used, as a share of the device busy time per
sweep (mean over those chips)."""
from bench import roofline
from bench import trace as TR


def read(r):
    devs = r.devices()
    if not devs or r.peaks is None or not r.info.get("sweeps"):
        return None
    busy = sum(TR.busy_ns(d.ops) for d in devs) / len(devs) / 1e9
    if busy <= 0:
        return None
    chips = r.info["chips"]
    t, _ = roofline.least_time(r.info["flops_per_sweep"] / chips,
                               r.info["bytes_per_sweep"] / chips, r.peaks)
    return 100.0 * t / (busy / r.info["sweeps"])
