"""Device idle share over the measured window: 1 - busy / window, busy
being the union of op intervals, averaged over the chips the cell used."""
from bench import trace as TR


def idle_percent(r):
    devs = r.devices()
    w = r.info.get("window_s")
    if not devs or not w:
        return None
    busy = sum(TR.busy_ns(d.ops) for d in devs) / len(devs) / 1e9
    return 100.0 * max(0.0, 1.0 - busy / w)
