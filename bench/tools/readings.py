"""Readings of the numbers ``correct`` compares, for setting limits:
sound runs of the program over many seeds, and the lower-precision
controls, in one process on the chip.

  python3 bench/tools/readings.py --workload <cell> --seeds 1 2 3 \\
      [--controls program_bf16 reference_bf16] [--seconds 1]

Each run is a whole run of the cell (set-up, a short window, the
comparison); one JSON line per run goes to stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    from bench import run as RUN
    from bench import spec
    cell = spec.cell(args.workload, spec.benchmark())
    devices = RUN.require_chips(cell["chips"])
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    runs = [(s, None) for s in args.seeds] + [
        (s, c) for c in args.controls for s in args.seeds[:args.control_seeds]]
    for seed, control in runs:
        t0 = time.monotonic()
        res = RUN.run_cell(args.workload, seed, args.seconds, False,
                           devices, control=control, t0=t0)
        print(json.dumps({"seed": seed, "control": control,
                          "correct": res["correct"], "check": res["check"],
                          "diagnostics": res["diagnostics"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "run_s": time.monotonic() - t0}), flush=True)
        jax.clear_caches()

if __name__ == "__main__":
    main()
