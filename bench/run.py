#!/usr/bin/env python3
"""Chip benchmark of block-parallel BMF (Posterior Propagation) on TPU.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of this machine: it finds the
cell's configuration, traffic mix, limits and runner by name under
``bench/``, makes every input from ``--seed``, warms up (set-up), measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of stdout:

  {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
   "check"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by ``bench/metrics/<name>.py``. Each
number compared for ``correct`` is printed with its limit as the last
lines of stderr and under ``check``.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def require_chips(n: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU found ({e})") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r}); "
                     f"the benchmark runs on the chip only")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, found {len(devs)}")
    return devs


class Ctx:
    """What a runner gets: the cell's files, the run's arguments, and the
    hooks that mark set-up's end, the measured window and the memory
    reading."""

    def __init__(self, bench: dict, cell: dict, seed: int, seconds: float,
                 trace: bool, devices, shrink: int = 1, control=None,
                 t0: float = T_START, bench_dir: Path = spec.BENCH):
        self.cell = cell
        self.config = spec.config(cell["config"], bench_dir)
        self.traffic = spec.traffic(cell["traffic"], bench_dir)
        self.limits = spec.limits(cell["name"], bench_dir)
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.devices = list(devices)[:cell["chips"]]
        self.shrink, self.control, self.t0 = shrink, control, t0
        self.log = log
        self.setup_end = None
        self.memory_peak_bytes = None
        self.trace_dir = None
        self.compile = None
        self.window_compiles = 0
        self._stats = None

    def setup_done(self) -> None:
        self.setup_end = time.monotonic()
        if self._stats is not None:
            self.compile = self._stats.snapshot()

    @contextlib.contextmanager
    def window(self):
        import jax
        before = self._stats.snapshot()[1] if self._stats else 0
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            if self._stats:
                self.window_compiles = self._stats.snapshot()[1] - before

    def read_memory(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak_bytes = int(max(peaks)) if peaks else 0


class Readings:
    """What a per-layer metric reader gets."""

    def __init__(self, trace, info: dict, compile_stats, peaks: dict,
                 n_devices: int):
        self.trace, self.info, self.peaks = trace, info, peaks
        self.compile = compile_stats
        self.n_devices = n_devices

    def devices(self):
        """The trace's device planes of the chips the cell used."""
        return self.trace.devices[:self.n_devices] if self.trace else []


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             shrink: int = 1, control=None, bench: dict = None,
             t0: float = None, stats=None,
             bench_dir: Path = spec.BENCH) -> dict:
    """Run one cell and return the result object (without printing).
    ``shrink`` > 1 divides the deployment's rows and items (tests only);
    ``control`` runs the lower-precision control in the program's place."""
    from bench import peaks as PEAKS
    from bench import trace as TR
    bench = bench if bench is not None else spec.benchmark()
    cell = spec.cell(name, bench)
    ctx = Ctx(bench, cell, seed, seconds, trace, devices, shrink, control,
              T_START if t0 is None else t0, bench_dir)
    ctx._stats = stats
    out = spec.runner(ctx.traffic["runner"], bench_dir)(ctx)
    dev0 = ctx.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    if ctx.window_compiles:
        log(f"warning: {ctx.window_compiles} compile(s) inside the window")
    metrics, breakdown = {}, None
    if not trace:
        values = dict(out["e2e"], setup_s=ctx.setup_end - ctx.t0)
        for m in spec.end_to_end(bench, name):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = TR.load(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        used = tr.devices[:len(ctx.devices)]
        busy = sum(TR.busy_ns(d.ops) for d in used) / max(1, len(used)) / 1e9
        device["busy_s"] = busy
        device["window_s"] = out["info"]["window_s"]
        try:
            pk = PEAKS.peaks(dev0.device_kind)
        except KeyError:
            if dev0.platform == "tpu":
                raise
            pk = None                    # CPU test runs: no roofline
        rd = Readings(tr, out["info"], ctx.compile, pk, len(ctx.devices))
        for m in spec.per_layer(bench, name):
            v = spec.metric_reader(m["name"], bench_dir)(rd)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        spans = [s for s in tr.host_spans if s[0] == "bench.window"]
        lo, hi = ((spans[0][1], spans[0][2]) if spans else
                  (min((o.start for d in used for o in d.ops), default=0),
                   max((o.end for d in used for o in d.ops), default=0)))
        breakdown = {"device_ops": TR.top_ops(tr),
                     "idle_gaps": TR.idle_gaps(tr, lo, hi)}
    for k, v in out.get("diagnostics", {}).items():
        log(f"diagnostic {k} = {v}")
    if ctx.compile:
        log(f"set-up compile: {ctx.compile[1]} backend compile(s) in "
            f"{ctx.compile[0]:.3f}s, {ctx.compile[2]} persistent-cache hit(s)")
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["diagnostics"] = out.get("diagnostics", {})
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, v, lim in out["checks"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(args.workload, bench)
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    import jax
    from repro.launch.compile_cache import use_compile_cache
    from bench.compile_stats import CompileStats
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache}")
    stats = CompileStats()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, bench=bench, stats=stats)
    for k, c in result["check"].items():
        log(f"check {k} = {c['value']!r} limit {c['limit']!r} -> "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
