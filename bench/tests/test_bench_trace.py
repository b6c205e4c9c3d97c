"""Trace reduction: busy time as a union of op intervals, idle share,
kernel and collective time by name, and reading a recorded trace."""
import glob

import jax
import jax.numpy as jnp

from bench import trace as TR
from bench.metrics._idle import idle_percent


def _op(name, s, e, stats=""):
    return TR.Op(name, s, e, f"{name} {stats}".lower())


class _R:
    def __init__(self, trace, info, n=1):
        self.trace, self.info, self.peaks, self.compile = trace, info, None, None
        self.n = n

    def devices(self):
        return self.trace.devices[:self.n]


def _trace():
    dev = TR.Device("/device:TPU:0", ops=[
        _op("fusion.1", 0, 100),
        _op("custom-call.3", 50, 250, "long_name=_sweep_kernel"),  # overlaps
        _op("all-gather.2", 300, 350),
        _op("copy.4", 600, 700),
    ])
    return TR.Trace(devices=[dev], host_spans=[("bench.dispatch", 340, 620)])


def test_busy_is_union_of_intervals():
    assert TR.union([(0, 100), (50, 250), (300, 350)]) == [(0, 250),
                                                           (300, 350)]
    assert TR.busy_ns(_trace().devices[0].ops) == 250 + 50 + 100


def test_kernel_and_collective_by_name():
    d = _trace().devices[0]
    assert TR.class_ns(d, TR.is_kernel) == 200
    assert TR.class_ns(d, TR.is_collective) == 50


def test_idle_share_and_gaps():
    tr = _trace()
    r = _R(tr, {"window_s": 1000e-9})
    assert abs(idle_percent(r) - 60.0) < 1e-9
    gaps = TR.idle_gaps(tr, 0, 1000)
    assert gaps[0] == ["host", 300e-9]            # 700..1000
    assert gaps[1] == ["bench.dispatch", 250e-9]  # 350..600


def test_metric_readers_on_synthetic_trace():
    from bench import spec
    tr = _trace()
    r = _R(tr, {"sweeps": 2, "window_s": 1e-6, "chips": 1})
    assert spec.metric_reader("factor_kernel_ms")(r) == 200 / 2 / 1e6
    assert spec.metric_reader("chain_other_ms")(r) == (400 - 200) / 2 / 1e6
    # no kernel op in the trace: the reader reports nothing
    empty = _R(TR.Trace([TR.Device("/device:TPU:0", ops=[
        _op("fusion.1", 0, 10)])], []), {"sweeps": 1})
    assert spec.metric_reader("factor_kernel_ms")(empty) is None


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tr = TR.load(str(tmp_path))
    assert [s[0] for s in tr.host_spans] == ["bench.window"]
    assert tr.devices == []              # no TPU plane on this host
