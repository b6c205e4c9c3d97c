"""Device time by the program's named scopes (bench/metrics/_scope.py) and
the five readers built on it: synthetic trace events matched to a
synthetic optimized HLO module, the HLO of a chain compiled on the host,
and a trace the test records on the host."""
import jax
import jax.numpy as jnp
import pytest

from bench import spec
from bench import trace as TR
from bench.metrics import _scope

ROOT = "jit(_run_gibbs_dispatch)"
BODY = f"{ROOT}/while/body"
READERS = ("u_step_ms", "v_step_ms", "sampler_ms", "accum_ms", "summary_ms")

# instruction name, result shape, opcode, op_name path (None: no metadata)
CHAIN = [
    ("while.50", "(s32[], f32[8,4])", "while", f"{ROOT}/while"),
    ("while.51", "(s32[], f32[2,8,4])", "while",
     f"{BODY}/bmf_u_step/bmf_stats/jit(precision_accum)/while"),
    ("closed_call.13", "(f32[2,8,4])", "fusion", f"{BODY}/bmf_u_step/"
     "bmf_stats/jit(precision_accum)/while/body/closed_call/pallas_call"),
    ("custom-call.67", "f32[16,4,4]{2,1,0:T(8,128)}", "custom-call",
     f"{BODY}/bmf_u_step/bmf_sample/jit(cholesky)/cholesky"),
    ("custom-call.72", "f32[8,4,4]{2,1,0:T(8,128)}", "custom-call",
     f"{BODY}/bmf_v_step/bmf_sample/jit(cholesky)/cholesky"),
    ("fusion.9", "f32[16,4,4]", "fusion", f"{BODY}/bmf_accumulate/add"),
    ("fusion.10", "f32[6]", "fusion",
     f"{BODY}/bmf_accumulate/bmf_predict/dot_general"),
    ("custom-call.37", "f32[16,4,4]{2,1,0:T(8,128)}", "custom-call",
     f"{ROOT}/bmf_summarize/jit(cholesky)/cholesky"),
    ("copy.3", "f32[16,4]", "copy", None),
    ("fusion.11", "f32[16,4]", "fusion", f"{BODY}/bmf_u_step/bmf_sampler/x"),
    ("fusion.12", "f32[16,4]", "fusion", f"{BODY}/xbmf_sample/add"),
    ("fusion.5", "f32[16,4,4]", "fusion",
     "jit(f)/vmap(bmf_summarize)/jit(cholesky)/cholesky"),
]


def _module(rows, header="HloModule jit__run_gibbs_dispatch"):
    lines = [header, "", "ENTRY %main {"]
    for name, shape, opcode, path in rows:
        meta = f', metadata={{op_name="{path}" stack_frame_id=1}}' \
            if path else ""
        lines.append(f"  %{name} = {shape} {opcode}(%p.1){meta}")
    return "\n".join(lines + ["}"])


def _op(name, s, e, shape="f32[16,4]", opcode="fusion"):
    # a trace event names its instruction with operand shapes, no metadata
    text = f"%{name} = {shape} {opcode}(f32[16,4] %p.1), kind=kLoop"
    return TR.Op(text, s, e, text.lower())


def _ev(name, s, e):
    row = next(r for r in CHAIN if r[0] == name)
    return _op(name, s, e, row[1], row[2])


class _R:
    def __init__(self, ops, info, n_devices=1):
        devs = ops if isinstance(ops[0], list) else [ops]
        self.trace = TR.Trace([TR.Device(f"/device:TPU:{i}", ops=o)
                               for i, o in enumerate(devs)], [])
        self.info, self.peaks, self.compile = info, None, None
        self.n = n_devices

    def devices(self):
        return self.trace.devices[:self.n]


@pytest.fixture()
def chain_hlo(monkeypatch):
    texts = [_module(CHAIN), _module(
        [("fusion.9", "u32[2]", "fusion", "jit(_normal)/add")],
        "HloModule jit__normal")]
    monkeypatch.setattr(_scope, "live_hlo_texts", lambda: texts)
    return texts


def _chain_ops():
    """One sweep loop (a container with no scope) around a U-step whose
    stats loop encloses its kernel, a V-step, the accumulators, and a
    summary after the loop."""
    return [_ev("while.50", 0, 1000), _ev("while.51", 0, 300),
            _ev("closed_call.13", 10, 290), _ev("custom-call.67", 300, 400),
            _ev("custom-call.72", 400, 450), _ev("fusion.9", 450, 480),
            _ev("fusion.10", 480, 490), _ev("custom-call.37", 1000, 1100),
            _ev("copy.3", 1100, 1110)]


def test_nested_events_under_one_scope_count_once(chain_hlo):
    r = _R(_chain_ops(), {"sweeps": 1, "calls": 1})
    # the stats loop (0..300) encloses its kernel (10..290); the sample
    # op follows: 300 + 100 ns, not 300 + 280 + 100
    assert _scope.scope_ms(r, "bmf_u_step") == 400 / 1e6
    assert _scope.scope_ms(r, "bmf_stats") == 300 / 1e6


def test_container_without_a_scope_is_not_counted(chain_hlo):
    r = _R(_chain_ops(), {"sweeps": 1, "calls": 1})
    # the sweep loop (0..1000) holds no bmf_ component
    assert _scope.scope_ms(r, "bmf_accumulate") == 40 / 1e6
    assert not any(c.startswith("bmf_") for c in _scope.components(
        CHAIN[0][3]))


def test_scope_matches_whole_components_only(chain_hlo):
    ops = [_ev("fusion.11", 0, 100), _ev("fusion.12", 100, 150)]
    assert _scope.scope_ms(_R(ops, {"sweeps": 1}), "bmf_sample") is None
    # a scope a transform wraps (the stacked chain's vmap) still counts
    wrapped = [_ev("fusion.5", 0, 70)]
    assert _scope.scope_ms(_R(wrapped, {"calls": 1}), "bmf_summarize",
                           per="calls") == 70 / 1e6


def test_event_matches_its_instruction_by_name_shape_and_opcode(chain_hlo):
    # fusion.9 of another module (u32[2]) is not the chain's fusion.9
    other = _op("fusion.9", 0, 50, "u32[2]", "fusion")
    assert _scope.scope_ms(_R([other], {"sweeps": 1}),
                           "bmf_accumulate") is None
    # one name, two paths in two scoped modules: attributed to neither
    paths = _scope.hlo_paths([
        _module([("fusion.1", "f32[4]", "fusion", "a/bmf_u_step/add")]),
        _module([("fusion.1", "f32[4]", "fusion", "a/bmf_v_step/add")])])
    assert paths == {}


def test_nothing_when_no_op_carries_the_scope(chain_hlo):
    r = _R([_ev("custom-call.67", 0, 10)], {"sweeps": 3, "calls": 1})
    for name in ("v_step_ms", "accum_ms", "summary_ms"):
        assert spec.metric_reader(name)(r) is None
    assert spec.metric_reader("u_step_ms")(r) == 10 / 3 / 1e6
    # no sweep ran: nothing to divide by
    assert spec.metric_reader("u_step_ms")(_R(
        [_ev("custom-call.67", 0, 10)], {"sweeps": 0})) is None


def test_nothing_from_a_program_without_scopes(monkeypatch):
    plain = [(n, s, o, p and p.replace("bmf_", "x_")) for n, s, o, p in CHAIN]
    monkeypatch.setattr(_scope, "live_hlo_texts", lambda: [_module(plain)])
    r = _R(_chain_ops(), {"sweeps": 1, "calls": 1})
    assert [spec.metric_reader(m)(r) for m in READERS] == [None] * 5


def test_readers_normalise_per_sweep_and_per_call(chain_hlo):
    r = _R(_chain_ops(), {"sweeps": 6, "calls": 2})
    got = {m: spec.metric_reader(m)(r) for m in READERS}
    assert got["u_step_ms"] == pytest.approx(400 / 6 / 1e6)
    assert got["v_step_ms"] == pytest.approx(50 / 6 / 1e6)
    assert got["sampler_ms"] == pytest.approx(150 / 6 / 1e6)
    assert got["accum_ms"] == pytest.approx(40 / 6 / 1e6)
    assert got["summary_ms"] == pytest.approx(100 / 2 / 1e6)


def test_mean_over_the_cells_chips(chain_hlo):
    a = [_ev("custom-call.72", 0, 100)]
    b = [_ev("custom-call.72", 0, 300)]
    assert spec.metric_reader("v_step_ms")(_R([a, b], {"sweeps": 1}, 2)) \
        == pytest.approx(200 / 1e6)
    # only the chips the cell used
    assert spec.metric_reader("v_step_ms")(_R([a, b], {"sweeps": 1}, 1)) \
        == pytest.approx(100 / 1e6)


def test_paths_of_a_chain_compiled_on_the_host():
    """The optimized HLO of a real chain gives the scope paths the readers
    look up."""
    from repro.core import bmf as BMF
    from repro.core import gibbs as GIBBS
    tc = GIBBS.trace_chain(BMF.BMFConfig(K=4, use_kernel=True), 12, 10, 8,
                           8, 6)
    text = tc.traced.lower().compile().as_text()
    found = set().union(*map(_scope.components,
                             _scope.hlo_paths([text]).values()))
    assert {"bmf_u_step", "bmf_v_step", "bmf_stats", "bmf_sample",
            "bmf_accumulate", "bmf_predict", "bmf_summarize"} <= found


def test_readers_on_a_recorded_host_trace(tmp_path):
    def f(x):
        with jax.named_scope("bmf_u_step"):
            return jnp.sin(x) @ x

    f = jax.jit(f)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = TR.load(str(tmp_path))
    # the live executables include f's, which holds the scope
    assert any("bmf_u_step" in t for t in _scope.live_hlo_texts())

    class R:
        trace, peaks, compile = tr, None, None
        info = {"sweeps": 1, "calls": 1}

        def devices(self):
            return tr.devices[:1]

    # the host trace holds no device op: every reader reports nothing
    assert [spec.metric_reader(m)(R()) for m in READERS] == [None] * 5
