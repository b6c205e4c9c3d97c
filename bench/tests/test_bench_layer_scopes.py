"""The readers of the phase-a chain's own layers, ``prior_ms`` (the NW
hyperprior, scope ``bmf_prior``) and ``sweep_layout_ms`` (the one-pass
sweep kernel's operand padding, scope ``bmf_sweep_layout``): synthetic
trace events matched to a synthetic optimized HLO module, and the HLO of
a phase-a chain compiled on the host."""
import pytest

from bench import spec
from bench.metrics import _scope
from bench.tests.test_bench_scopes import _R, _module, _op

ROOT = "jit(_run_gibbs_dispatch)"
BODY = f"{ROOT}/while/body"
READERS = ("prior_ms", "sweep_layout_ms")

# instruction name, result shape, opcode, op_name path
CHAIN = [
    ("fusion.20", "f32[10,10]", "fusion",
     f"{BODY}/bmf_prior/dot_general"),
    ("custom-call.21", "f32[10,10]{1,0:T(8,128)}", "custom-call",
     f"{BODY}/bmf_prior/jit(cholesky)/cholesky"),
    ("fusion.22", "f32[64,128,128]", "fusion",
     f"{BODY}/bmf_u_step/bmf_sweep/bmf_sweep_layout/jit(_pad)/pad"),
    ("closed_call.23", "f32[64,128]", "fusion",
     f"{BODY}/bmf_u_step/bmf_sweep/closed_call/pallas_call"),
    ("fusion.24", "f32[16,128,128]", "fusion",
     f"{BODY}/bmf_v_step/bmf_sweep/bmf_sweep_layout/add"),
    ("closed_call.25", "f32[16,128]", "fusion",
     f"{BODY}/bmf_v_step/bmf_sweep/closed_call/pallas_call"),
    ("fusion.26", "f32[64,10,10]", "fusion", f"{BODY}/bmf_accumulate/add"),
]


def _ev(name, s, e):
    row = next(r for r in CHAIN if r[0] == name)
    return _op(name, s, e, row[1], row[2])


@pytest.fixture()
def chain_hlo(monkeypatch):
    texts = [_module(CHAIN)]
    monkeypatch.setattr(_scope, "live_hlo_texts", lambda: texts)
    return texts


def _sweep_ops():
    """One phase-a sweep: the hyperprior on both sides, then each side's
    layout before its kernel, then the accumulators."""
    return [_ev("fusion.20", 0, 20), _ev("custom-call.21", 20, 30),
            _ev("fusion.20", 30, 45), _ev("fusion.22", 45, 100),
            _ev("closed_call.23", 100, 400), _ev("fusion.24", 400, 420),
            _ev("closed_call.25", 420, 500), _ev("fusion.26", 500, 510)]


def test_readers_normalise_per_sweep(chain_hlo):
    r = _R(_sweep_ops(), {"sweeps": 3, "calls": 1})
    got = {m: spec.metric_reader(m)(r) for m in READERS}
    assert got["prior_ms"] == pytest.approx(45 / 3 / 1e6)
    # both sides' padding, not the kernels it feeds
    assert got["sweep_layout_ms"] == pytest.approx((55 + 20) / 3 / 1e6)


def test_layout_is_part_of_its_factor_step(chain_hlo):
    r = _R(_sweep_ops(), {"sweeps": 1, "calls": 1})
    assert spec.metric_reader("u_step_ms")(r) == pytest.approx(355 / 1e6)
    assert spec.metric_reader("v_step_ms")(r) == pytest.approx(100 / 1e6)


def test_nothing_where_no_op_carries_the_scope(chain_hlo):
    # fixed priors and the precision-kernel route: neither layer runs
    r = _R([_ev("closed_call.23", 0, 300), _ev("fusion.26", 300, 310)],
           {"sweeps": 3, "calls": 1})
    assert [spec.metric_reader(m)(r) for m in READERS] == [None, None]


def test_nothing_from_a_program_without_the_scopes(monkeypatch):
    plain = [(n, s, o, p.replace("bmf_prior", "x_prior").replace(
        "bmf_sweep_layout", "x_layout")) for n, s, o, p in CHAIN]
    monkeypatch.setattr(_scope, "live_hlo_texts", lambda: [_module(plain)])
    r = _R(_sweep_ops(), {"sweeps": 1, "calls": 1})
    assert [spec.metric_reader(m)(r) for m in READERS] == [None, None]


def test_paths_of_a_phase_a_chain_compiled_on_the_host():
    """The optimized HLO of a fused-sweep chain with the NW hyperprior on
    both sides gives the scope paths the readers look up."""
    from repro.core import bmf as BMF
    from repro.core import gibbs as GIBBS
    tc = GIBBS.trace_chain(BMF.BMFConfig(K=4, sweep_fused=True), 12, 10, 8,
                           8, 6, u_prior=False, v_prior=False)
    text = tc.traced.lower().compile().as_text()
    paths = _scope.hlo_paths([text]).values()
    found = set().union(*map(_scope.components, paths))
    assert {"bmf_prior", "bmf_sweep_layout"} <= found
    # the layout runs inside the sweep, inside a factor step
    assert all({"bmf_sweep"} <= _scope.components(p)
               and ({"bmf_u_step", "bmf_v_step"} & _scope.components(p))
               for p in paths if "bmf_sweep_layout" in _scope.components(p))
