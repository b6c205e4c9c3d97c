"""The ``sample_kernel_share`` reader on synthetic trace events matched to
a synthetic optimized HLO module (the helpers of test_bench_scopes)."""
import pytest

from bench import spec
from bench import trace as TR
from bench.metrics import _scope
from bench.tests.test_bench_scopes import BODY, _R, _module

SAMPLE = f"{BODY}/bmf_u_step/bmf_sample"
# instruction name, result shape, opcode, op_name path, custom-call target
HLO = [
    ("custom-call.67", "f32[16,4,4]{2,1,0:T(8,128)}", "custom-call",
     f"{SAMPLE}/jit(cholesky)/cholesky", "Cholesky"),
    ("fusion.20", "f32[8,1,8,128]", "fusion", f"{SAMPLE}/transpose", None),
    ("_run_gibbs_dispatch.3", "f32[1,8,128]", "custom-call",
     f"{SAMPLE}/pallas_call", "tpu_custom_call"),
    ("closed_call.13", "(f32[2,8,4])", "fusion",
     f"{BODY}/bmf_u_step/bmf_stats/closed_call/pallas_call", None),
]


def _ev(name, s, e):
    row = next(r for r in HLO if r[0] == name)
    text = f"%{name} = {row[1]} {row[2]}(f32[16,4] %p.1)"
    text += (f', custom_call_target="{row[4]}"' if row[4]
             else ", kind=kCustom" if name.startswith("closed_call")
             else ", kind=kLoop")
    return TR.Op(text, s, e, text.lower())


@pytest.fixture()
def sample_hlo(monkeypatch):
    texts = [_module([r[:4] for r in HLO])]
    monkeypatch.setattr(_scope, "live_hlo_texts", lambda: texts)


def _read(ops, n_devices=1):
    return spec.metric_reader("sample_kernel_share")(
        _R(ops, {"sweeps": 1}, n_devices))


def test_xla_sampler_reads_zero(sample_hlo):
    # the parent's sampler: Cholesky and solves, no kernel under the scope;
    # the precision kernel under bmf_stats is not the sampler's
    ops = [_ev("closed_call.13", 0, 500), _ev("custom-call.67", 500, 900),
           _ev("fusion.20", 900, 1000)]
    assert TR.is_kernel(ops[0]) and not TR.is_kernel(ops[1])
    assert _read(ops) == 0.0


def test_kernel_share_of_the_sampler(sample_hlo):
    ops = [_ev("closed_call.13", 0, 500), _ev("fusion.20", 500, 510),
           _ev("_run_gibbs_dispatch.3", 510, 600)]
    assert _read(ops) == pytest.approx(90.0)
    # two chips: kernel time over sampler time, summed over both
    other = [_ev("fusion.20", 0, 30), _ev("_run_gibbs_dispatch.3", 30, 100)]
    assert _read([ops, other], 2) == pytest.approx(100 * 160 / 200)
    assert _read([ops, other], 1) == pytest.approx(90.0)


def test_nothing_without_sampler_ops(sample_hlo, monkeypatch):
    assert _read([_ev("closed_call.13", 0, 500)]) is None
    plain = [(n, s, o, p.replace("bmf_", "x_")) for n, s, o, p, _ in HLO]
    monkeypatch.setattr(_scope, "live_hlo_texts", lambda: [_module(plain)])
    assert _read([_ev("_run_gibbs_dispatch.3", 0, 90)]) is None
