"""``correct`` of ``netflix-k100.chain`` against its committed limits, on the CPU at
1/8 of the deployment's rows and items: a sound run passes; the
lower-precision control fails; a run whose chain is broken underneath
fails (the look for a chip is skipped, the rest is a whole run)."""
import jax
import pytest

from bench.tests import correct_helpers as H

CELL = "netflix-k100.chain"


@pytest.fixture()
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(fresh):
    res = H.run(CELL)
    assert res["correct"] is True, res["check"]


@pytest.mark.parametrize("control", ["reference_bf16"])
def test_control_is_not_correct(control, fresh):
    res = H.run(CELL, control)
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "summary"])
def test_broken_chain_is_not_correct(fault, monkeypatch, fresh):
    H.break_chain(monkeypatch, fault)
    res = H.run(CELL)
    assert res["correct"] is False, (fault, res["check"])
