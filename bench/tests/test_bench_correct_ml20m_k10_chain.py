"""``correct`` of ``ml20m-k10.chain`` against its committed limits, on the CPU at
1/8 of the deployment's rows and items: a sound run passes; the
lower-precision control fails; a run whose chain is broken underneath
fails, and so does one whose NW hyperprior sees its factors in bf16 (the
look for a chip is skipped, the rest is a whole run)."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests import correct_helpers as H

CELL = "ml20m-k10.chain"


@pytest.fixture()
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


def hyper_bf16(monkeypatch):
    """Round the factor rows that enter the NW posterior's scatter matrix
    to bfloat16: a TPU's default precision does as much to an f32 dot."""
    from repro.core import posterior as POST
    orig = POST.nw_posterior

    def rounded(prior, X):
        return orig(prior, X.astype(jnp.bfloat16).astype(X.dtype))

    monkeypatch.setattr(POST, "nw_posterior", rounded)


def test_sound_run_is_correct(fresh):
    res = H.run(CELL)
    assert res["correct"] is True, res["check"]


@pytest.mark.parametrize("control", ["reference_bf16"])
def test_control_is_not_correct(control, fresh):
    res = H.run(CELL, control)
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("fault", ["unchanged", "altered", "hyper_bf16"])
def test_broken_chain_is_not_correct(fault, monkeypatch, fresh):
    if fault == "hyper_bf16":
        hyper_bf16(monkeypatch)
    else:
        H.break_chain(monkeypatch, fault)
    res = H.run(CELL)
    assert res["correct"] is False, (fault, res["check"])
