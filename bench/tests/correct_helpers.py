"""Shared by the ``correct`` tests: a whole run of a cell on the CPU at a
size a test run holds, and the faults planted under the timed path."""
import jax
import jax.numpy as jnp

from bench import run as RUN

SEED = 2 ** 31 + 11
SHRINK = 8


def run(cell, control=None, shrink=SHRINK):
    return RUN.run_cell(cell, SEED, 0.3, False, jax.devices(),
                        shrink=shrink, control=control)


def break_chain(monkeypatch, fault):
    """Break the chain body every chain executable runs:
    ``unchanged`` returns the state it was given, ``half`` leaves half the
    ratings out, ``altered`` alters one answer where it is made,
    ``summary`` summarizes the U side with a ridge ten times too wide."""
    from repro.core import gibbs as GIBBS
    from repro.data.sparse import PaddedCSR
    orig = GIBBS._run_gibbs_impl

    def broken(key, csr_rows, csr_cols, tr, tc, cfg, n_samples, burnin,
               *a, **k):
        if fault == "unchanged":
            n_samples = n_samples * 0
        if fault == "half":
            N = csr_rows.idx.shape[0]
            keep = (jnp.arange(N) < N // 2)[:, None]
            csr_rows = PaddedCSR(csr_rows.idx, csr_rows.val,
                                 csr_rows.mask * keep, csr_rows.n_cols)
        res = orig(key, csr_rows, csr_cols, tr, tc, cfg, n_samples, burnin,
                   *a, **k)
        if fault == "altered":
            res = res._replace(U=res.U.at[3, 1].add(0.05))
        if fault == "summary":
            acc = res.acc
            res = res._replace(U_post=GIBBS._summarize(
                acc.U_sum, acc.U_outer, jnp.maximum(acc.pred_cnt, 1.0),
                ridge=1e-3))
        return res

    monkeypatch.setattr(GIBBS, "_run_gibbs_impl", broken)
