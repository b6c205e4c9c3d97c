"""Runner ``chain``: one block's Gibbs chain (``gibbs.run_gibbs``) called
back to back on one chip.

Traffic keys: ``block`` [i, j] of the configuration's grid,
``pattern_seed`` (which pairs are rated: the same for every run), ``sweeps``
and ``burnin`` per call, ``priors`` "nw" (phase a: the NW hyperprior
drawn every sweep on both sides) or "fixed" (phase b/c: per-row Gaussian
priors on both sides, made from the seed).

The planes are reused by every call, so nothing is donated.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import chainlib

WARM_KEY = 1 << 30     # fold-in index of the warm-up call, outside the window


def run(ctx):
    from repro.core import gibbs as GIBBS
    C, T = ctx.config, ctx.traffic
    ij = tuple(T["block"])
    blk = chainlib.draw(C, T, ctx.seed, ctx.shrink)
    inp = chainlib.inputs(blk, C, T, ij, ctx.seed)
    ctx.log(f"block {ij}: {blk.n_rows} x {blk.n_cols}, {blk.nnz} training "
            f"ratings, {len(blk.test_rows)} test; planes "
            f"{inp.csr_rows.idx.shape} / {inp.csr_cols.idx.shape}")
    cfg = chainlib.chain_config(C, T)
    base = jax.random.key(chainlib.seed32(ctx.seed))
    key = lambda i: jax.random.fold_in(base, i)

    if ctx.control == "reference_bf16":
        def call(i, c=None):
            return chainlib.as_result(chainlib.reference(
                inp, key(i), C, T, precision="bf16"))
    else:
        def call(i, c=cfg):
            return GIBBS.run_gibbs(key(i), inp.csr_rows, inp.csr_cols,
                                   inp.test_rows, inp.test_cols, c,
                                   U_prior=inp.U_prior, V_prior=inp.V_prior)

    # the chain length is a traced argument: a chain of no sweeps loads the
    # window's executable without running its sweeps
    jax.block_until_ready(call(WARM_KEY, cfg._replace(n_samples=0,
                                                      burnin=0)))
    ctx.setup_done()

    keep = int(np.random.default_rng([int(ctx.seed), 3]).integers(0, 2))
    with ctx.window():
        n, wall, kept, kept_i, failed = chainlib.window(ctx.seconds, call, keep,
                                                ctx.log)
    ctx.read_memory()
    inp.csr_rows = inp.csr_cols = None

    ref = chainlib.reference(inp, key(kept_i), C, T)
    gaps = chainlib.compare(kept, ref, len(blk.test_rows), ctx.seed)
    checks, ok = chainlib.verdict(gaps, ctx.limits)
    flops, bytes_ = chainlib.work_per_sweep([blk], C["K"], T)
    return dict(
        e2e={"train_ratings_per_s": n * T["sweeps"] * blk.nnz / wall},
        info={"kind": "chain", "calls": n, "sweeps": n * T["sweeps"],
              "window_s": wall, "flops_per_sweep": flops,
              "bytes_per_sweep": bytes_, "chips": 1},
        attempted=n, failed=failed, checks=checks, correct=ok,
        diagnostics={"compared_call": kept_i, **gaps})
