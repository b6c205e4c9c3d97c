"""What the chain runners share: the block's inputs, the timed window of
back-to-back chain calls, and the comparison with the plain reference.

A chain call is the unit of work every PP executor dispatches: one
block's Gibbs chain of ``sweeps`` sweeps (``burnin`` of them not kept).
The window calls it back to back on the same block, each call with its
own key, until ``--seconds`` have passed and the call in flight is done.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import roofline
from bench.gen import ratings
from bench.reference import gibbs as REF

# rows of the summarized posterior compared, per side, drawn from the seed
SUMMARY_ROWS = 256


@dataclass
class BlockInputs:
    blk: ratings.Block
    csr_rows: object          # PaddedCSR (program input)
    csr_cols: object
    test_rows: jnp.ndarray
    test_cols: jnp.ndarray
    U_prior: object           # RowGaussians or None
    V_prior: object


def seed32(seed: int, tag: int = 0) -> int:
    return int(np.random.SeedSequence([int(seed), tag]).generate_state(1)[0])


def shape_of(config: dict, shrink: int = 1) -> dict:
    """The deployment's generator shape; ``shrink`` > 1 only in tests."""
    s = dict(config["generator"])
    s.update(n_rows=config["n_rows"] // shrink, n_cols=config["n_cols"]
             // shrink, nnz=config["nnz"] // (shrink * shrink),
             scale_lo=config["scale_lo"], scale_hi=config["scale_hi"])
    return s


def chain_config(config: dict, traffic: dict):
    """The program's chain configuration: the config file's ``chain`` keys
    (the route: ``use_kernel``, ``sweep_fused``, ...), K, and the traffic's
    chain length."""
    from repro.core import bmf as BMF
    return BMF.BMFConfig(K=config["K"], n_samples=traffic["sweeps"],
                         burnin=traffic["burnin"], **config["chain"])


def fixed_prior(key, deg: np.ndarray, K: int):
    """PD per-row Gaussian priors standing in for propagated posteriors:
    Lambda_n = s_n (I + G_n G_n^T / K), s_n growing with the row's degree,
    mean ~ N(0, 0.3^2); made on the device in one call."""
    from repro.core.posterior import RowGaussians
    return RowGaussians(*_fixed_prior_fn(key, jnp.asarray(deg, jnp.float32),
                                         K))


def _fixed_prior_impl(key, deg, K):
    n = deg.shape[0]
    kg, km = jax.random.split(key)
    G = jax.random.normal(kg, (n, K, K), jnp.float32)
    s = 0.5 + 0.05 * deg
    Lam = s[:, None, None] * (jnp.eye(K) + jnp.einsum(
        "nik,njk->nij", G, G, precision=jax.lax.Precision.HIGHEST) / K)
    mu = 0.3 * jax.random.normal(km, (n, K), jnp.float32)
    eta = jnp.einsum("nij,nj->ni", Lam, mu,
                     precision=jax.lax.Precision.HIGHEST)
    return eta, Lam


_fixed_prior_fn = jax.jit(_fixed_prior_impl, static_argnames="K")


def draw(config: dict, traffic: dict, seed: int,
         shrink: int = 1) -> ratings.Block:
    """The traffic's block, its pattern from the traffic's
    ``pattern_seed``, its ratings from ``seed``."""
    return ratings.block(shape_of(config, shrink), config["grid"],
                         tuple(traffic["block"]), seed, config["test_frac"],
                         traffic["pattern_seed"])


def inputs(blk: ratings.Block, config: dict, traffic: dict, ij,
           seed: int) -> BlockInputs:
    """Put the program's inputs for block ``blk`` on the device."""
    from repro.data.sparse import COO, coo_to_padded_csr
    coo = COO(blk.rows, blk.cols, blk.vals, blk.n_rows, blk.n_cols)
    csr_rows = coo_to_padded_csr(coo)
    csr_cols = coo_to_padded_csr(coo.transpose())
    tr, tc = blk.test_rows, blk.test_cols
    U_prior = V_prior = None
    if traffic["priors"] == "fixed":
        K = config["K"]
        kp = jax.random.key(seed32(seed, 11 + 13 * ij[0] + ij[1]))
        ku, kv = jax.random.split(kp)
        U_prior = fixed_prior(ku, np.bincount(blk.rows, minlength=blk.n_rows),
                              K)
        V_prior = fixed_prior(kv, np.bincount(blk.cols, minlength=blk.n_cols),
                              K)
    return BlockInputs(blk=blk, csr_rows=csr_rows, csr_cols=csr_cols,
                       test_rows=jnp.asarray(tr, jnp.int32),
                       test_cols=jnp.asarray(tc, jnp.int32),
                       U_prior=U_prior, V_prior=V_prior)


def max_degrees(blk: ratings.Block):
    return (int(np.bincount(blk.rows).max()), int(np.bincount(blk.cols).max()))


def window(seconds: float, call, keep_index: int, log):
    """Call ``call(i)`` back to back until ``seconds`` have passed and the
    call in flight has finished. Returns (calls, wall seconds from the
    first dispatch to the last completion, output of call ``keep_index``
    or of the last call if fewer ran, its index, calls whose chain-health
    flag was false)."""
    kept, kept_i, n, bad = None, -1, 0, 0
    t0 = time.monotonic()
    while True:
        with jax.profiler.TraceAnnotation("bench.chain_call"):
            out = call(n)
            jax.block_until_ready(out)
        health = getattr(out, "health", None)
        if health is not None and not bool(np.all(np.asarray(health))):
            bad += 1
        if n <= keep_index:
            kept, kept_i = out, n
        del out
        n += 1
        if time.monotonic() - t0 >= seconds:
            break
    wall = time.monotonic() - t0
    log(f"window: {n} call(s) in {wall:.3f}s")
    return n, wall, kept, kept_i, bad


def work_per_sweep(blocks, K: int, traffic: dict):
    """(flops, bytes) of one sweep over all ``blocks``."""
    kept = (traffic["sweeps"] - traffic["burnin"]) / traffic["sweeps"]
    f = b = 0.0
    for blk in blocks:
        fi, bi = roofline.sweep_work(blk.n_rows, blk.n_cols, blk.nnz,
                                     len(blk.test_rows), K,
                                     traffic["priors"] == "fixed", kept)
        f, b = f + fi, b + bi
    return f, b


def reference(inp: BlockInputs, key, config: dict, traffic: dict,
              precision: str = "highest"):
    """The plain reference chain on the same block, key and priors."""
    blk, K = inp.blk, config["K"]
    rows = REF.group(blk.rows, blk.cols, blk.vals, blk.n_rows, K)
    cols = REF.group(blk.cols, blk.rows, blk.vals, blk.n_cols, K)
    pr = lambda p: None if p is None else REF.Prior(p.eta, p.Lambda)
    tr, tc = blk.test_rows, blk.test_cols
    with jax.default_matmul_precision("highest"):
        return REF.chain(key, rows, cols, tr, tc, K, config["chain"]["tau"],
                         traffic["sweeps"], traffic["burnin"],
                         pr(inp.U_prior), pr(inp.V_prior), precision)


def _rel(p, r) -> float:
    """Largest element gap over the leaf's largest magnitude."""
    p = jnp.asarray(p, jnp.float32)
    r = jnp.asarray(r, jnp.float32)
    den = float(jnp.max(jnp.abs(r)))
    num = float(jnp.max(jnp.abs(p - r)))
    if not np.isfinite(num):
        return float("inf")
    return num / max(den, 1e-30)


def compare(res, ref: REF.Chain, n_test: int, seed: int, prefix: str = ""):
    """Per-leaf gaps of one program chain result against the reference.
    ``res`` is a GibbsResult for one block, trimmed to the block."""
    acc = res.acc
    N, D = ref.U.shape[0], ref.V.shape[0]
    gaps = {
        "U": _rel(res.U[:N], ref.U),
        "V": _rel(res.V[:D], ref.V),
        "pred": _rel(acc.pred_sum[:n_test], ref.pred_sum[:n_test]),
        "U_sum": _rel(acc.U_sum[:N], ref.U_sum),
        "U_outer": _rel(acc.U_outer[:N], ref.U_outer),
        "V_sum": _rel(acc.V_sum[:D], ref.V_sum),
        "V_outer": _rel(acc.V_outer[:D], ref.V_outer),
    }
    # the summarized posterior, on rows drawn from the seed: its precision
    # in the reference's whitened coordinates, L^T Lambda L - I with
    # L L^T the reference's summary covariance. With two kept samples the
    # covariance is rank one plus the ridge, so a plain relative gap is
    # swamped by f32 rounding along the ridge; whitened, every direction
    # counts in units of its own spread.
    rng = np.random.default_rng([int(seed), 5])
    for side, post, s, o, n in (("U", res.U_post, ref.U_sum, ref.U_outer, N),
                                ("V", res.V_post, ref.V_sum, ref.V_outer, D)):
        rows = np.sort(rng.choice(n, size=min(SUMMARY_ROWS, n),
                                  replace=False))
        lam = np.asarray(post.Lambda[rows], np.float64)
        cov_r = REF.summary_cov(np.asarray(s)[rows], np.asarray(o)[rows],
                                ref.count)
        L = np.linalg.cholesky(cov_r)
        W = np.swapaxes(L, -1, -2) @ lam @ L - np.eye(L.shape[-1])
        gap = np.max(np.abs(W))
        gaps[f"{side}_summary"] = float(gap) if np.isfinite(gap) else \
            float("inf")
    return {prefix + k: v for k, v in gaps.items()}


# the numbers ``correct`` compares, each the worst gap over its leaves
NUMBERS = {
    "state": ("U", "V", "pred", "U_sum", "U_outer", "V_sum", "V_outer"),
    "summary": ("U_summary", "V_summary"),
}


def verdict(gaps: dict, limits: dict):
    """(checks, correct): each number of NUMBERS against its limit."""
    checks = []
    for num, leaves in NUMBERS.items():
        v = max(g for k, g in gaps.items() if k.split(".")[-1] in leaves)
        checks.append((num, v, limits[num]))
    return checks, all(np.isfinite(v) and v <= lim for _, v, lim in checks)


class _Acc(NamedTuple):
    pred_sum: jnp.ndarray
    U_sum: jnp.ndarray
    U_outer: jnp.ndarray
    V_sum: jnp.ndarray
    V_outer: jnp.ndarray


class _Post(NamedTuple):
    Lambda: object


class ControlResult(NamedTuple):
    """A reference chain shaped like the program's result, so a control
    computed by the reference can stand in the program's place."""
    U: jnp.ndarray
    V: jnp.ndarray
    acc: _Acc
    U_post: _Post
    V_post: _Post


class _LazyPrecision:
    """Summary precision of the rows ``compare`` asks for (f64, host)."""

    def __init__(self, s, o, count):
        self.s, self.o, self.count = s, o, count

    def __getitem__(self, rows):
        cov = REF.summary_cov(np.asarray(self.s)[rows],
                              np.asarray(self.o)[rows], self.count)
        return np.linalg.inv(cov)


def as_result(ch: REF.Chain) -> ControlResult:
    return ControlResult(
        U=ch.U, V=ch.V,
        acc=_Acc(ch.pred_sum, ch.U_sum, ch.U_outer, ch.V_sum, ch.V_outer),
        U_post=_Post(_LazyPrecision(ch.U_sum, ch.U_outer, ch.count)),
        V_post=_Post(_LazyPrecision(ch.V_sum, ch.V_outer, ch.count)))
