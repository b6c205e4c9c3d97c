"""Plain reference of one block's BPMF Gibbs chain, in jax.numpy.

Written from the model (Salakhutdinov & Mnih 2008; the paper's Eq. 1-4),
not from the program: no kernel, no padded CSR, no tiles. It is told the
chain's random stream, which is part of what the chain is:

  key            -> (k_init, key);  k_init -> (k_u, k_v);
                    U0 = 0.1 N(k_u, (N, K)), V0 = 0.1 N(k_v, (D, K))
  each sweep     key -> (key, k_hyper_u, k_hyper_v, k_u, k_v)
                 both priors are drawn from the factors as the sweep
                 starts; then U | V, prior_u with noise N(k_u, (N, K));
                 then V | U, prior_v with noise N(k_v, (D, K))
  NW hyperprior  default (mu0 = 0, beta0 = 2, W0 = I, nu0 = K);
                 (k_w, k_m) = split(k); Wishart by Bartlett with
                 (k_gamma, k_normal) = split(k_w); mu by
                 N(mu_n, (beta_n Lambda)^-1) from k_m
  row draw       x = Lambda^-1 eta + L^-T z with L L^T = Lambda + 1e-6 I

After burn-in each sweep adds to the accumulators: predictions on the
test entries, sum of U, sum of U U^T per row, and the same for V.

The sufficient statistics are summed from the ratings themselves: rows are
grouped by degree class (power-of-two widths), each class gathers its
ratings' factor rows into a dense (rows, width, K) tile, and one matmul
per tile forms sum v v^T and sum r v. ``precision`` sets those matmuls
and every other product: "highest" is the reference; "bf16" rounds the
gathered factor rows to bfloat16 before the statistics (f32 accumulate),
the lower-precision control.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
JITTER = 1e-6
TILE_BYTES = 64 << 20


class Prior(NamedTuple):
    eta: jnp.ndarray      # (n, K)
    Lambda: jnp.ndarray   # (n, K, K)


class Side(NamedTuple):
    """Ratings of one side grouped by degree class: a tuple of
    (row ids (C, R), col ids (C, R, W), values (C, R, W), mask (C, R, W))
    per class; padded rows carry row id n (dropped)."""
    classes: tuple
    n: int


def group(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n: int,
          K: int) -> Side:
    """Host-side grouping of COO ratings by row into degree classes."""
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]
    deg = np.bincount(rows, minlength=n)
    start = np.concatenate([[0], np.cumsum(deg)])
    width = np.maximum(1, 2 ** np.ceil(np.log2(np.maximum(deg, 1)))).astype(
        np.int64)
    classes = []
    for w in np.unique(width):
        ids = np.nonzero((width == w) & (deg > 0))[0]
        if len(ids) == 0:
            continue
        R = int(max(8, TILE_BYTES // (w * K * 4)))
        R = min(R, int(-(-len(ids) // 8) * 8))
        C = -(-len(ids) // R)
        rid = np.full(C * R, n, np.int64)
        rid[:len(ids)] = ids
        slot = np.arange(w)[None, :]
        d = np.where(rid < n, deg[np.minimum(rid, n - 1)], 0)[:, None]
        pos = np.where(rid < n, start[np.minimum(rid, n - 1)], 0)[:, None] \
            + np.minimum(slot, np.maximum(d - 1, 0))
        m = (slot < d)
        ci = np.where(m, cols[pos], 0).astype(np.int32)
        cv = np.where(m, vals[pos], 0.0).astype(np.float32)
        classes.append((jnp.asarray(rid.reshape(C, R).astype(np.int32)),
                        jnp.asarray(ci.reshape(C, R, w)),
                        jnp.asarray(cv.reshape(C, R, w)),
                        jnp.asarray(m.reshape(C, R, w).astype(np.float32))))
    return Side(classes=tuple(classes), n=n)


def _stats_class(other, rid, ci, cv, cm, n, precision):
    K = other.shape[-1]

    def tile(args):
        r, c, v, m = args
        g = other[c] * m[..., None]                          # (R, W, K)
        if precision == "bf16":
            g = g.astype(jnp.bfloat16).astype(jnp.float32)
        lam = jnp.einsum("rwk,rwl->rkl", g, g, precision=HI)
        eta = jnp.einsum("rw,rwk->rk", v, g, precision=HI)
        return lam, eta

    lam, eta = jax.lax.map(tile, (rid, ci, cv, cm))
    rid = rid.reshape(-1)
    Lam = jnp.zeros((n + 1, K, K), jnp.float32).at[rid].add(
        lam.reshape(-1, K, K))
    Eta = jnp.zeros((n + 1, K), jnp.float32).at[rid].add(eta.reshape(-1, K))
    return Lam[:n], Eta[:n]


def stats(side: Side, other, tau, precision):
    K = other.shape[-1]
    Lam = jnp.zeros((side.n, K, K), jnp.float32)
    Eta = jnp.zeros((side.n, K), jnp.float32)
    for rid, ci, cv, cm in side.classes:
        lam, eta = _stats_fn(other, rid, ci, cv, cm, side.n, precision)
        Lam, Eta = Lam + lam, Eta + eta
    return tau * Lam, tau * Eta


_stats_fn = jax.jit(_stats_class, static_argnames=("n", "precision"))


def draw_rows(key, eta, Lam):
    """x ~ N(Lam^-1 eta, Lam^-1), one row each."""
    n, K = eta.shape
    z = jax.random.normal(key, (n, K), jnp.float32)
    L = jnp.linalg.cholesky(Lam + JITTER * jnp.eye(K))
    mu = jax.scipy.linalg.cho_solve((L, True), eta[..., None])[..., 0]
    dx = jax.scipy.linalg.solve_triangular(jnp.swapaxes(L, -1, -2),
                                           z[..., None], lower=False)[..., 0]
    return mu + dx


def _inv_spd(A):
    K = A.shape[-1]
    L = jnp.linalg.cholesky(A)
    return jax.scipy.linalg.cho_solve((L, True), jnp.eye(K, dtype=A.dtype))


def nw_draw(key, X):
    """(mu, Lambda) from the Normal-Wishart posterior given rows X, under
    the default prior mu0 = 0, beta0 = 2, W0 = I, nu0 = K."""
    N, K = X.shape
    beta0, nu0 = 2.0, float(K)
    xbar = X.mean(0)
    Xc = X - xbar
    S = jnp.matmul(Xc.T, Xc, precision=HI)
    beta_n, nu_n = beta0 + N, nu0 + N
    mu_n = (N * xbar) / beta_n
    Wn = _inv_spd(jnp.eye(K) + S + (beta0 * N / beta_n)
                  * jnp.outer(xbar, xbar))
    kw, km = jax.random.split(key)
    kg, kn = jax.random.split(kw)
    df = (nu_n - jnp.arange(K, dtype=jnp.float32)) / 2.0
    A = jnp.diag(jnp.sqrt(2.0 * jax.random.gamma(kg, df))) + \
        jnp.tril(jax.random.normal(kn, (K, K)), -1)
    LA = jnp.matmul(jnp.linalg.cholesky(Wn + JITTER * jnp.eye(K)), A,
                    precision=HI)
    Lam = jnp.matmul(LA, LA.T, precision=HI)
    L = jnp.linalg.cholesky(beta_n * Lam + JITTER * jnp.eye(K))
    z = jax.random.normal(km, (K,))
    mu = mu_n + jax.scipy.linalg.solve_triangular(L.T, z, lower=False)
    return mu, Lam


class Chain(NamedTuple):
    U: jnp.ndarray
    V: jnp.ndarray
    pred_sum: jnp.ndarray
    U_sum: jnp.ndarray
    U_outer: jnp.ndarray
    V_sum: jnp.ndarray
    V_outer: jnp.ndarray
    count: float


def _nw_prior(key, X):
    mu, Lam = nw_draw(key, X)
    n, K = X.shape
    return (jnp.broadcast_to(jnp.matmul(Lam, mu, precision=HI), (n, K)),
            jnp.broadcast_to(Lam, (n, K, K)))


_nw_prior_jit = jax.jit(_nw_prior)
_draw_jit = jax.jit(draw_rows)


@jax.jit
def _accumulate(acc, U, V, tr, tc):
    pred = jnp.sum(U[tr] * V[tc], axis=-1)
    p, us, uo, vs, vo = acc
    return (p + pred, us + U, uo + U[:, :, None] * U[:, None, :],
            vs + V, vo + V[:, :, None] * V[:, None, :])


def chain(key, rows_side: Side, cols_side: Side, test_rows, test_cols, K: int,
          tau: float, n_sweeps: int, burnin: int,
          U_prior: Optional[Prior] = None, V_prior: Optional[Prior] = None,
          precision: str = "highest") -> Chain:
    """Run ``n_sweeps`` sweeps (the first ``burnin`` not kept) of one
    block's chain. ``U_prior``/``V_prior`` fixed per-row priors, or None
    for the NW hyperprior drawn every sweep."""
    N, D = rows_side.n, cols_side.n
    k0, key = jax.random.split(key)
    ku0, kv0 = jax.random.split(k0)
    U = 0.1 * jax.random.normal(ku0, (N, K), jnp.float32)
    V = 0.1 * jax.random.normal(kv0, (D, K), jnp.float32)
    tr, tc = jnp.asarray(test_rows), jnp.asarray(test_cols)
    acc = (jnp.zeros(tr.shape, jnp.float32), jnp.zeros((N, K)),
           jnp.zeros((N, K, K)), jnp.zeros((D, K)), jnp.zeros((D, K, K)))
    count = 0
    for i in range(n_sweeps):
        key, kh1, kh2, ku, kv = jax.random.split(key, 5)
        pu = U_prior if U_prior is not None else _nw_prior_jit(kh1, U)
        pv = V_prior if V_prior is not None else _nw_prior_jit(kh2, V)
        Lam, eta = stats(rows_side, V, tau, precision)
        U = _draw_jit(ku, pu[0] + eta, pu[1] + Lam)
        Lam, eta = stats(cols_side, U, tau, precision)
        V = _draw_jit(kv, pv[0] + eta, pv[1] + Lam)
        if i >= burnin:
            acc = _accumulate(acc, U, V, tr, tc)
            count += 1
    return Chain(U, V, *acc, count=float(count))


def summary_cov(sum_, outer, count, ridge=1e-4):
    """Per-row posterior covariance the chain summarizes, with the
    row-scaled ridge (numpy float64)."""
    mean = np.asarray(sum_, np.float64) / count
    cov = np.asarray(outer, np.float64) / count - \
        np.einsum("nk,nl->nkl", mean, mean)
    K = mean.shape[-1]
    mag = np.max(np.abs(np.diagonal(cov, axis1=-2, axis2=-1)), axis=-1)
    return cov + (ridge * np.maximum(mag, 1.0))[:, None, None] * np.eye(K)
