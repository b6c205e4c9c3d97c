"""The least work of one Gibbs sweep, counted from shapes and the real
number of ratings, for ``sweep_roofline``.

What is counted is the algorithm, not an implementation: no lane padding
of K, no padding of rows to a block's largest degree. A later change that
fuses, splits or renames kernels leaves the count as it is.

FLOPs, per side of a sweep (U | V, then V | U):
  * rank-1 accumulation: 2 K^2 per rating for sum v v^T, 2 K for sum r v;
  * per row: adding the prior (K^2 + K), a K x K Cholesky (K^3 / 3) and
    three triangular solves (two for the mean, one for the noise: 3 K^2);
  * with the NW hyperprior: the scatter matrix (2 n K^2) and the K x K
    Wishart / mean draw (about 2 K^3).
Predictions on kept sweeps: 2 K per test entry.

Bytes, per side: each input read once and each output written once:
  * CSR column index and value, 8 B per rating;
  * the other factor (4 K per row of the other side);
  * the prior as the chain receives it: per-row (K^2 + K) floats when
    fixed, nothing per row when drawn from the hyperprior;
  * the noise draw and the sampled factor (4 K per row each);
  * on kept sweeps, the accumulators read and written once
    (2 x 4 (K^2 + K) per row) and the test predictions (12 B per entry).
"""
from __future__ import annotations

F32 = 4


def side_work(n: int, n_other: int, nnz: int, K: int, fixed_prior: bool):
    flops = nnz * (2 * K * K + 2 * K) + n * (K * K + K + K ** 3 / 3
                                              + 3 * K * K)
    if not fixed_prior:
        flops += 2 * n * K * K + 2 * K ** 3
    bytes_ = 8 * nnz + F32 * K * n_other + 2 * F32 * K * n
    if fixed_prior:
        bytes_ += F32 * (K * K + K) * n
    return flops, bytes_


def sweep_work(n_rows: int, n_cols: int, nnz: int, n_test: int, K: int,
               fixed_prior: bool, kept_share: float):
    """(flops, bytes) of one sweep of one block's chain, averaged over a
    chain whose ``kept_share`` of sweeps are kept."""
    fu, bu = side_work(n_rows, n_cols, nnz, K, fixed_prior)
    fv, bv = side_work(n_cols, n_rows, nnz, K, fixed_prior)
    keep_bytes = 2 * F32 * (K * K + K) * (n_rows + n_cols) + 12 * n_test
    return (fu + fv + kept_share * 2 * K * n_test,
            bu + bv + kept_share * keep_bytes)


def least_time(flops: float, bytes_: float, peaks: dict):
    """(seconds, bound) where bound names the binding limit."""
    t_f = flops / peaks["flops_per_s"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
