"""One block of a Table-1 rating matrix, drawn from the seed without
drawing the rest of the matrix.

The full generator this copies (the repository's synthetic rating
generator, at full scale) draws ``oversample * nnz`` (row, col) pairs from
bounded power-law popularities (row rank^-a, column rank^-b), removes
duplicates, keeps ``nnz`` of the unique pairs uniformly, and gives each a
low-rank-plus-noise value clipped to the rating scale. An nnz-balanced
I x J partition then hands each block a row group and a column group.

Here only one block is drawn:

  * the block's row (column) group is the set of popularity ranks that the
    partition's balance permutation places in that group: ranks are
    spread by the same strided round robin the partition applies to
    rating counts, then split into contiguous groups;
  * pairs are drawn from the popularities restricted to those groups, as
    many as the full draw would land in the block in expectation;
  * duplicates are removed and each unique pair is kept with the full
    generator's keep fraction nnz / E[unique pairs], E taken over the
    whole matrix (computed on binned popularities);
  * values are low-rank plus noise, rounded to the integer scale;
  * ``test_frac`` of the ratings are held out;
  * rows and columns are relabelled by descending training count, as the
    partition's within-stripe occupancy sort does.

All of it is vectorized numpy. ``pattern_seed`` draws which pairs are
rated and held out; ``seed`` draws the values. A benchmark run fixes the
pattern, so that every seed gives the same sizes (the same executables,
the same work) with other ratings.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

ROUND_ROBIN_STRIDE = 64      # the partition's balance stride
BINS = 2048                  # popularity bins for E[unique pairs]


@dataclass
class Block:
    rows: np.ndarray         # training ratings, block-local ids
    cols: np.ndarray
    vals: np.ndarray
    test_rows: np.ndarray
    test_cols: np.ndarray
    test_vals: np.ndarray
    n_rows: int
    n_cols: int

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])


def round_robin_positions(n: int, stride: int = ROUND_ROBIN_STRIDE):
    """Position of the i-th heaviest row under the balance permutation."""
    return np.concatenate([np.arange(s, n, stride) for s in range(stride)])


def group_ranks(n: int, groups: int, g: int) -> np.ndarray:
    """Popularity ranks (0 = heaviest) that land in group ``g`` of
    ``groups`` contiguous groups after the balance permutation; ascending."""
    pos = round_robin_positions(n)
    splits = np.linspace(0, n, groups + 1).astype(np.int64)
    return np.nonzero((pos >= splits[g]) & (pos < splits[g + 1]))[0]


def popularity(n: int, exponent: float) -> np.ndarray:
    w = (np.arange(n) + 1.0) ** -exponent
    return w / w.sum()


def _binned(p: np.ndarray, bins: int = BINS):
    """(mean value, count) of ``bins`` contiguous groups of sorted p."""
    parts = np.array_split(np.sort(p), min(bins, len(p)))
    return (np.array([x.mean() for x in parts]),
            np.array([len(x) for x in parts], np.float64))


def expected_unique(p: np.ndarray, q: np.ndarray, draws: float) -> float:
    """E[# distinct (row, col) pairs] after ``draws`` independent draws
    from the product of popularities p, q (Poisson approximation)."""
    pv, pn = _binned(p)
    qv, qn = _binned(q)
    hit = -np.expm1(-draws * np.outer(pv, qv))
    return float(pn @ hit @ qn)


def block(shape: dict, grid, ij, seed: int, test_frac: float,
          pattern_seed: int = None) -> Block:
    """Draw block ``ij`` of an I x J partition of the deployment ``shape``
    (keys n_rows, n_cols, nnz, scale_lo, scale_hi and the generator's
    row_exponent, col_exponent, true_rank, noise_std, oversample). The
    pattern is drawn from ``pattern_seed`` (``seed`` when None)."""
    I, J = grid
    i, j = ij
    N, D, nnz = int(shape["n_rows"]), int(shape["n_cols"]), int(shape["nnz"])
    ps = int(seed) if pattern_seed is None else int(pattern_seed)
    rng = np.random.default_rng([ps, 7919 * i + j])
    rng_v = np.random.default_rng([int(seed), 7919 * i + j, 1])
    p = popularity(N, shape["row_exponent"])
    q = popularity(D, shape["col_exponent"])
    r_ranks, c_ranks = group_ranks(N, I, i), group_ranks(D, J, j)
    pb, qb = p[r_ranks], q[c_ranks]
    total_draws = shape["oversample"] * nnz
    keep = nnz / expected_unique(p, q, total_draws)
    n_draw = int(round(total_draws * pb.sum() * qb.sum()))

    rows = rng.choice(len(r_ranks), size=n_draw, p=pb / pb.sum())
    cols = rng.choice(len(c_ranks), size=n_draw, p=qb / qb.sum())
    _, uniq = np.unique(rows.astype(np.int64) * len(c_ranks) + cols,
                        return_index=True)
    uniq = rng.permutation(uniq)[:int(round(keep * len(uniq)))]
    rows, cols = rows[uniq], cols[uniq]

    lo, hi = float(shape["scale_lo"]), float(shape["scale_hi"])
    r = int(shape["true_rank"])
    Ut = rng_v.normal(0, 1, (len(r_ranks), r))
    Vt = rng_v.normal(0, 1, (len(c_ranks), r))
    raw = np.einsum("ek,ek->e", Ut[rows], Vt[cols]) / np.sqrt(r)
    mid, spread = 0.5 * (lo + hi), 0.5 * (hi - lo)
    vals = mid + spread * 0.5 * raw + \
        shape["noise_std"] * spread * rng_v.normal(size=len(rows))
    vals = np.clip(np.rint(vals), lo, hi).astype(np.float32)

    test = rng.random(len(rows)) < test_frac
    # occupancy relabel: descending training count, stable
    rc = np.bincount(rows[~test], minlength=len(r_ranks))
    cc = np.bincount(cols[~test], minlength=len(c_ranks))
    rlab = np.empty(len(rc), np.int64)
    rlab[np.argsort(-rc, kind="stable")] = np.arange(len(rc))
    clab = np.empty(len(cc), np.int64)
    clab[np.argsort(-cc, kind="stable")] = np.arange(len(cc))
    rows, cols = rlab[rows].astype(np.int32), clab[cols].astype(np.int32)
    return Block(rows=rows[~test], cols=cols[~test], vals=vals[~test],
                 test_rows=rows[test], test_cols=cols[test],
                 test_vals=vals[test], n_rows=len(r_ranks),
                 n_cols=len(c_ranks))
