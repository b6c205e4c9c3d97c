"""The one-block rating generator against the full generator and
core.partition at a reduced size."""
import numpy as np
import pytest

from bench.gen import ratings
from repro.core.partition import partition
from repro.data import synthetic as SYN
from repro.data.sparse import train_test_split

SHAPE = dict(n_rows=6000, n_cols=1500, nnz=6000 * 60, scale_lo=1,
             scale_hi=5, row_exponent=0.7, col_exponent=0.6, true_rank=8,
             noise_std=0.35, oversample=1.6)


@pytest.mark.parametrize("ij", [(0, 0), (1, 1), (3, 2)])
def test_block_matches_partition_of_full_generator(ij):
    pre = SYN.DatasetPreset("t", 6000, 1500, 60, 1, 5, 8, 8)
    coo, _ = SYN.generate(pre, seed=3)
    train, _ = train_test_split(coo, 0.1, seed=4)
    full = partition(train, 4, 4).block(*ij).coo
    blk = ratings.block(SHAPE, (4, 4), ij, 3, 0.1)
    assert (blk.n_rows, blk.n_cols) == (full.n_rows, full.n_cols)
    # nnz within 3%, largest row / column degree within 15%
    assert abs(blk.nnz / full.nnz - 1) < 0.03
    for a, b in ((blk.rows, full.row), (blk.cols, full.col)):
        assert abs(np.bincount(a).max() / np.bincount(b).max() - 1) < 0.15
    assert len(blk.test_rows) == pytest.approx(0.1 / 0.9 * blk.nnz, rel=0.1)
    # occupancy order: descending training degree
    deg = np.bincount(blk.rows, minlength=blk.n_rows)
    assert (np.diff(deg) <= 0).all()
    assert set(np.unique(blk.vals)) <= {1.0, 2.0, 3.0, 4.0, 5.0}


def test_block_is_a_function_of_the_seed():
    a = ratings.block(SHAPE, (4, 4), (1, 1), 2 ** 31 + 5, 0.1)
    b = ratings.block(SHAPE, (4, 4), (1, 1), 2 ** 31 + 5, 0.1)
    c = ratings.block(SHAPE, (4, 4), (1, 1), 2 ** 31 + 6, 0.1)
    assert np.array_equal(a.rows, b.rows) and np.array_equal(a.vals, b.vals)
    assert not np.array_equal(a.rows, c.rows)


def test_fixed_pattern_gives_every_seed_the_same_sizes():
    a = ratings.block(SHAPE, (4, 4), (1, 1), 2 ** 31 + 5, 0.1, 17)
    b = ratings.block(SHAPE, (4, 4), (1, 1), 2 ** 32 + 9, 0.1, 17)
    for f in ("rows", "cols", "test_rows", "test_cols"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.vals, b.vals)
    c = ratings.block(SHAPE, (4, 4), (1, 1), 2 ** 31 + 5, 0.1, 18)
    assert not np.array_equal(a.rows, c.rows)
