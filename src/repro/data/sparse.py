"""Fixed-shape sparse rating-matrix containers for XLA.

``PaddedCSR`` stores, for each row, up to ``max_nnz`` (column, value) pairs
plus a mask — the TPU-friendly analogue of CSR (static shapes; the Gibbs
per-row conditionals become masked gathers + batched einsums). ``COO`` keeps
flat triplets for scatter-style updates (item-side statistics, test-set
evaluation).

Host-side construction uses numpy (data prep happens once, outside jit).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax.numpy as jnp


@dataclass
class COO:
    row: np.ndarray      # (nnz,) int32
    col: np.ndarray      # (nnz,) int32
    val: np.ndarray      # (nnz,) float32
    n_rows: int
    n_cols: int

    @property
    def nnz(self) -> int:
        return int(self.row.shape[0])

    def transpose(self) -> "COO":
        return COO(row=self.col, col=self.row, val=self.val,
                   n_rows=self.n_cols, n_cols=self.n_rows)

    def submatrix(self, row_ids: np.ndarray, col_ids: np.ndarray) -> "COO":
        """Extract block given *sorted* global id arrays; ids are relabeled
        to local [0, len) coordinates."""
        row_pos = -np.ones(self.n_rows, np.int64)
        row_pos[row_ids] = np.arange(len(row_ids))
        col_pos = -np.ones(self.n_cols, np.int64)
        col_pos[col_ids] = np.arange(len(col_ids))
        r = row_pos[self.row]
        c = col_pos[self.col]
        keep = (r >= 0) & (c >= 0)
        return COO(row=r[keep].astype(np.int32), col=c[keep].astype(np.int32),
                   val=self.val[keep], n_rows=len(row_ids), n_cols=len(col_ids))


@dataclass
class PaddedCSR:
    """Row-major padded sparse matrix (device arrays)."""
    idx: jnp.ndarray     # (N, M) int32 column ids (0 where padded)
    val: jnp.ndarray     # (N, M) f32
    mask: jnp.ndarray    # (N, M) f32 {0,1}
    n_cols: int

    @property
    def n_rows(self) -> int:
        return int(self.idx.shape[0])

    @property
    def max_nnz(self) -> int:
        return int(self.idx.shape[1])


def coo_to_padded_csr(coo: COO, max_nnz: Optional[int] = None,
                      pad_to_multiple: int = 8,
                      n_rows_pad: Optional[int] = None,
                      n_cols_pad: Optional[int] = None,
                      as_numpy: bool = False) -> PaddedCSR:
    """``n_rows_pad`` / ``n_cols_pad`` / ``max_nnz`` let callers bucket many
    matrices to ONE shape so a single jitted executable serves all blocks
    (the PP scheduler pads every block of a phase to common shapes).

    ``as_numpy=True`` keeps the planes on the host: the streaming executor
    assembles whole window chunks in numpy and ships each chunk with ONE
    async ``device_put`` instead of one transfer per plane."""
    order = np.argsort(coo.row, kind="stable")
    rows, cols, vals = coo.row[order], coo.col[order], coo.val[order]
    counts = np.bincount(rows, minlength=coo.n_rows)
    M = int(counts.max()) if counts.size and counts.max() > 0 else 1
    if max_nnz is not None:
        M = max_nnz   # bucket target: pad up to it, truncate rows beyond it
    M = max(1, ((M + pad_to_multiple - 1) // pad_to_multiple) * pad_to_multiple)
    NR = n_rows_pad if n_rows_pad is not None else coo.n_rows
    assert NR >= coo.n_rows

    idx = np.zeros((NR, M), np.int32)
    val = np.zeros((NR, M), np.float32)
    mask = np.zeros((NR, M), np.float32)
    # vectorized scatter fill: entry e (row-sorted) lands in slot
    # e - starts[row[e]]; slots >= M are truncated (rows beyond max_nnz)
    starts = np.concatenate([[0], np.cumsum(counts)])
    slot = np.arange(len(rows), dtype=np.int64) - starts[rows]
    keep = slot < M
    r_k, s_k = rows[keep], slot[keep]
    idx[r_k, s_k] = cols[keep]
    val[r_k, s_k] = vals[keep]
    mask[r_k, s_k] = 1.0
    n_cols = n_cols_pad if n_cols_pad is not None else coo.n_cols
    if as_numpy:
        return PaddedCSR(idx=idx, val=val, mask=mask, n_cols=n_cols)
    return PaddedCSR(idx=jnp.asarray(idx), val=jnp.asarray(val),
                     mask=jnp.asarray(mask), n_cols=n_cols)


def row_extent(mask):
    """Per-row live extent: the last unmasked slot + 1, 0 for an empty row
    — the plane the fused-gather kernels scalar-prefetch.  Their DMA pump
    copies slots [0, extent) of a row and none past it, and their grid
    skips an M-tile that starts at or past every extent of its row tile.
    CSR padding fills slots from the left, so the extent is the row's
    degree; a mask with holes (a masked slot before a live one) gets its
    last live slot + 1, and the pump copies the hole and masks it.

    mask: (N, M) (np or jnp; traceable).  Returns (N,) int32."""
    M = mask.shape[1]
    slot = jnp.arange(1, M + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(jnp.asarray(mask) != 0, slot, 0), axis=1)


def pump_copies(mask) -> int:
    """Row copies the kernels' DMA pump issues for one (N, M) plane: one
    per slot below each row's ``row_extent``, whatever the tiling — so on
    the left-filled planes of ``coo_to_padded_csr`` exactly one per live
    rating (``mask.sum()``), and more only for holes.  Host helper, for
    counting; not traceable."""
    return int(np.asarray(row_extent(mask), np.int64).sum())


def occupancy_rank(counts: np.ndarray) -> np.ndarray:
    """rank[i] = position of row i when sorted by DESCENDING count (stable)
    — the core of ``occupancy_permutation``, exposed so core.partition can
    refine stripes from one global bincount instead of building a
    submatrix per stripe."""
    order = np.argsort(-counts, kind="stable")
    rank = np.empty(len(counts), np.int64)
    rank[order] = np.arange(len(counts))
    return rank


def occupancy_permutation(coo: COO, axis: str = "row") -> np.ndarray:
    """Permutation sorting rows (or cols) by DESCENDING rating count, so the
    fused kernel's tn-row tiles are occupancy-coherent and its M-tile skip
    is effective (the complement of ``balance_permutation``, which spreads
    heavy rows — use this WITHIN a block after blocks are balanced)."""
    ids = coo.row if axis == "row" else coo.col
    n = coo.n_rows if axis == "row" else coo.n_cols
    return occupancy_rank(np.bincount(ids, minlength=n))


def train_test_split(coo: COO, test_frac: float = 0.1,
                     seed: int = 0) -> Tuple[COO, COO]:
    rng = np.random.default_rng(seed)
    m = rng.random(coo.nnz) < test_frac
    tr = COO(coo.row[~m], coo.col[~m], coo.val[~m], coo.n_rows, coo.n_cols)
    te = COO(coo.row[m], coo.col[m], coo.val[m], coo.n_rows, coo.n_cols)
    return tr, te


def balance_permutation(coo: COO, axis: str = "row") -> np.ndarray:
    """Permutation that round-robins rows (or cols) by descending rating
    count — the blocking then gets near-equal nnz per block stripe (the
    TPU-padded analogue of ref [16]'s sparsity-aware load balancing)."""
    ids = coo.row if axis == "row" else coo.col
    n = coo.n_rows if axis == "row" else coo.n_cols
    counts = np.bincount(ids, minlength=n)
    order = np.argsort(-counts, kind="stable")
    # round-robin assignment: order[i] -> position pattern spreading heavy rows
    perm = np.empty(n, np.int64)
    perm[order] = _round_robin_positions(n)
    return perm


def _round_robin_positions(n: int, stride: int = 64) -> np.ndarray:
    """i-th entry = target position of the i-th heaviest row: strided so the
    heavy rows spread uniformly over the index space (any contiguous blocking
    into <= stride blocks then receives a balanced mix)."""
    pos = []
    for s in range(stride):
        pos.extend(range(s, n, stride))
    return np.asarray(pos[:n], np.int64)


def apply_permutation(coo: COO, row_perm: Optional[np.ndarray] = None,
                      col_perm: Optional[np.ndarray] = None) -> COO:
    row = coo.row if row_perm is None else row_perm[coo.row].astype(np.int32)
    col = coo.col if col_perm is None else col_perm[coo.col].astype(np.int32)
    return COO(row=row, col=col, val=coo.val, n_rows=coo.n_rows,
               n_cols=coo.n_cols)
