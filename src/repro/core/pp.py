"""Posterior Propagation (PP) for BMF — the paper's algorithmic contribution.

Three phases over an I×J block grid (paper §2.2, Fig. 1):
  (a)   block (0,0): vanilla BMF with NW hyperpriors.
  (b)   first block-column (i,0) and block-row (0,j), in parallel: the
        shared factor's prior is the phase-(a) posterior (per-row
        Gaussians); the new factor keeps the NW hyperprior.
  (c)   remaining blocks (i,j), in parallel: both factors receive
        propagated phase-(b) posteriors as priors.

Communication happens ONLY at the two phase boundaries: what moves between
blocks is O((N/I + D/J)·K²) posterior summaries — never ratings, never
samples. Within a phase, blocks are embarrassingly parallel. Orchestration
lives in core.engine (the phase-graph engine): ``run_pp`` is a thin wrapper
that picks an Executor — serial reference loop, stacked (one vmapped Gibbs
call per phase shape bucket), or sharded (same-phase blocks concurrently on
a 'block' device mesh). Each block's Gibbs loop can also be internally
sharded via core.distributed (serial executor only).

Aggregation (paper §2.2 last ¶, following Qin et al. 2019): per factor row,
the final posterior multiplies the per-block posteriors (natural-parameter
sums) and divides away the (J-1 or I-1) multiply-counted propagated priors.

Prediction: each test entry falls in exactly one block; the predictive mean
is that block's posterior-mean product (accumulated over its Gibbs samples).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS
from repro.core.partition import Block, Partition
from repro.core.posterior import RowGaussians
from repro.data.sparse import COO, PaddedCSR, coo_to_padded_csr


@dataclass
class PPResult:
    rmse: float
    U_agg: RowGaussians              # aggregated posterior (permuted space)
    V_agg: RowGaussians
    per_block_rmse: np.ndarray       # (I, J)
    wall_time_s: float
    phase_times_s: Dict[str, float]
    n_test: int
    block_times_s: Dict[Tuple[int, int], float] = field(default_factory=dict)
    executor: str = "serial"         # engine executor that produced this run
    # dispatch→resolve spans per block, seconds relative to run start.
    # Recorded by overlapped executors (async); empty for barrier executors,
    # whose block_times_s are true per-block seconds (serial) or even bucket
    # splits (stacked/sharded — prefer phase_times_s there).
    block_spans_s: Dict[Tuple[int, int], Tuple[float, float]] = \
        field(default_factory=dict)
    # fault-tolerance ledger (engine.FaultRecord entries): every health-
    # guard trip, watchdog timeout, dispatch failure — and what the engine
    # did about it (retried / degraded / raised). Empty on a clean run;
    # "degrade" outcomes are ONLY trustworthy together with this record,
    # which is why it rides on the result instead of a log line.
    faults: list = field(default_factory=list)
    # blocks restored from a resume_from checkpoint (not re-run)
    resumed_blocks: int = 0
    # elastic group-fault-domain counters from the executor (engine
    # events: quarantine / steal / speculate / cancel). All-zero for
    # barrier executors and single-group async/streaming runs.
    group_stats: Dict[str, int] = field(default_factory=dict)
    # serving-export seam (repro.serving.PosteriorStore.from_pp_result):
    # U_agg/V_agg live in PERMUTED row/col space, so the result carries the
    # original->permuted maps plus the chain config the serve-time fold-in
    # conditional needs (rating precision tau, latent dim K). A PPResult is
    # thereby a self-contained servable artifact — no Partition or
    # BMFConfig needed at store-build time.
    row_perm: Optional[np.ndarray] = None
    col_perm: Optional[np.ndarray] = None
    tau: Optional[float] = None
    K: Optional[int] = None

    @property
    def n_retries(self) -> int:
        return sum(1 for f in self.faults if f.action == "retried")

    def _dep_graph(self):
        """Canonical PP dependency structure for this run's grid."""
        I, J = self.per_block_rmse.shape
        deps = {(0, 0): ()}
        deps.update({(i, 0): ((0, 0),) for i in range(1, I)})
        deps.update({(0, j): ((0, 0),) for j in range(1, J)})
        deps.update({(i, j): ((i, 0), (0, j))
                     for i in range(1, I) for j in range(1, J)})
        return deps

    def modeled_parallel_s(self, workers: int) -> float:
        """Wall-clock under the paper's deployment: a dependency-aware list
        schedule of the measured per-block times over ``workers`` — a block
        starts when its row/col prior sources are done AND a worker frees
        up, NOT at a phase barrier (overlapped execution is the point of
        the async executor, and the model matches it).

        block_times_s are true per-block seconds under serial, measured
        dispatch→resolve spans under async; under stacked/sharded they're
        even bucket splits, so prefer the MEASURED phase wall-clock in
        ``phase_times_s`` there."""
        import heapq
        deps = self._dep_graph()
        succ: Dict[Tuple[int, int], list] = {c: [] for c in deps}
        for c, ds in deps.items():
            for d in ds:
                succ[d].append(c)
        dur = {c: self.block_times_s.get(c, 0.0) for c in deps}
        free = [0.0] * max(int(workers), 1)
        heapq.heapify(free)
        ready = [(0.0, (0, 0))]
        finish: Dict[Tuple[int, int], float] = {}
        while ready:
            ready_t, c = heapq.heappop(ready)
            start = max(heapq.heappop(free), ready_t)
            finish[c] = start + dur[c]
            heapq.heappush(free, finish[c])
            for s in succ[c]:
                if all(d in finish for d in deps[s]):
                    heapq.heappush(ready, (max(finish[d] for d in deps[s]), s))
        return max(finish.values(), default=0.0)

    def critical_path_s(self) -> float:
        """Length of the longest dependency chain through the measured
        per-block times — the wall-clock floor under unbounded workers
        (what the async executor approaches as barrier stalls vanish)."""
        deps = self._dep_graph()
        memo: Dict[Tuple[int, int], float] = {}

        def cp(c):
            if c not in memo:
                memo[c] = (self.block_times_s.get(c, 0.0)
                           + max((cp(d) for d in deps[c]), default=0.0))
            return memo[c]

        return max((cp(c) for c in deps), default=0.0)


def _slice_prior(prior: RowGaussians, ids: np.ndarray) -> RowGaussians:
    return RowGaussians(eta=prior.eta[ids], Lambda=prior.Lambda[ids])


def _block_test(test: COO, block: Block) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Test entries falling inside a block, in local coordinates."""
    sub = test.submatrix(block.row_ids, block.col_ids)
    return sub.row, sub.col, sub.val


@dataclass
class BlockShapes:
    """Common bucketed shapes so one jitted executable serves every block
    of a bucket (per-block shapes would trigger a recompile each).

    Buckets are PER PHASE (``per_phase``): phase-a / b_row / b_col / c
    blocks have systematically different occupancy (phase a sees one dense
    corner block; phase-c blocks are the sparse interior), so one global
    max-shape bucket pads every interior block to the corner block's
    worst-case nnz/row.  Per-phase buckets trade ≤4 compilations for much
    tighter padding — and tighter padding is compute, not just memory,
    since the Gibbs einsum/kernel work scales with the padded M."""
    n_rows: int
    n_cols: int
    m_rows: int       # max nnz per user row
    m_cols: int       # max nnz per item row
    n_test: int

    def astuple(self) -> Tuple[int, int, int, int, int]:
        return (self.n_rows, self.n_cols, self.m_rows, self.m_cols,
                self.n_test)

    def block_bytes(self, K: int) -> int:
        """Device bytes ONE block occupies at this bucket's padding: CSR
        planes in both orientations (idx/val/mask), the four test vectors
        (row/col indices, values, mask), both propagated priors (eta +
        Lambda), and the U0/V0
        factor initializations — i.e. what a stacked executor multiplies
        by its batch size, and what the streaming executor multiplies by
        its window."""
        csr = 3 * 4 * (self.n_rows * self.m_rows + self.n_cols * self.m_cols)
        tst = 4 * 4 * self.n_test        # tr, tc, tv, tmask
        priors = 4 * (self.n_rows + self.n_cols) * (K + K * K)
        factors = 4 * (self.n_rows + self.n_cols) * K
        return csr + tst + priors + factors

    @staticmethod
    def coalesce(per_phase: Dict[str, "BlockShapes"], K: int,
                 max_waste: float = 1.5) -> Dict[str, "BlockShapes"]:
        """Bucket-coalescing for the streaming window: merge per-phase
        buckets whose padded footprints are within ``max_waste`` of each
        other (``partition.coalesce_shapes``), so one window shape — and
        therefore ONE window executable and one recycled buffer pool —
        serves blocks of several phase tags. Tags that coalesce share one
        ``BlockShapes`` instance (identity marks the group)."""
        from repro.core.partition import coalesce_shapes
        merged = coalesce_shapes(
            {tag: s.astuple() for tag, s in per_phase.items()},
            footprint=lambda t: BlockShapes(*t).block_bytes(K),
            max_waste=max_waste)
        uniq: Dict[Tuple[int, ...], BlockShapes] = {}
        return {tag: uniq.setdefault(t, BlockShapes(*t))
                for tag, t in merged.items()}

    @staticmethod
    def of(part: Partition, test: Optional[COO],
           phases: Optional[Tuple[str, ...]] = None) -> "BlockShapes":
        """Max shapes over the partition's blocks (optionally restricted to
        the given ``Block.phase`` tags)."""
        def row_m(c: COO, n):
            return int(np.bincount(c.row, minlength=n).max()) if c.nnz else 1
        n_rows = m_r = m_c = n_cols = n_test = 1
        for b in part.all_blocks():
            if phases is not None and b.phase not in phases:
                continue
            n_rows = max(n_rows, len(b.row_ids))
            n_cols = max(n_cols, len(b.col_ids))
            m_r = max(m_r, row_m(b.coo, len(b.row_ids)))
            m_c = max(m_c, row_m(b.coo.transpose(), len(b.col_ids)))
            if test is not None:
                sub = test.submatrix(b.row_ids, b.col_ids)
                n_test = max(n_test, sub.nnz)
        return BlockShapes(n_rows=n_rows, n_cols=n_cols, m_rows=m_r,
                           m_cols=m_c, n_test=n_test)

    @staticmethod
    def per_phase(part: Partition, test: Optional[COO]
                  ) -> Dict[str, "BlockShapes"]:
        """One occupancy bucket per phase tag present in the partition."""
        tags = {b.phase for b in part.all_blocks()}
        return {ph: BlockShapes.of(part, test, phases=(ph,)) for ph in tags}


def _pad_prior(prior: Optional[RowGaussians], n: int, K: int):
    if prior is None:
        return None
    pad = n - prior.eta.shape[0]
    if pad <= 0:
        return prior
    eta = jnp.concatenate([prior.eta, jnp.zeros((pad, K))])
    eye = jnp.broadcast_to(jnp.eye(K), (pad, K, K))
    Lam = jnp.concatenate([prior.Lambda, eye])
    return RowGaussians(eta=eta, Lambda=Lam)


def pad_block_inputs_host(block: Block, shapes: BlockShapes,
                          test: Optional[COO], poison_nan: bool = False):
    """Host-side (numpy) padding of one block's CSR planes and test
    entries to a shape bucket — the transferable part of
    ``pad_block_inputs``, kept in numpy so the streaming executor can
    assemble a whole window chunk on the host and ship it with ONE async
    ``device_put`` (the double-buffered prefetch H2D transfer) while the
    previous chunk is still computing. Priors are NOT built here: they are
    device-resident outputs of earlier blocks.

    Returns ``(csr_rows, csr_cols, tr, tc, tv, tmask)`` with numpy leaves.
    """
    csr_rows = coo_to_padded_csr(block.coo, max_nnz=shapes.m_rows,
                                 n_rows_pad=shapes.n_rows,
                                 n_cols_pad=shapes.n_cols, as_numpy=True)
    csr_cols = coo_to_padded_csr(block.coo.transpose(),
                                 max_nnz=shapes.m_cols,
                                 n_rows_pad=shapes.n_cols,
                                 n_cols_pad=shapes.n_rows, as_numpy=True)
    if poison_nan:
        # deterministic fault-injection seam (engine.FaultPlan): NaN-fill
        # the rating planes so the chain's very first sufficient-stats
        # einsum goes non-finite and the in-chain health guard trips — the
        # same failure surface as a real diverged/NaN'd chain, via the one
        # padding path every executor shares.
        csr_rows = PaddedCSR(idx=csr_rows.idx,
                             val=np.full_like(csr_rows.val, np.nan),
                             mask=csr_rows.mask, n_cols=csr_rows.n_cols)
        csr_cols = PaddedCSR(idx=csr_cols.idx,
                             val=np.full_like(csr_cols.val, np.nan),
                             mask=csr_cols.mask, n_cols=csr_cols.n_cols)
    if test is not None:
        tr, tc, tv_raw = _block_test(test, block)
    else:
        tr = np.zeros((0,), np.int32)
        tc = np.zeros((0,), np.int32)
        tv_raw = np.zeros((0,), np.float32)
    n = min(len(tr), shapes.n_test)

    def padded(arr, dtype):
        out = np.zeros((shapes.n_test,), dtype)
        out[:n] = arr[:n]
        return out

    tv = padded(tv_raw.astype(np.float32), np.float32)
    tmask = np.zeros((shapes.n_test,), np.float32)
    tmask[:n] = 1.0
    return (csr_rows, csr_cols, padded(tr, np.int32), padded(tc, np.int32),
            tv, tmask)


def pad_block_inputs(block: Block, shapes: BlockShapes, K: int,
                     test: Optional[COO],
                     U_prior: Optional[RowGaussians],
                     V_prior: Optional[RowGaussians],
                     poison_nan: bool = False):
    """Pad one block's CSR planes, priors, and test entries to its phase
    shape bucket — the single source of truth for bucketed padding.
    ``engine._group_chain`` (serial and async executors),
    ``engine._task_leaves`` (stacked/sharded executors), and the
    streaming executor's chunk assembly (via ``pad_block_inputs_host``)
    all go through the same numpy fill; the executors' chain-identical
    parity depends on them never diverging.

    Returns ``(csr_rows, csr_cols, tr, tc, tv, tmask, U_prior, V_prior)``:
    padded test indices, VALUES, and a validity mask over the bucket's
    n_test slots (one submatrix scan serves all three) — tv/tmask let the
    engine compute each block's squared error as a tiny on-device scalar
    instead of pulling the (n_test,) prediction vector to the host."""
    csr_rows_h, csr_cols_h, tr, tc, tv, tmask = pad_block_inputs_host(
        block, shapes, test, poison_nan=poison_nan)
    csr_rows = PaddedCSR(idx=jnp.asarray(csr_rows_h.idx),
                         val=jnp.asarray(csr_rows_h.val),
                         mask=jnp.asarray(csr_rows_h.mask),
                         n_cols=csr_rows_h.n_cols)
    csr_cols = PaddedCSR(idx=jnp.asarray(csr_cols_h.idx),
                         val=jnp.asarray(csr_cols_h.val),
                         mask=jnp.asarray(csr_cols_h.mask),
                         n_cols=csr_cols_h.n_cols)
    U_prior = _pad_prior(U_prior, shapes.n_rows, K)
    V_prior = _pad_prior(V_prior, shapes.n_cols, K)
    return (csr_rows, csr_cols, tr, tc, tv, tmask, U_prior, V_prior)


def run_pp(key, part: Partition, cfg: BMF.BMFConfig, test: COO,
           verbose: bool = False, executor="serial",
           window: Optional[int] = None, topology=None,
           on_fault: str = "raise", max_retries: int = 2,
           fault_policy=None, fault_plan=None,
           checkpoint_dir=None, ckpt_every: int = 1,
           resume_from=None) -> PPResult:
    """Full three-phase Posterior Propagation over the partition.

    Thin wrapper over the phase-graph engine (core.engine): the run is an
    explicit three-phase DAG of BlockTasks executed by a pluggable Executor.

    executor: "serial" (reference: per-block jitted calls, today's exact
      semantics), "stacked" (one vmapped Gibbs call per phase shape bucket),
      "sharded" (the stacked batch shard_map'd over a 'block' device mesh so
      same-phase blocks run concurrently on separate devices), "async"
      (dependency-driven overlap: readiness counters dispatch each block the
      moment its propagated priors resolve — phase b and c overlap, buffers
      are donated, posteriors stay device-resident), "streaming" (bounded
      window of donated block buffers streamed through the same ready
      queue — for grids whose stacked buckets don't fit device memory), or
      an ``engine.Executor`` instance.
    topology: unified 2-D device placement (core.topology.Topology or an
      ``(block, data)`` pair): 'block' counts device groups running blocks
      concurrently, 'data' counts devices INSIDE each block's Gibbs chain
      (the intra-block distributed sweep of core.distributed). E.g.
      ``run_pp(..., executor="sharded", topology=Topology(block=2, data=2))``
      on 4 devices runs 2 blocks at a time, each chain sharded 2-way —
      the paper's combined system. Consumed by serial (block must be 1),
      sharded, async (group streams), and streaming (per-group windows);
      the only way to place blocks on devices.
    window: streaming executor's window size W (blocks per chunk); ignored
      by the other executors.
    verbose: per-phase progress lines (block count, shape buckets, wall time).

    Fault tolerance (core/README.md "Fault tolerance"):
    on_fault: what to do with a block whose chain stays unhealthy after
      ``max_retries`` re-runs — "raise" (engine.BlockFaultError) or
      "degrade" (the block's posterior := its propagated prior, so it
      cancels exactly in the divide-away aggregation; its test entries are
      dropped from the RMSE; recorded in ``PPResult.faults``).
    max_retries: bounded re-runs of an unhealthy block, each with a
      ``fold_in``-resplit PRNG key and a jitter-inflated prior.
    fault_policy: a full ``engine.FaultPolicy`` (watchdog deadlines, RMSE
      divergence threshold, retry jitter); overrides on_fault/max_retries.
    fault_plan: deterministic test-only fault injection
      (``engine.FaultPlan``): NaN'd chains / hung dispatches / failed
      dispatches by coord and attempt.
    checkpoint_dir: persist each resolved block's posteriors through
      ``checkpoint.ckpt.PPCheckpoint`` (every ``ckpt_every`` resolves).
    resume_from: a checkpoint directory from an earlier (interrupted) run
      with the same key/grid/K/topology: resolved blocks are restored, the
      readiness counters rebuilt, and the finished run is bitwise
      identical to an uninterrupted one.
    """
    from repro.core import engine as ENG
    if int(max_retries) < 0:
        raise ValueError(f"max_retries must be >= 0, got {max_retries}")
    if on_fault not in ("raise", "degrade"):
        raise ValueError(f"on_fault must be 'raise' or 'degrade', "
                         f"got {on_fault!r}")
    if int(ckpt_every) < 1:
        raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
    if fault_policy is None:
        fault_policy = ENG.FaultPolicy(on_fault=on_fault,
                                       max_retries=int(max_retries))
    ex = ENG.make_executor(executor, window=window, topology=topology)
    return ENG.run_phase_graph(key, part, cfg, test, ex, verbose=verbose,
                               policy=fault_policy, fault_plan=fault_plan,
                               checkpoint_dir=checkpoint_dir,
                               ckpt_every=int(ckpt_every),
                               resume_from=resume_from)


@partial(jax.jit, static_argnames=("axis",))
def _aggregate_axis_jit(posts, axis: str) -> RowGaussians:
    """Jitted divide-away reduction over a (I, J) nested tuple of
    device-resident RowGaussians — ONE executable, no host round-trip:
    the engine keeps posterior summaries on device between phases and this
    is the only consumer, so natural-parameter sums, prior subtraction, and
    the final concatenation all stay on device."""
    I, J = len(posts), len(posts[0])
    out_eta, out_lam = [], []
    if axis == "row":
        for i in range(I):
            eta_stack = jnp.stack([posts[i][j].eta for j in range(J)])
            lam_stack = jnp.stack([posts[i][j].Lambda for j in range(J)])
            prior = posts[i][0]          # the propagated one for this row grp
            out_eta.append(eta_stack.sum(0) - (J - 1) * prior.eta)
            out_lam.append(lam_stack.sum(0) - (J - 1) * prior.Lambda)
    else:
        for j in range(J):
            eta_stack = jnp.stack([posts[i][j].eta for i in range(I)])
            lam_stack = jnp.stack([posts[i][j].Lambda for i in range(I)])
            prior = posts[0][j]
            out_eta.append(eta_stack.sum(0) - (I - 1) * prior.eta)
            out_lam.append(lam_stack.sum(0) - (I - 1) * prior.Lambda)
    return RowGaussians(eta=jnp.concatenate(out_eta),
                        Lambda=jnp.concatenate(out_lam))


def _aggregate_axis(part: Partition, posts, axis: str) -> RowGaussians:
    """Combine per-block posteriors for one factor.

    For U row-group i: posterior from blocks (i, 0..J-1); blocks 1..J-1 in
    that row all received the same propagated prior (the phase-b posterior
    of U^(i) — or phase-a for i=0), counted J times in the product, so J-1
    copies are divided away (Qin et al. 2019, eq. 5).

    Operates on stacked leaves: blocks of a row (col) group share their row
    (col) ids, so the J (I) per-block posteriors stack along a leading axis
    and the natural-parameter sum is one reduction instead of a Python
    chain of adds — and the whole reduction is one jitted executable
    (``_aggregate_axis_jit``) so posteriors never visit the host.
    """
    assert len(posts) == part.I and len(posts[0]) == part.J
    return _aggregate_axis_jit(tuple(tuple(row) for row in posts), axis)


def run_full_bmf(key, train: COO, test: COO, cfg: BMF.BMFConfig):
    """1×1 'partition' — the vanilla BMF baseline (paper Table 3 column BMF)."""
    csr_rows = coo_to_padded_csr(train)
    csr_cols = coo_to_padded_csr(train.transpose())
    t0 = time.time()
    res = GIBBS.run_gibbs(key, csr_rows, csr_cols,
                          jnp.asarray(test.row), jnp.asarray(test.col), cfg)
    rmse = float(GIBBS.rmse_from_acc(res.acc, jnp.asarray(test.val)))
    return rmse, time.time() - t0, res
