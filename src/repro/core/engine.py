"""Phase-graph execution engine for Posterior Propagation.

The paper's §2.2 structure is a three-phase DAG over the I×J block grid:
phase (a) is block (0,0); phase (b) is the first block-row and block-column,
depending only on (a); phase (c) is the interior, depending only on (b).
Within a phase, blocks are embarrassingly parallel — O((N/I + D/J)·K²)
posterior summaries cross phase boundaries, nothing else does.

This module makes the graph explicit (``BlockTask`` / ``build_phase_graph``)
and executes it through a pluggable ``Executor``:

  SerialExecutor   reference semantics: one jitted Gibbs call per block with
                   a host sync after each — what ``run_pp`` always did.
                   Composes with an intra-block ``distributed_mesh`` /
                   ``Topology(block=1, data=S)``.
  StackedExecutor  stacks all blocks of a phase shape bucket along a leading
                   axis and runs ONE jitted vmapped chain per bucket
                   (``gibbs.run_gibbs_stacked``) — the per-block Python
                   dispatch and per-block host syncs disappear.
                   ``BlockShapes.per_phase`` is what makes stacking legal:
                   every block of a bucket is padded to identical shapes.
  ShardedExecutor  the stacked batch additionally shard_map'd over a 1-D
                   'block' device mesh: same-phase blocks genuinely run
                   concurrently on separate devices with NO collectives
                   inside a phase — the paper's deployment model, on-device.
  AsyncExecutor    dependency-driven overlap: readiness counters over
                   ``BlockTask.deps`` dispatch each block's jitted chain the
                   moment its row/col prior posteriors resolve, riding JAX
                   async dispatch — phase-c blocks whose phase-b sources
                   finished early start while the rest of phase b is still
                   running. No ``block_until_ready`` until the final
                   aggregation; completion is detected by non-blocking
                   ``is_ready()`` polls on tiny per-block squared-error
                   scalars. Posterior summaries stay device-resident
                   between phases, padded input buffers are donated to XLA
                   (``gibbs.run_gibbs(donate=True)``), and with >1 local
                   device each dispatch lands on the next topology GROUP
                   round-robin: per-group streams instead of one sharded
                   bucket (groups of 1 device = the legacy per-device
                   streams; groups of >1 run each chain 'data'-sharded).
  StreamingExecutor the same ready queue, but blocks stream through a
                   bounded window of W donated block buffers: host-side
                   chunk assembly + double-buffered ``device_put``
                   prefetch, ``run_gibbs_stacked(donate=True)`` recycling,
                   live peak ≤ W×(depth+1)×block_bytes per stream — flat
                   in the grid size, for grids whose stacked buckets
                   exceed HBM. With a multi-group ``Topology`` it keeps
                   ONE such window per device group (per-stream prefetch).

Device placement is unified behind ``core.topology.Topology`` — a single
2-D ('block', 'data') mesh whose groups run blocks concurrently while the
'data' axis shards each block's Gibbs sweep (the intra-block distributed
chain of core.distributed). Executors consume the same object instead of
ad-hoc device lists; ``topology=Topology(block=2, data=2)`` turns any of
sharded/async/streaming into the paper's combined two-level system.

The async and streaming ready queues dispatch CRITICAL-PATH-FIRST: ready
blocks pop in descending bottom-level order (``critical_path_priority`` —
estimated block cost plus the longest estimated successor chain, the same
dependency-aware list-schedule depth ``PPResult.modeled_parallel_s``
schedules measured times with), FIFO among ties.

Executor contract
-----------------
``run_graph(ctx, graph, verbose) -> (outcomes, phase_times_s, spans)`` owns
ordering: it must write each block's posterior summaries into ``ctx.U_posts``
/ ``ctx.V_posts`` before any dependent reads them via ``ctx.priors(task)``.
Barrier executors get that for free from the default implementation, which
runs ``run_phase(ctx, phase, tasks) -> {(i, j): BlockOutcome}`` once per
phase after asserting every dep resolved; the async executor replaces the
whole loop with its dependency-counting scheduler. Executors never
aggregate: ``run_phase_graph`` owns RMSE accumulation and the Qin-et-al.
divide-away aggregation (``pp._aggregate_axis``, one jitted device-resident
reduction).

Fault tolerance (see core/README.md): every block's chain computes a
device-resident health scalar (``gibbs.GibbsResult.health``) checked at
resolve time by ``_commit_guard`` — unhealthy blocks retry through one
shared single-block runner (re-split key, jittered prior), then degrade to
their propagated prior or raise per ``FaultPolicy``. The async/streaming
poll loops are watchdog-policed (cost-model deadlines; timed-out dispatches
re-dispatch on the next device group), ``checkpoint_dir``/``resume_from``
persist and restore per-block posteriors bitwise, and ``FaultPlan`` is the
deterministic injection seam the chaos tests drive every executor with.

Note on timings: SerialExecutor measures true per-block seconds;
Stacked/Sharded report bucket wall time split evenly across the bucket's
blocks (one executable runs them all) — the interesting number there is the
*measured* phase wall time in ``PPResult.phase_times_s``. AsyncExecutor
records true dispatch→resolve spans per block (``PPResult.block_spans_s``);
because phases overlap, its per-phase times are first-dispatch→last-resolve
envelopes and may sum to more than the wall time —
``PPResult.critical_path_s()`` is the honest aggregate.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS
from repro.core import pp as PP
from repro.core.partition import Partition
from repro.core.posterior import RowGaussians
from repro.core.topology import Topology
from repro.data.sparse import COO, PaddedCSR, apply_permutation

Coord = Tuple[int, int]

# stable intra-phase bucket order (phase b runs its two buckets back to back)
_TAG_ORDER = ("a", "b_row", "b_col", "c")


# ---------------------------------------------------------------------------
# Fault tolerance: policy, deterministic injection plan, fault ledger
# ---------------------------------------------------------------------------


class BlockFaultError(RuntimeError):
    """A block exhausted its retry budget (unhealthy chain, repeated
    dispatch failure, or repeated watchdog timeout) under
    ``FaultPolicy.on_fault == 'raise'``."""


class TopologyDegradedError(RuntimeError):
    """Quarantines shrank the usable device-group set below
    ``FaultPolicy.min_groups`` (or to zero healthy groups). Raised AFTER
    flushing any active checkpoint, so the run is immediately resumable
    on a different topology. ``dead_groups`` names the quarantined
    groups in canonical order."""

    def __init__(self, msg: str, dead_groups: Sequence[int] = ()):
        super().__init__(msg)
        self.dead_groups: Tuple[int, ...] = tuple(dead_groups)


class _InjectedDispatchFailure(RuntimeError):
    """Raised by the FaultPlan seam to simulate a dispatch-time failure
    (device OOM, dead runtime) — handled exactly like the real thing."""


# dispatch-time failures the engine treats as block faults rather than
# bugs: the injected seam plus JAX's runtime-side errors (OOM, dead
# device). Anything else propagates — a TypeError is a bug, not a fault.
_DISPATCH_ERRORS = (_InjectedDispatchFailure, jax.errors.JaxRuntimeError)


@dataclass(frozen=True)
class FaultPolicy:
    """What the engine does when a block goes bad.

    on_fault: after ``max_retries`` failed re-runs — "raise"
      (``BlockFaultError``) or "degrade" (posterior := the block's
      propagated prior, which cancels EXACTLY in the divide-away
      aggregation; the block's test entries drop out of the RMSE and the
      fault is recorded in ``PPResult.faults``).
    max_retries: bounded re-runs of a faulty block. Retry ``a`` uses
      ``fold_in(key, a)`` (a fresh independent chain) and a prior whose
      precision is inflated by ``retry_jitter·a·I`` — the two standard
      fixes for a NaN'd Cholesky / diverged chain. Retries run through ONE
      shared single-block runner, so a retried block's chain is identical
      under every executor (deterministic by (coord, attempt)).
    rmse_max: optional divergence threshold — a resolved block whose own
      test RMSE exceeds it is treated as faulty even if finite.
    watchdog: deadline-police the async/streaming poll loops. A block's
      deadline is ``timeout_floor_s + timeout_slack · rate · est(block)``
      where ``est`` is the nnz cost proxy (``_block_cost_estimates``, the
      same model priority dispatch uses) and ``rate`` is the max observed
      seconds-per-cost-unit over already-resolved blocks (0 until the
      first resolve, so early blocks get the generous floor). A timed-out
      dispatch is dropped, its block re-dispatched on the next device
      group (same PRNG key — a slow-but-alive block re-resolves to
      bitwise-identical numbers); budget exhaustion degrades/raises.
      watchdog=False restores the legacy block-on-oldest fallback, which
      deadlocks if the oldest in-flight block died — keep it on.

    Group fault domain (active when the executor's topology has >1 device
    group; with one group there is nowhere to rebalance to):

    quarantine_after: a group whose dispatches expire this many
      CONSECUTIVE times is quarantined — drained, never dispatched to
      again this run; its staged share and in-flight blocks rebalance
      onto healthy groups under the same keys (a group fault consumes no
      block retry budget — the blocks did nothing wrong).
    speculate_at: straggler hedge — when a dispatch has been in flight
      longer than ``speculate_at × rate(group) × est`` (the group's OWN
      calibrated rate), the block is redundantly dispatched to an idle
      healthy group with the same attempt-0 key. Twins are bitwise
      identical by construction; resolution commits a deterministic
      winner (canonical group order, not wall-clock first) and cancels
      the other. 0 disables speculation (the default).
    min_groups: quarantines that leave fewer healthy groups than this
      trigger graceful degradation: the checkpoint (if any) is flushed,
      then the run either continues on the survivors or raises
      ``TopologyDegradedError`` naming the dead groups, per
      ``on_group_fault`` ("continue" | "raise"). Zero healthy groups
      always raises.
    """
    on_fault: str = "raise"
    max_retries: int = 2
    rmse_max: Optional[float] = None
    retry_jitter: float = 1e-3
    watchdog: bool = True
    timeout_floor_s: float = 60.0
    timeout_slack: float = 10.0
    quarantine_after: int = 3
    speculate_at: float = 0.0
    min_groups: int = 1
    on_group_fault: str = "raise"

    def __post_init__(self):
        if self.on_fault not in ("raise", "degrade"):
            raise ValueError(f"on_fault must be 'raise' or 'degrade', "
                             f"got {self.on_fault!r}")
        if int(self.max_retries) < 0:
            raise ValueError(f"max_retries must be >= 0, "
                             f"got {self.max_retries}")
        if self.on_group_fault not in ("raise", "continue"):
            raise ValueError(f"on_group_fault must be 'raise' or "
                             f"'continue', got {self.on_group_fault!r}")
        if int(self.quarantine_after) < 1:
            raise ValueError(f"quarantine_after must be >= 1, "
                             f"got {self.quarantine_after}")
        if int(self.min_groups) < 1:
            raise ValueError(f"min_groups must be >= 1, "
                             f"got {self.min_groups}")
        if float(self.speculate_at) < 0:
            raise ValueError(f"speculate_at must be >= 0 (0 disables), "
                             f"got {self.speculate_at}")


@dataclass(frozen=True)
class FaultPlan:
    """Deterministic fault injection by coordinate — the test-only seam
    the conformance fault battery drives every executor with.

    Each map is ``{coord: n}``: the block's first ``n`` attempts are
    affected (attempt 0 is the normal dispatch, attempt ``a`` the a-th
    retry), so a plan is a pure function of (coord, attempt) and every
    run under it is deterministic.

    nan_at: NaN-poison the block's rating planes at padding time — the
      chain itself goes non-finite and the in-chain health guard trips,
      exercising the REAL failure surface rather than a mocked flag.
    hang_at: suppress completion detection for the block's dispatch
      (async/streaming ``_is_resolved`` never fires) until the watchdog
      deadline recovers it. Ignored by barrier executors, which have no
      poll loop to hang.
    fail_dispatch_at: dispatching the block raises — exercised at every
      executor's dispatch site (serial call, stacked bucket assembly,
      async dispatch, streaming chunk formation).

    Group-level injections key on the GROUP and its per-group dispatch
    ordinal (``PhaseContext.next_group_ordinal``) instead of (coord,
    attempt) — they model a device row going bad partway through a run,
    independent of which blocks happen to land on it. Both act at the
    completion-observation seam (the device work is untouched), the real
    surface the watchdog / quarantine / speculation layers react to:

    group_dead_at: ``{group: n}`` — the group's n-th and later dispatches
      are never observed complete (a dead group: every dispatch expires
      until the group is quarantined).
    group_slow_at: ``{group: (n, slow_s)}`` — from the group's n-th
      dispatch on, completion is withheld for ``slow_s`` seconds after
      dispatch (a straggler group: alive, just late — the speculation
      target).
    """
    nan_at: Dict[Coord, int] = field(default_factory=dict)
    hang_at: Dict[Coord, int] = field(default_factory=dict)
    fail_dispatch_at: Dict[Coord, int] = field(default_factory=dict)
    group_dead_at: Dict[int, int] = field(default_factory=dict)
    group_slow_at: Dict[int, Tuple[int, float]] = field(default_factory=dict)

    def nan(self, c: Coord, attempt: int) -> bool:
        return attempt < self.nan_at.get(tuple(c), 0)

    def hang(self, c: Coord, attempt: int) -> bool:
        return attempt < self.hang_at.get(tuple(c), 0)

    def fail(self, c: Coord, attempt: int) -> bool:
        return attempt < self.fail_dispatch_at.get(tuple(c), 0)

    def group_dead(self, g: int, ordinal: int) -> bool:
        n = self.group_dead_at.get(int(g))
        return n is not None and ordinal >= int(n)

    def group_slow_s(self, g: int, ordinal: int) -> float:
        ent = self.group_slow_at.get(int(g))
        if ent is None:
            return 0.0
        n, slow = ent
        return float(slow) if ordinal >= int(n) else 0.0


@dataclass(frozen=True)
class FaultRecord:
    """One ledger entry in ``PPResult.faults``: what went wrong with which
    block at which attempt, and what the engine did about it. kind
    "group" entries record the group fault domain: action "quarantined"
    marks the block whose expiry tripped a group's quarantine, and
    "rebalanced" each in-flight block moved off the quarantined group
    (no retry budget consumed — the block did nothing wrong)."""
    coord: Coord
    kind: str        # "nonfinite" | "rmse" | "dispatch" | "timeout" | "group"
    attempt: int
    action: str      # "retried" | "redispatched" | "degraded" | "raised"
    #                  | "quarantined" | "rebalanced"


@dataclass(frozen=True)
class BlockTask:
    """One node of the PP phase graph.

    ``phase`` is the partition's shape-bucket tag ('a'|'b_row'|'b_col'|'c');
    ``u_prior_from`` / ``v_prior_from`` name the block whose U / V posterior
    is propagated into this block as its prior (None = NW hyperprior)."""
    i: int
    j: int
    phase: str
    u_prior_from: Optional[Coord]
    v_prior_from: Optional[Coord]

    @property
    def coord(self) -> Coord:
        return (self.i, self.j)

    @property
    def deps(self) -> Tuple[Coord, ...]:
        return tuple(c for c in (self.u_prior_from, self.v_prior_from)
                     if c is not None)


def build_phase_graph(part: Partition) -> List[Tuple[str, List[BlockTask]]]:
    """The paper's three-phase DAG: [(phase_name, tasks)] in execution
    order. Every task's deps live in strictly earlier phases."""
    I, J = part.I, part.J
    phase_a = [BlockTask(0, 0, "a", None, None)]
    phase_b = ([BlockTask(i, 0, "b_row", None, (0, 0)) for i in range(1, I)]
               + [BlockTask(0, j, "b_col", (0, 0), None) for j in range(1, J)])
    phase_c = [BlockTask(i, j, "c", (i, 0), (0, j))
               for i in range(1, I) for j in range(1, J)]
    return [(name, tasks) for name, tasks in
            (("a", phase_a), ("b", phase_b), ("c", phase_c)) if tasks]


@dataclass
class PhaseContext:
    """Run state shared with executors: inputs (partition, config, permuted
    test set, per-block keys, shape buckets) plus the posterior store that
    carries summaries across phase boundaries. The store holds DEVICE
    arrays end to end — executors write device-resident summaries, the
    engine aggregates them in one jitted reduction, and nothing round-trips
    through the host between phases."""
    part: Partition
    cfg: BMF.BMFConfig
    test_p: COO
    keys: jax.Array                      # (I, J) typed PRNG keys
    shapes: Dict[str, "PP.BlockShapes"]  # per phase tag
    U_posts: Dict[Coord, RowGaussians] = field(default_factory=dict)
    V_posts: Dict[Coord, RowGaussians] = field(default_factory=dict)
    # fault tolerance: policy, optional deterministic injection plan,
    # per-block attempt counters (0 = the normal dispatch), the run's
    # fault ledger, optional block-level checkpoint writer, and outcomes
    # restored from a resume_from directory (their tasks are pruned from
    # the graph the executor sees).
    policy: FaultPolicy = field(default_factory=FaultPolicy)
    fault_plan: Optional[FaultPlan] = None
    attempts: Dict[Coord, int] = field(default_factory=dict)
    faults: List[FaultRecord] = field(default_factory=list)
    ckpt: Optional[object] = None        # checkpoint.ckpt.PPCheckpoint
    resumed: Dict[Coord, "BlockOutcome"] = field(default_factory=dict)
    # per-group dispatch counters — the ordinals the group-level fault
    # injections (FaultPlan.group_dead_at / group_slow_at) key on
    group_dispatches: Dict[int, int] = field(default_factory=dict)

    def block_cfg(self, task: BlockTask) -> BMF.BMFConfig:
        """Reduced chains for phases b/c when cfg.phase_bc_samples is set
        (the propagated priors are informative — paper future-work)."""
        cfg = self.cfg
        if cfg.phase_bc_samples and task.phase != "a":
            return cfg._replace(n_samples=cfg.phase_bc_samples,
                                burnin=max(2, cfg.phase_bc_samples // 4))
        return cfg

    def priors(self, task: BlockTask):
        up = self.U_posts[task.u_prior_from] if task.u_prior_from else None
        vp = self.V_posts[task.v_prior_from] if task.v_prior_from else None
        return up, vp

    # -- fault-tolerance plumbing ----------------------------------------

    def cur_attempt(self, c: Coord) -> int:
        return self.attempts.get(c, 0)

    def attempt_key(self, c: Coord, attempt: int):
        """Retry ``a`` re-splits the block's key with ``fold_in(key, a)``
        — a fresh chain, still a pure function of (run key, coord, a), so
        retried runs are deterministic and executor-independent."""
        k = self.keys[c[0], c[1]]
        return k if attempt == 0 else jax.random.fold_in(k, attempt)

    def should_poison(self, c: Coord) -> bool:
        return (self.fault_plan is not None
                and self.fault_plan.nan(c, self.cur_attempt(c)))

    def is_hung(self, c: Coord) -> bool:
        return (self.fault_plan is not None
                and self.fault_plan.hang(c, self.cur_attempt(c)))

    def check_dispatch(self, c: Coord):
        if (self.fault_plan is not None
                and self.fault_plan.fail(c, self.cur_attempt(c))):
            raise _InjectedDispatchFailure(
                f"injected dispatch failure for block {c} "
                f"(attempt {self.cur_attempt(c)})")

    def next_group_ordinal(self, g: int) -> int:
        """Bump-and-return group ``g``'s dispatch ordinal (0-based) — one
        per chunk/block dispatch landing on the group."""
        n = self.group_dispatches.get(int(g), 0)
        self.group_dispatches[int(g)] = n + 1
        return n

    def group_suppressed_until(self, g: int, ordinal: int,
                               td: float) -> float:
        """Group-level injection verdict for one dispatch: 0.0 = healthy,
        ``inf`` = the group is dead (completion never observed), else the
        wall-clock time before which completion is withheld
        (``group_slow_at``). Applied at the completion-observation seam,
        like ``is_hung``."""
        if self.fault_plan is None:
            return 0.0
        if self.fault_plan.group_dead(g, ordinal):
            return float("inf")
        slow = self.fault_plan.group_slow_s(g, ordinal)
        return td + slow if slow else 0.0

    def record_fault(self, c: Coord, kind: str, action: str):
        self.faults.append(FaultRecord(coord=c, kind=kind,
                                       attempt=self.cur_attempt(c),
                                       action=action))

    def note_resolved(self, task: BlockTask, out: "BlockOutcome"):
        """Checkpoint hook: persist one resolved block's posteriors + RMSE
        contribution. No-cost when checkpointing is off."""
        if self.ckpt is None:
            return
        n, sq = _host_sq(self, task, out)
        self.ckpt.note(task.coord, out.U_post, out.V_post, sq, n)


@dataclass
class BlockOutcome:
    U_post: RowGaussians       # trimmed to the block's true row count
    V_post: RowGaussians       # trimmed to the block's true col count
    # (bucket n_test,) posterior-mean predictions — None on the async path,
    # which reports squared error through the sq_err scalar instead
    pred_mean: Optional[np.ndarray]
    seconds: float
    # device-resident RMSE channel (async path): a tiny on-device scalar of
    # Σ(pred-val)² over the block's true test entries + their count. When
    # set, the engine never touches pred_mean.
    sq_err: Optional[jax.Array] = None
    n_obs: int = 0
    # the chain's device-resident health flag (gibbs.GibbsResult.health);
    # None on paths that predate the guard — treated as healthy.
    health: Optional[jax.Array] = None


def _outcome(res: GIBBS.GibbsResult, blk, seconds: float) -> BlockOutcome:
    nr, nc = len(blk.row_ids), len(blk.col_ids)
    pred = np.asarray(res.acc.pred_sum
                      / np.maximum(float(res.acc.pred_cnt), 1.0))
    return BlockOutcome(
        U_post=RowGaussians(eta=res.U_post.eta[:nr],
                            Lambda=res.U_post.Lambda[:nr]),
        V_post=RowGaussians(eta=res.V_post.eta[:nc],
                            Lambda=res.V_post.Lambda[:nc]),
        pred_mean=pred, seconds=seconds, health=res.health)


@jax.jit
def _block_sq_err(pred_sum, pred_cnt, vals, mask):
    """Masked Σ(pred-val)² — the per-block completion/RMSE scalar."""
    err = (pred_sum / jnp.maximum(pred_cnt, 1.0) - vals) * mask
    return jnp.vdot(err, err)


def _host_sq(ctx: PhaseContext, task: BlockTask,
             o: BlockOutcome) -> Tuple[int, float]:
    """One block's (n_test, Σ(pred-val)²) as host scalars — from the
    device-resident sq_err channel when present, else from pred_mean."""
    if o.sq_err is not None:
        return o.n_obs, float(o.sq_err)
    blk = ctx.part.block(task.i, task.j)
    _, _, tv = PP._block_test(ctx.test_p, blk)
    n = len(tv)
    sq = float(np.sum((np.asarray(o.pred_mean[:n]) - tv) ** 2)) if n else 0.0
    return n, sq


def _fault_kind(ctx: PhaseContext, task: BlockTask,
                o: BlockOutcome) -> Optional[str]:
    """Health verdict on a resolved outcome: None = healthy, else the
    fault kind. Checked BEFORE the posterior feeds any successor or the
    final aggregation — a NaN caught here never poisons anything
    downstream."""
    if o.health is not None and not bool(np.asarray(o.health)):
        return "nonfinite"
    if ctx.policy.rmse_max is not None:
        n, sq = _host_sq(ctx, task, o)
        # `not <=` (rather than `>`) also trips on a NaN sq that slipped
        # past a health-less outcome
        if n and not (sq <= (ctx.policy.rmse_max ** 2) * n):
            return "rmse"
    return None


def _jitter_prior(p: Optional[RowGaussians],
                  eps: float) -> Optional[RowGaussians]:
    """Precision-inflate a retry's prior: Λ + eps·I. Tightens the
    conditional toward the prior mean — the standard stabilization for a
    chain whose Cholesky went non-PD."""
    if p is None or not eps:
        return p
    K = p.eta.shape[-1]
    return RowGaussians(eta=p.eta, Lambda=p.Lambda + eps * jnp.eye(K))


def _run_block_attempt(ctx: PhaseContext, task: BlockTask,
                       attempt: int) -> BlockOutcome:
    """The shared retry runner: one synchronous single-block chain with
    the attempt's re-split key and jittered prior. EVERY executor heals
    through this path, so a retried block's chain — and therefore the
    whole faulted run's numbers — is identical whichever executor hit the
    fault. Uses the block's per-phase bucket shapes (the serial
    executable), so no new compilation is introduced."""
    c = task.coord
    ctx.check_dispatch(c)
    blk = ctx.part.block(task.i, task.j)
    s = ctx.shapes[task.phase]
    up, vp = ctx.priors(task)
    # the parent posteriors committed wherever their dispatches resolved,
    # which on a multi-group topology can be two different devices; the
    # retry chain is one single-device executable, so colocate them on
    # the default device (a pure transfer — bitwise-neutral, and the
    # same placement the serial executor uses)
    d0 = jax.devices()[0]
    up = jax.device_put(up, d0) if up is not None else None
    vp = jax.device_put(vp, d0) if vp is not None else None
    csr_r, csr_c, tr, tc, tv, tmask, up_p, vp_p = PP.pad_block_inputs(
        blk, s, ctx.cfg.K, ctx.test_p, up, vp,
        poison_nan=(ctx.fault_plan is not None
                    and ctx.fault_plan.nan(c, attempt)))
    eps = ctx.policy.retry_jitter * attempt
    res = GIBBS.run_gibbs(ctx.attempt_key(c, attempt), csr_r, csr_c,
                          jnp.asarray(tr), jnp.asarray(tc),
                          ctx.block_cfg(task),
                          U_prior=_jitter_prior(up_p, eps),
                          V_prior=_jitter_prior(vp_p, eps))
    nr, nc = len(blk.row_ids), len(blk.col_ids)
    sq = _block_sq_err(res.acc.pred_sum, res.acc.pred_cnt,
                       jnp.asarray(tv), jnp.asarray(tmask))
    return BlockOutcome(
        U_post=RowGaussians(eta=res.U_post.eta[:nr],
                            Lambda=res.U_post.Lambda[:nr]),
        V_post=RowGaussians(eta=res.V_post.eta[:nc],
                            Lambda=res.V_post.Lambda[:nc]),
        pred_mean=None, seconds=0.0, sq_err=sq, n_obs=int(tmask.sum()),
        health=res.health)


def _degrade_outcome(ctx: PhaseContext, task: BlockTask) -> BlockOutcome:
    """on_fault='degrade': the block's posterior becomes its propagated
    prior (neutral N(0, I) where it had none). In the divide-away
    aggregation ``Σ_j posts − (J−1)·prior`` a prior-valued posterior
    cancels EXACTLY, so a degraded block contributes nothing instead of
    something wrong; its test entries are dropped from the RMSE
    (sq_err=0, n_obs=0) — the reported error stays honest over the blocks
    that actually ran."""
    blk = ctx.part.block(task.i, task.j)
    up, vp = ctx.priors(task)
    K = ctx.cfg.K
    return BlockOutcome(
        U_post=up if up is not None else _dummy_prior(len(blk.row_ids), K),
        V_post=vp if vp is not None else _dummy_prior(len(blk.col_ids), K),
        pred_mean=None, seconds=0.0, sq_err=jnp.zeros(()), n_obs=0,
        health=jnp.asarray(True))


def _commit_guard(ctx: PhaseContext, task: BlockTask,
                  out: Optional[BlockOutcome],
                  kind: Optional[str] = None) -> BlockOutcome:
    """The chain-health guard, applied to every block at resolve time.

    Healthy outcome → returned untouched (the common case costs one tiny
    device→host bool read of an already-computed scalar). Faulty outcome
    (or ``kind`` pre-set by a dispatch failure / watchdog timeout) →
    bounded retries through ``_run_block_attempt``, then degrade or raise
    per ``ctx.policy``. Whenever the outcome changes, the posterior store
    is rewritten BEFORE returning, so successors and the final aggregation
    only ever see the healed values."""
    c = task.coord
    if kind is None:
        if out is None:
            raise AssertionError(f"block {c}: no outcome and no fault kind")
        kind = _fault_kind(ctx, task, out)
        if kind is None:
            return out
    pol = ctx.policy
    t0 = time.time()
    while ctx.cur_attempt(c) < pol.max_retries:
        attempt = ctx.cur_attempt(c) + 1
        ctx.record_fault(c, kind, "retried")
        ctx.attempts[c] = attempt
        try:
            out = _run_block_attempt(ctx, task, attempt)
            kind = _fault_kind(ctx, task, out)
        except _DISPATCH_ERRORS:
            kind = "dispatch"
            continue
        if kind is None:
            out.seconds = time.time() - t0
            ctx.U_posts[c], ctx.V_posts[c] = out.U_post, out.V_post
            return out
    if pol.on_fault == "degrade":
        ctx.record_fault(c, kind, "degraded")
        out = _degrade_outcome(ctx, task)
        ctx.U_posts[c], ctx.V_posts[c] = out.U_post, out.V_post
        return out
    ctx.record_fault(c, kind, "raised")
    raise BlockFaultError(
        f"block {c}: {kind} fault after {ctx.cur_attempt(c)} of "
        f"{pol.max_retries} retries (on_fault='raise'; pass "
        f"on_fault='degrade' to fall back to the propagated prior)")


class Executor:
    """Runs the PP phase graph; subclasses choose the schedule.

    Every executor records an optional event trace (``record_trace=True``):
    (event, coord) or (event, coord, group) entries appended in real
    order — the overlapped executors (async/streaming) attribute every
    event to the device group it happened on; barrier executors have no
    group concept and emit 2-tuples. "dispatch" means the block's chain
    was handed to the runtime (its priors were read), "resolve" means its
    results were observed complete. Watchdog paths add "expire" (the
    in-flight attempt hit its deadline and its handles were dropped) and
    "redispatch" (the expired attempt was re-dispatched under the same
    keys) — so a fault-free run is always dispatch/resolve pairs and a
    timeout is totally ordered as dispatch < expire < redispatch <
    resolve (an expire followed directly by a terminal resolve is the
    degraded/exhausted-budget path). The group fault domain adds four
    more (all group-attributed):

      "quarantine"  the group crossed ``quarantine_after`` consecutive
                    expiries and was drained (coord = the trigger block);
                    no dispatch may target it afterwards;
      "steal"       an idle healthy group took this staged (not yet
                    dispatched) block from the most-loaded group — the
                    next dispatch of the coord runs on the thief;
      "speculate"   a straggling in-flight block was redundantly
                    dispatched to this idle group under the same
                    attempt-0 key (its twin);
      "cancel"      one side of a twin pair was dropped — every
                    speculative pair ends in exactly one resolve and one
                    cancel (the deterministic canonical-group winner
                    commits; wall-clock order does not).

    The conformance suite (tests/test_executor_conformance.py) asserts on
    this trace that no executor ever dispatches a block before its
    dependencies resolved, and the analyzer's happens-before pass
    (repro.analysis.trace_passes) checks the full protocol — new
    executors get both for free by reporting honestly.

    ``n_quarantined`` / ``n_steals`` / ``n_speculations`` / ``n_cancels``
    count the group-fault events of the last run (surfaced as
    ``PPResult.group_stats``); always 0 for barrier executors.
    """
    name = "base"
    devices: Tuple = ()    # AsyncExecutor's per-device streams

    def __init__(self, record_trace: bool = False):
        self.record_trace = record_trace
        self.trace: List[Tuple] = []
        self.n_quarantined = 0
        self.n_steals = 0
        self.n_speculations = 0
        self.n_cancels = 0

    def _reset_run_state(self):
        """Clear per-run mutable state. Every ``run_graph`` implementation
        calls this first, so one executor instance is safely reusable
        across ``run_pp`` calls (warmup + timed runs, repeated benches)
        without traces or peak counters leaking between runs."""
        self.trace = []
        self.n_quarantined = 0
        self.n_steals = 0
        self.n_speculations = 0
        self.n_cancels = 0

    def _record(self, event: str, coord: Coord, group: Optional[int] = None):
        if self.record_trace:
            self.trace.append((event, coord) if group is None
                              else (event, coord, int(group)))

    def run_phase(self, ctx: PhaseContext, phase: str,
                  tasks: Sequence[BlockTask]) -> Dict[Coord, BlockOutcome]:
        raise NotImplementedError

    def run_graph(self, ctx: PhaseContext, graph, verbose: bool = False):
        """Default barrier schedule: phases strictly in order, one
        ``run_phase`` call each, posterior store updated at the phase
        boundary. Returns ``(outcomes, phase_times_s, spans)``; spans is
        empty — per-block dispatch→resolve timing only exists under an
        overlapped schedule."""
        self._reset_run_state()
        outcomes: Dict[Coord, BlockOutcome] = {}
        phase_times: Dict[str, float] = {}
        for phase, tasks in graph:
            missing = {d for t in tasks for d in t.deps} - set(ctx.U_posts)
            assert not missing, f"phase {phase} scheduled before {missing}"
            t0 = time.time()
            outs = self.run_phase(ctx, phase, tasks)
            dropped = {t.coord for t in tasks} - set(outs)
            assert not dropped, f"executor {self.name} dropped blocks {dropped}"
            for t in tasks:
                # chain-health guard at block resolution: retry / degrade /
                # raise BEFORE the posterior reaches the store (and with it
                # every successor and the final aggregation)
                o = _commit_guard(ctx, t, outs[t.coord])
                outs[t.coord] = o
                ctx.U_posts[t.coord] = o.U_post
                ctx.V_posts[t.coord] = o.V_post
                ctx.note_resolved(t, o)
            dt = time.time() - t0
            phase_times[phase] = dt
            outcomes.update(outs)
            if verbose:
                print(f"[pp:{self.name}] phase {phase}: {len(tasks)} "
                      f"block(s) {_phase_desc(ctx, tasks)} {dt:.2f}s",
                      flush=True)
        return outcomes, phase_times, {}


def _phase_desc(ctx: PhaseContext, tasks: Sequence[BlockTask]) -> str:
    tags = [g for g in _TAG_ORDER if any(t.phase == g for t in tasks)]
    return " ".join(
        f"{g}[{sum(1 for t in tasks if t.phase == g)}blk "
        f"{ctx.shapes[g].n_rows}x{ctx.shapes[g].n_cols} "
        f"m={ctx.shapes[g].m_rows}/{ctx.shapes[g].m_cols}]" for g in tags)


class SerialExecutor(Executor):
    """One jitted Gibbs call + host sync per block (reference semantics,
    bit-for-bit today's ``run_pp`` loop). Composes with an intra-block
    ``distributed_mesh``: each block's chain is itself shard_map'd.
    A ``topology`` (block must be 1 — serial runs one block at a time)
    is the unified way to say the same thing: its single group's 'data'
    mesh becomes the intra-block mesh."""
    name = "serial"

    def __init__(self, distributed_mesh=None, record_trace: bool = False,
                 topology: Optional[Topology] = None):
        super().__init__(record_trace=record_trace)
        if topology is not None:
            if distributed_mesh is not None:
                raise ValueError("pass distributed_mesh OR topology, not both")
            if topology.block != 1:
                raise ValueError(
                    f"serial executor runs one block at a time — a topology "
                    f"with block={topology.block} device groups needs the "
                    f"sharded/async/streaming executor")
            if topology.data > 1:
                distributed_mesh = topology.data_mesh(0)
        self.distributed_mesh = distributed_mesh

    def run_phase(self, ctx, phase, tasks):
        out: Dict[Coord, BlockOutcome] = {}
        for t in tasks:
            blk = ctx.part.block(t.i, t.j)
            up, vp = ctx.priors(t)
            self._record("dispatch", t.coord)
            t0 = time.time()
            try:
                ctx.check_dispatch(t.coord)
                res = PP.run_block(ctx.keys[t.i, t.j], blk, ctx.block_cfg(t),
                                   ctx.test_p, up, vp, self.distributed_mesh,
                                   shapes=ctx.shapes[t.phase],
                                   poison_nan=ctx.should_poison(t.coord))
                jax.block_until_ready(res.U)
                self._record("resolve", t.coord)
                out[t.coord] = _outcome(res, blk, time.time() - t0)
            except _DISPATCH_ERRORS:
                self._record("resolve", t.coord)
                out[t.coord] = _commit_guard(ctx, t, None, kind="dispatch")
        return out


def _task_leaves(ctx: PhaseContext, task: BlockTask):
    """Device-ready leaves for one block — pp.pad_block_inputs is the
    single source of truth for bucket padding, shared with run_block, so
    stacked chains are identical to serial ones by construction."""
    blk = ctx.part.block(task.i, task.j)
    up, vp = ctx.priors(task)
    csr_r, csr_c, tr, tc, _, _, up, vp = PP.pad_block_inputs(
        blk, ctx.shapes[task.phase], ctx.cfg.K, ctx.test_p, up, vp,
        poison_nan=ctx.should_poison(task.coord))
    return ((csr_r.idx, csr_r.val, csr_r.mask),
            (csr_c.idx, csr_c.val, csr_c.mask),
            jnp.asarray(tr), jnp.asarray(tc), up, vp)


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class StackedExecutor(Executor):
    """One jitted vmapped Gibbs call per phase shape bucket: all blocks of
    the bucket run as a leading batch axis inside a single executable.
    The stacked input leaves are donated to XLA by default (they are
    per-bucket copies nothing else holds)."""
    name = "stacked"
    block_mesh = None      # ShardedExecutor sets this

    def __init__(self, donate: bool = True, record_trace: bool = False):
        super().__init__(record_trace=record_trace)
        self.donate = donate

    def run_phase(self, ctx, phase, tasks):
        out: Dict[Coord, BlockOutcome] = {}
        for tag in _TAG_ORDER:
            group = [t for t in tasks if t.phase == tag]
            if group:
                out.update(self._run_bucket(ctx, tag, group))
        return out

    def _batch_pad(self, n_tasks: int) -> int:
        if self.block_mesh is None:
            return 0
        n_dev = self.block_mesh.devices.size
        return (-n_tasks) % n_dev

    def _run_bucket(self, ctx, tag, group):
        s = ctx.shapes[tag]
        t0 = time.time()
        for t in group:
            self._record("dispatch", t.coord)
        # dispatch-failure injection/handling: flagged blocks are excluded
        # from the bucket (per-block vmapped chains are independent, so the
        # rest of the bucket is unaffected) and healed individually through
        # the shared retry runner
        failed = []
        ok = []
        for t in group:
            try:
                ctx.check_dispatch(t.coord)
                ok.append(t)
            except _DISPATCH_ERRORS:
                failed.append(t)
        out: Dict[Coord, BlockOutcome] = {}
        for t in failed:
            self._record("resolve", t.coord)
            out[t.coord] = _commit_guard(ctx, t, None, kind="dispatch")
        if not ok:
            return out
        group = ok
        leaves = _stack_trees([_task_leaves(ctx, t) for t in group])
        rows_arrs, cols_arrs, test_rows, test_cols, up, vp = leaves
        ii = np.array([t.i for t in group])
        jj = np.array([t.j for t in group])
        keys = ctx.keys[ii, jj]
        pad = self._batch_pad(len(group))
        sel = np.arange(len(group))
        if pad:
            # round the batch up to the block mesh size by repeating the
            # last block (its duplicate results are dropped below)
            sel = np.concatenate([sel, np.full(pad, len(group) - 1)])
            rows_arrs, cols_arrs, test_rows, test_cols, up, vp = jax.tree.map(
                lambda x: x[sel],
                (rows_arrs, cols_arrs, test_rows, test_cols, up, vp))
            keys = keys[sel]
        res = self._dispatch_stacked(
            ctx, s, keys, [group[i] for i in sel],
            PaddedCSR(*rows_arrs, n_cols=s.n_cols),
            PaddedCSR(*cols_arrs, n_cols=s.n_rows),
            test_rows, test_cols, ctx.block_cfg(group[0]), up, vp)
        jax.block_until_ready(res.U)
        for t in group:
            self._record("resolve", t.coord)
        per = (time.time() - t0) / len(group)
        for b, t in enumerate(group):
            blk = ctx.part.block(t.i, t.j)
            res_b = jax.tree.map(lambda x: x[b], res)
            out[t.coord] = _outcome(res_b, blk, per)
        return out

    def _dispatch_stacked(self, ctx, s, keys, tasks, csr_r, csr_c,
                          test_rows, test_cols, cfg, up, vp):
        """Bucket-dispatch seam: the stacked executor runs one vmapped
        executable; the sharded executor overrides placement (1-D 'block'
        mesh, or the composed 2-D chain when its topology has a 'data'
        axis). ``tasks`` lists the batch's tasks AFTER padding (duplicates
        included) so overrides can assemble per-block host planes."""
        return GIBBS.run_gibbs_stacked(
            keys, csr_r, csr_c, test_rows, test_cols, cfg,
            U_prior=up, V_prior=vp, block_mesh=self.block_mesh,
            donate=self.donate)


def _stacked_csrt(ctx, tasks, s, n_shards: int, scatter: bool):
    """Host-assembled per-shard transposed planes for a stacked batch —
    (B, S, D_pad, m_cols) numpy leaves feeding the composed chain's
    'psum'/'scatter' V-step (``distributed.shard_transposed_planes``).
    ``tasks`` may contain batch-padding duplicates; the O(nnz) host
    assembly runs once per distinct block and duplicates are stacked by
    reference."""
    from repro.core import distributed as DIST
    N_pad = ((s.n_rows + n_shards - 1) // n_shards) * n_shards
    D_pad = (((s.n_cols + n_shards - 1) // n_shards) * n_shards
             if scatter else s.n_cols)
    cache: Dict[Coord, tuple] = {}
    for t in tasks:
        if t.coord not in cache:
            blk = ctx.part.block(t.i, t.j)
            cache[t.coord] = DIST.shard_transposed_planes(
                blk.coo.row, blk.coo.col, blk.coo.val, n_shards, N_pad,
                D_pad, s.m_cols)
    planes = [cache[t.coord] for t in tasks]
    return tuple(np.stack([p[k] for p in planes]) for k in range(3))


class ShardedExecutor(StackedExecutor):
    """StackedExecutor with the bucket batch placed by a ``Topology``.

    data == 1 (default): the historical 1-D 'block' mesh — the stacked
    batch shard_map'd so blocks of a phase run concurrently on separate
    devices with NO collective inside a phase.

    data > 1: the paper's combined system — the batch splits over the
    'block' axis (device groups) while each block's Gibbs sweep runs the
    intra-block distributed chain over the 'data' axis
    (``distributed.run_gibbs_stacked_2d``). ``comm`` picks the intra-block
    exchange: 'gather' (factor exchange, chain-parity with serial),
    'psum' (ref [16] item-stat reduction), 'scatter' (§Perf H6
    reduce-scatter). Either way no collective EVER runs on the 'block'
    axis — posterior summaries return to the host at the phase boundary,
    which is the paper's entire communication budget."""
    name = "sharded"

    def __init__(self, topology=None, donate: bool = True,
                 record_trace: bool = False, comm: str = "gather"):
        super().__init__(donate=donate, record_trace=record_trace)
        self.topology = Topology.from_spec(topology)
        self.comm = comm
        # data==1 keeps the legacy single-level executable; the base class
        # dispatch seam reads block_mesh
        self.block_mesh = (self.topology.block_mesh()
                           if self.topology.data == 1 else None)
        if self.topology.data > 1 and self.topology.n_devices > 1:
            self.devices = self.topology.devices

    def _batch_pad(self, n_tasks: int) -> int:
        return (-n_tasks) % self.topology.block

    def _dispatch_stacked(self, ctx, s, keys, tasks, csr_r, csr_c,
                          test_rows, test_cols, cfg, up, vp):
        if self.topology.data == 1:
            return super()._dispatch_stacked(ctx, s, keys, tasks, csr_r,
                                             csr_c, test_rows, test_cols,
                                             cfg, up, vp)
        from repro.core import distributed as DIST
        csrt = (None if self.comm == "gather" else
                _stacked_csrt(ctx, tasks, s, self.topology.data,
                              scatter=(self.comm == "scatter")))
        return DIST.run_gibbs_stacked_2d(
            keys, csr_r, csr_c, test_rows, test_cols, cfg, self.topology,
            U_prior=up, V_prior=vp, donate=self.donate, comm=self.comm,
            csrt=csrt)


def critical_path_priority(tasks: Dict[Coord, BlockTask],
                           est: Dict[Coord, float],
                           succ: Optional[Dict[Coord, List[Coord]]] = None
                           ) -> Dict[Coord, float]:
    """Bottom-level of every task: its estimated cost plus the longest
    estimated chain through its successors — the same dependency-aware
    list-schedule depth ``PPResult.modeled_parallel_s`` schedules measured
    times with, computed a priori from cost estimates. Dispatching ready
    blocks in DESCENDING bottom-level order (critical-path-first) closes
    the longest chain earliest, which is where skewed grids lose time under
    FIFO dispatch: a near-empty phase-b block can otherwise delay the dense
    column of phase-c blocks behind it. ``succ`` may be passed pre-built
    (``_dep_state`` shares its copy)."""
    if succ is None:
        succ = {c: [] for c in tasks}
        for t in tasks.values():
            for d in t.deps:
                succ[d].append(t.coord)
    memo: Dict[Coord, float] = {}

    def bottom(c: Coord) -> float:
        if c not in memo:
            memo[c] = (est.get(c, 0.0)
                       + max((bottom(s) for s in succ[c]), default=0.0))
        return memo[c]

    return {c: bottom(c) for c in tasks}


def _block_cost_estimates(ctx: PhaseContext,
                          tasks: Dict[Coord, BlockTask]) -> Dict[Coord, float]:
    """A-priori per-block cost proxy for priority dispatch: the block's nnz
    (+1 so empty blocks still order deterministically). Within a shape
    bucket the padded compute is nominally shape-bound, but the fused
    kernel's nnz-aware tile skip and the test-entry count both track nnz,
    and on skewed grids nnz spans orders of magnitude."""
    return {c: float(ctx.part.block(t.i, t.j).coo.nnz + 1)
            for c, t in tasks.items()}


def _dep_state(ctx: PhaseContext, graph, priority: bool, make_queue=None):
    """Shared ready-queue scaffolding for the overlapped schedulers
    (async + streaming): task/phase maps, readiness counters, successor
    lists, and the priority ready queue seeded with the dep-free blocks.
    ``make_queue(prio, tasks)`` lets callers substitute a queue type (the
    streaming executor uses a per-group view). Returns
    ``(tasks, phase_of, waiting, succ, ready)``."""
    tasks = {t.coord: t for _, ts in graph for t in ts}
    phase_of = {t.coord: ph for ph, ts in graph for t in ts}
    # a resumed graph is pruned: deps satisfied by restored blocks don't
    # count toward readiness, and restored blocks appear in no succ list
    waiting = {c: sum(1 for d in t.deps if d in tasks)
               for c, t in tasks.items()}
    succ: Dict[Coord, List[Coord]] = {c: [] for c in tasks}
    for t in tasks.values():
        for d in t.deps:
            if d in succ:
                succ[d].append(t.coord)
    prio = (critical_path_priority(tasks, _block_cost_estimates(ctx, tasks),
                                   succ=succ)
            if priority else None)
    ready = make_queue(prio, tasks) if make_queue else _ReadyQueue(prio)
    for c, w in waiting.items():
        if w == 0:
            ready.push(c)
    return tasks, phase_of, waiting, succ, ready


class _ReadyQueue:
    """Priority ready queue shared by the async and streaming schedulers:
    pops in descending critical-path (bottom-level) order, FIFO among ties
    — with priorities disabled it degenerates to the PR-3 FIFO exactly."""

    def __init__(self, prio: Optional[Dict[Coord, float]] = None):
        import heapq
        self._heapq = heapq
        self._prio = prio or {}
        self._seq = 0
        self._heap: List[Tuple[float, int, Coord]] = []

    def push(self, c: Coord):
        self._heapq.heappush(self._heap,
                             (-self._prio.get(c, 0.0), self._seq, c))
        self._seq += 1

    def pop(self) -> Coord:
        return self._heapq.heappop(self._heap)[2]

    def __len__(self):
        return len(self._heap)

    def __bool__(self):
        return bool(self._heap)


class _GroupedReadyQueue:
    """Streaming ready queue: a global priority heap for lead selection
    plus one heap per chunk-group key, so forming a chunk is O(W log n)
    instead of draining and re-pushing the whole queue whenever many
    groups interleave (hundreds of phase-c blocks behind a lone phase-b
    lead on the oversized grids streaming targets). Entries popped
    through one view are lazily skipped in the other."""

    def __init__(self, prio, group_of):
        self._prio = prio
        self._group_of = group_of
        self._global = _ReadyQueue(prio)
        self._groups: Dict = {}
        self._taken: set = set()
        self._n = 0

    def push(self, c: Coord):
        self._global.push(c)
        self._groups.setdefault(self._group_of(c),
                                _ReadyQueue(self._prio)).push(c)
        self._n += 1

    def __len__(self):
        return self._n

    def __bool__(self):
        return self._n > 0

    def pop_chunk(self, max_n: int) -> List[Coord]:
        """Highest-priority ready block plus up to ``max_n - 1`` more from
        its group, in priority order."""
        while True:
            lead = self._global.pop()
            if lead not in self._taken:
                break
        self._taken.add(lead)
        self._n -= 1
        take = [lead]
        grp = self._groups[self._group_of(lead)]
        while grp and len(take) < max_n:
            c = grp.pop()
            if c in self._taken:
                continue
            self._taken.add(c)
            self._n -= 1
            take.append(c)
        return take


class _GroupHealth:
    """Per-device-group health ledger shared by the overlapped schedulers
    (async + streaming): per-group EWMA rates, consecutive-expiry
    counters, and the quarantined set.

    Rate model (the watchdog/speculation cost calibration): ``rate(g)``
    is an EWMA (alpha=0.4) of group ``g``'s observed seconds per
    estimated cost unit — per-group, replacing the single global
    fastest-rate, which mis-sizes deadlines ~Nx too tight on any group
    slower than the fastest. Each group's FIRST observed resolve spans
    that group's executable compile and is excluded entirely (the
    per-group twin of the old global first-resolve skip). A group that
    has not yet calibrated inherits the fastest calibrated rate
    (``global_rate``); before ANY group calibrates every rate is 0.0 and
    deadlines fall back to the generous floor — the same cold-start
    behavior as before.

    Quarantine: ``note_expiry`` counts CONSECUTIVE expiries per group
    (any resolve resets the count) and returns True when the count
    crosses ``quarantine_after`` — the caller then drains the group.
    """

    ALPHA = 0.4

    def __init__(self, n_groups: int, quarantine_after: int):
        self.n = max(1, int(n_groups))
        self.quarantine_after = max(1, int(quarantine_after))
        self._rate = [0.0] * self.n     # EWMA s/cost; 0 = uncalibrated
        self._seen = [False] * self.n   # first resolve = compile span
        self.consec = [0] * self.n      # consecutive expiries
        self.quarantined: set = set()

    def healthy(self) -> List[int]:
        return [g for g in range(self.n) if g not in self.quarantined]

    @property
    def global_rate(self) -> float:
        cal = [r for r in self._rate if r > 0.0]
        return min(cal) if cal else 0.0

    def rate(self, g: int) -> float:
        return self._rate[g] if self._rate[g] > 0.0 else self.global_rate

    def observe(self, g: int, obs: float):
        if not self._seen[g]:
            self._seen[g] = True
            return
        if obs <= 0.0:
            return
        r = self._rate[g]
        self._rate[g] = (obs if r == 0.0
                         else (1 - self.ALPHA) * r + self.ALPHA * obs)

    def note_resolve(self, g: int):
        self.consec[g] = 0

    def note_expiry(self, g: int) -> bool:
        """True when this expiry crosses the quarantine threshold — the
        caller quarantines the group. Already-quarantined groups never
        re-trip."""
        if g in self.quarantined:
            return False
        self.consec[g] += 1
        return self.consec[g] >= self.quarantine_after

    def quarantine(self, g: int):
        self.quarantined.add(g)


@dataclass
class _Flight:
    """One in-flight dispatch attempt on a device group — a single block
    (async) or a window chunk (streaming). Multiple flights for the same
    work = a speculative twin pair. ``sup`` is the group-level injection
    verdict for this dispatch (0 healthy / wall-clock gate / inf dead),
    applied at the completion-observation seam like ``is_hung``."""
    sig: object                            # completion scalar/vector
    out: object                            # BlockOutcome | {coord: outcome}
    td: float                              # dispatch wall time
    group: int
    sup: float = 0.0
    tasks: Optional[List[BlockTask]] = None  # streaming chunk members


def _maybe_degrade_topology(ctx: PhaseContext, health: _GroupHealth):
    """Graceful topology degradation, checked after every quarantine:
    fewer healthy groups than ``FaultPolicy.min_groups`` (or none at all)
    flushes the checkpoint, then continues on the survivors or raises
    ``TopologyDegradedError`` per ``FaultPolicy.on_group_fault``."""
    pol = ctx.policy
    survivors = health.healthy()
    if len(survivors) >= pol.min_groups:
        return
    if ctx.ckpt is not None:
        ctx.ckpt.flush()
    if pol.on_group_fault == "continue" and survivors:
        return
    dead = sorted(health.quarantined)
    raise TopologyDegradedError(
        f"{len(survivors)} healthy device group(s) left (quarantined: "
        f"{dead}), below min_groups={pol.min_groups} "
        f"(on_group_fault={pol.on_group_fault!r}; checkpoint flushed)",
        dead_groups=dead)


class AsyncExecutor(Executor):
    """Dependency-driven overlapped schedule riding JAX async dispatch.

    Readiness counters over ``BlockTask.deps`` replace the phase barrier:
    each block is dispatched (one jitted per-block chain, the SAME bucketed
    executable the serial executor compiles — ≤4 compilations per run) the
    moment both of its prior sources have resolved, so phase-c blocks whose
    phase-b dependencies finished early start while the slowest phase-b
    bucket is still running. The host never blocks on bulk results:

      * completion detection polls ``is_ready()`` on a per-block scalar
        (masked Σ(pred-val)², doubling as the block's RMSE numerator) and
        only falls back to blocking on the OLDEST in-flight scalar when
        nothing has resolved — the device queue keeps draining either way;
      * posterior summaries (trimmed device slices) go straight into the
        context store and feed successors without touching the host;
      * padded per-block input buffers are donated to XLA
        (``run_gibbs(donate=True)``): U0/V0 are rewritten in place as the
        U/V outputs, the rest is released at dispatch where the runtime
        supports it — and holding ONE block's planes at a time instead of a
        whole stacked bucket is itself the larger live-footprint cut
        (``bench_roofline --gibbs-peak`` measures both);
      * with >1 device, ready blocks are assigned to the LEAST-LOADED
        healthy device group (per-group streams, zero inter-group
        collectives; priors device_put to the target group are the
        phase-boundary O(K²) summaries — the paper's whole budget); a
        group of >1 devices runs the block's chain 'data'-sharded
        (``distributed.run_gibbs_group``, intra-group collectives only).
        Each group holds at most ``depth`` blocks in flight; the rest of
        its share stays STAGED (assigned but undispatched), which is what
        makes the elastic layer possible: an idle group STEALS the
        highest-priority staged block from the most-loaded group, a group
        whose dispatches expire ``quarantine_after`` consecutive times is
        QUARANTINED (staged share re-queued, in-flight blocks rebalanced
        onto healthy groups under the same keys), and a straggling
        dispatch past ``speculate_at ×`` the group's own rate estimate is
        SPECULATIVELY twinned on an idle group — resolution commits the
        deterministic canonical-group winner and cancels the twin, so
        results stay bitwise identical to the fault-free run. With ONE
        group all of this is inert and dispatch is unbounded (legacy
        behavior).

    ``record_trace=True`` appends (event, coord, group) events to
    ``self.trace`` in real order (see ``Executor`` for the schema); the
    stress tests use it to assert no block ever dispatches before its
    dependencies resolved. ``_is_resolved`` is the completion-detection
    seam tests override to fake arbitrary completion orders.

    ``priority=True`` (default) pops the ready queue critical-path-first:
    ready blocks are ordered by their bottom-level (estimated cost + the
    longest estimated chain through their successors,
    ``critical_path_priority``), so on skewed grids the dense phase-b
    blocks that gate whole phase-c rows/columns dispatch before the
    near-empty stragglers. ``priority=False`` restores plain FIFO.
    """
    name = "async"

    def __init__(self, donate: bool = True, block_mesh=None,
                 record_trace: bool = False, priority: bool = True,
                 topology: Optional[Topology] = None, comm: str = "gather",
                 depth: int = 2):
        super().__init__(record_trace=record_trace)
        if topology is None:
            # legacy spellings: a 1-D 'block' mesh (or None = all local
            # devices) means single-device streams
            topology = Topology.from_spec(block_mesh)
        elif block_mesh is not None:
            raise ValueError("pass block_mesh OR topology, not both")
        else:
            topology = Topology.from_spec(topology)
        if int(depth) < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.topology = topology
        self.comm = comm
        self.donate = donate
        self.devices = topology.devices
        self.priority = priority
        self.depth = int(depth)    # per-group in-flight cap (multi-group)
        self._n_dispatched = 0

    def run_phase(self, ctx, phase, tasks):
        raise NotImplementedError(
            "AsyncExecutor overlaps phases — it schedules whole graphs "
            "(run_graph), not single phases")

    # -- completion-detection seam (tests fake completion order here) -----
    def _is_resolved(self, coord: Coord, signal) -> bool:
        return signal.is_ready()

    def _reset_run_state(self):
        super()._reset_run_state()
        self._n_dispatched = 0

    def run_graph(self, ctx, graph, verbose: bool = False):
        self._reset_run_state()
        tasks, phase_of, waiting, succ, ready = _dep_state(
            ctx, graph, self.priority)
        est = _block_cost_estimates(ctx, tasks)
        pol = ctx.policy
        G = max(1, self.topology.block)
        health = _GroupHealth(G, pol.quarantine_after)
        elastic = G > 1    # one group: nowhere to rebalance/steal/twin
        cap = self.depth if elastic else None   # per-group in-flight cap
        # per-group staged share (assigned, undispatched — the steal pool)
        staged = [_ReadyQueue(ready._prio) for _ in range(G)]
        flights: Dict[Coord, List[_Flight]] = {}  # >1 = speculative twins
        outcomes: Dict[Coord, BlockOutcome] = {}
        spans: Dict[Coord, Tuple[float, float]] = {}
        first_d: Dict[str, float] = {}
        last_r: Dict[str, float] = {}
        remaining = {ph: len(ts) for ph, ts in graph}
        t0 = time.time()

        def n_inflight(g):
            return sum(1 for fl in flights.values()
                       for f in fl if f.group == g)

        def n_assigned(g):
            return len(staged[g]) + n_inflight(g)

        def pick_group():
            return min(health.healthy(), key=lambda g: (n_assigned(g), g))

        def deadline(c, f):
            # per-group watchdog deadline: generous floor + slack × the
            # group's OWN calibrated rate (EWMA seconds/cost; cold groups
            # inherit the fastest calibrated rate, 0 until any group
            # calibrates — early blocks get the floor alone). A false
            # expiry is benign: re-dispatch reuses attempt-0 keys, so a
            # slow-but-alive block still resolves bitwise-identically.
            return (pol.timeout_floor_s
                    + pol.timeout_slack * health.rate(f.group) * est[c])

        def flight_ready(c, f):
            if ctx.is_hung(c):
                return False
            if f.sup and time.time() < f.sup:
                return False
            return self._is_resolved(c, f.sig)

        def retire(c, out, td, kind=None, group=None):
            self._record("resolve", c, group)
            out = _commit_guard(ctx, tasks[c], out, kind=kind)
            tr = time.time()
            if not out.seconds:
                out.seconds = tr - td
            if kind is None and group is not None:
                # per-group EWMA rate; the group's first resolve (compile
                # span) is dropped inside observe()
                health.observe(group, out.seconds / est[c])
            spans[c] = (td - t0, tr - t0)
            outcomes[c] = out
            ctx.note_resolved(tasks[c], out)
            ph = phase_of[c]
            remaining[ph] -= 1
            last_r[ph] = tr - t0
            if verbose and remaining[ph] == 0:
                ts = [t for t in tasks.values() if phase_of[t.coord] == ph]
                print(f"[pp:{self.name}] phase {ph}: {len(ts)} block(s) "
                      f"{_phase_desc(ctx, ts)} "
                      f"{last_r[ph] - first_d[ph]:.2f}s "
                      f"(dispatch→resolve envelope; phases overlap)",
                      flush=True)
            for s in succ[c]:
                waiting[s] -= 1
                if waiting[s] == 0:
                    ready.push(s)

        def dispatch_on(c, g, event):
            """Dispatch block ``c`` on group ``g``. Returns False when the
            dispatch failed (already healed through the retire path)."""
            self._record(event, c, g)
            td = time.time()
            first_d.setdefault(phase_of[c], td - t0)
            ordinal = ctx.next_group_ordinal(g)
            sup = ctx.group_suppressed_until(g, ordinal, td)
            try:
                sig, out = self._dispatch(ctx, tasks[c], group=g)
            except _DISPATCH_ERRORS:
                retire(c, None, td, kind="dispatch", group=g)
                return False
            flights.setdefault(c, []).append(
                _Flight(sig=sig, out=out, td=td, group=g, sup=sup))
            return True

        def quarantine_group(g, trigger):
            """Drain group ``g``: no future dispatch targets it, its
            staged share returns to the global ready queue, and its
            in-flight blocks rebalance onto healthy groups under the SAME
            keys (kind="group" — no block retry budget is consumed)."""
            health.quarantine(g)
            self._record("quarantine", trigger, g)
            self.n_quarantined += 1
            ctx.record_fault(trigger, "group", "quarantined")
            _maybe_degrade_topology(ctx, health)      # may raise (ckpt
            while staged[g]:                          # already flushed)
                ready.push(staged[g].pop())
            for c2 in list(flights):
                fl = flights.get(c2, [])
                mine = [f for f in fl if f.group == g]
                if not mine:
                    continue
                keep = [f for f in fl if f.group != g]
                if keep:
                    # its healthy twin flies on: this side just cancels
                    for f in mine:
                        self._record("cancel", c2, g)
                        self.n_cancels += 1
                    flights[c2] = keep
                    continue
                flights.pop(c2)
                self._record("expire", c2, g)
                ctx.record_fault(c2, "group", "rebalanced")
                dispatch_on(c2, pick_group(), "redispatch")

        def handle_expiries(now):
            """Watchdog sweep: expire overdue flights, count consecutive
            expiries toward quarantine, re-dispatch or terminally retire.
            Returns True when any state changed."""
            changed = False
            for c in list(flights):
                fl = flights.get(c)
                if fl is None:
                    continue
                dead = [f for f in fl if now - f.td > deadline(c, f)]
                if not dead:
                    continue
                changed = True
                live = [f for f in fl if f not in dead]
                if live:
                    # the twin flies on — the expired side only cancels
                    flights[c] = live
                    for f in dead:
                        self._record("cancel", c, f.group)
                        self.n_cancels += 1
                        if elastic and health.note_expiry(f.group):
                            quarantine_group(f.group, c)
                    continue
                flights.pop(c)
                self._record("expire", c, dead[0].group)
                for f in dead[1:]:
                    self._record("cancel", c, f.group)
                    self.n_cancels += 1
                for f in dead:
                    if elastic and health.note_expiry(f.group):
                        quarantine_group(f.group, c)
                if ctx.cur_attempt(c) < pol.max_retries:
                    ctx.record_fault(c, "timeout", "redispatched")
                    ctx.attempts[c] = ctx.cur_attempt(c) + 1
                    dispatch_on(c, pick_group(), "redispatch")
                else:
                    retire(c, None, dead[0].td, kind="timeout",
                           group=dead[0].group)
            return changed

        def maybe_speculate(now):
            """Straggler hedge: a sole flight past ``speculate_at ×`` its
            group's calibrated deadline model is twinned on an idle
            healthy group with the SAME attempt-0 key."""
            if not elastic or pol.speculate_at <= 0.0:
                return
            for c in list(flights):
                fl = flights.get(c)
                if fl is None or len(fl) != 1:
                    continue
                f = fl[0]
                r = health.rate(f.group)
                if r <= 0.0 or now - f.td <= pol.speculate_at * r * est[c]:
                    continue
                idle = [g for g in health.healthy()
                        if g != f.group and not staged[g]
                        and (cap is None or n_inflight(g) < cap)]
                if not idle:
                    continue
                g2 = min(idle, key=lambda g: (n_assigned(g), g))
                td = time.time()
                ordinal = ctx.next_group_ordinal(g2)
                sup = ctx.group_suppressed_until(g2, ordinal, td)
                try:
                    sig, out = self._dispatch(ctx, tasks[c], group=g2)
                except _DISPATCH_ERRORS:
                    continue    # the primary still flies; skip the twin
                self._record("speculate", c, g2)
                self.n_speculations += 1
                fl.append(_Flight(sig=sig, out=out, td=td, group=g2,
                                  sup=sup))

        def await_progress():
            """Adaptive-sleep poll until a flight resolves or the watchdog
            changes state (expiry/quarantine). ``watchdog=False`` restores
            the legacy block-on-oldest fallback, which deadlocks if the
            oldest in-flight block died — keep it on."""
            if not pol.watchdog:
                c0 = min(flights, key=lambda c: flights[c][0].td)
                jax.block_until_ready(flights[c0][0].sig)
                return
            sleep = 5e-5
            while flights:
                if any(flight_ready(c, f) for c, fl in flights.items()
                       for f in fl):
                    return
                now = time.time()
                if handle_expiries(now):
                    return
                maybe_speculate(now)
                time.sleep(sleep)
                sleep = min(sleep * 2, 2e-3)

        while ready or any(staged) or flights:
            # assign fresh ready blocks to the least-loaded healthy group
            while ready:
                staged[pick_group()].push(ready.pop())
            progress = False
            for g in health.healthy():
                while staged[g] and (cap is None or n_inflight(g) < cap):
                    dispatch_on(staged[g].pop(), g, "dispatch")
                    progress = True
            if elastic and not progress:
                # work stealing: an idle healthy group takes the highest-
                # priority STAGED block from the most-loaded group
                for g in health.healthy():
                    if staged[g] or (cap is not None
                                     and n_inflight(g) >= cap):
                        continue
                    victims = [h for h in health.healthy()
                               if h != g and staged[h]]
                    if not victims:
                        continue
                    v = max(victims, key=lambda h: (n_assigned(h), -h))
                    c = staged[v].pop()
                    self._record("steal", c, g)
                    self.n_steals += 1
                    dispatch_on(c, g, "dispatch")
                    progress = True
            if progress or not flights:
                continue
            await_progress()
            resolved = [c for c, fl in flights.items()
                        if any(flight_ready(c, f) for f in fl)]
            for c in resolved:
                fl = flights.pop(c, None)
                if fl is None:
                    continue
                rd = [f for f in fl if flight_ready(c, f)]
                if not rd:
                    flights[c] = fl
                    continue
                # deterministic winner: canonical group order among the
                # READY flights — twins share the attempt-0 key so either
                # outcome is bitwise the fault-free numbers, and the
                # canonical rule keeps the committed handles/trace
                # independent of wall-clock completion order
                win = min(rd, key=lambda f: f.group)
                for f in fl:
                    if f is not win:
                        self._record("cancel", c, f.group)
                        self.n_cancels += 1
                # the store may hold a losing twin's handles (written at
                # its dispatch) — successors must consume the winner's
                ctx.U_posts[c] = win.out.U_post
                ctx.V_posts[c] = win.out.V_post
                health.note_resolve(win.group)
                retire(c, win.out, win.td, group=win.group)
        # per-phase envelopes: first dispatch → last resolve. Phases
        # overlap, so these may sum to MORE than the wall time.
        phase_times = {ph: last_r[ph] - first_d[ph] for ph in first_d}
        return outcomes, phase_times, spans

    def _dispatch(self, ctx: PhaseContext, task: BlockTask,
                  group: Optional[int] = None):
        """Dispatch one block's jitted chain without waiting for anything:
        inputs may still be computing (JAX chains the dataflow) and no
        output is synced. ``group`` is the scheduler-chosen target device
        group (None = legacy round-robin). Returns (completion scalar,
        device outcome)."""
        ctx.check_dispatch(task.coord)
        blk = ctx.part.block(task.i, task.j)
        s = ctx.shapes[task.phase]
        up, vp = ctx.priors(task)
        csr_r, csr_c, tr, tc, tv, tmask, up, vp = PP.pad_block_inputs(
            blk, s, ctx.cfg.K, ctx.test_p, up, vp,
            poison_nan=ctx.should_poison(task.coord))
        n_obs = int(tmask.sum())
        key = ctx.keys[task.i, task.j]
        topo = self.topology
        g = (self._n_dispatched % topo.block) if group is None \
            else int(group)
        if topo.n_devices > 1:
            # per-GROUP streams: the block's padded planes plus the O(K²)
            # prior summaries move to the target group — the latter IS the
            # paper's phase-boundary communication, made explicit. With
            # data == 1 a group is the single device of the legacy
            # round-robin; with data > 1 the planes are replicated across
            # the group and the chain shards its sweep over them.
            if topo.data == 1:
                target = topo.group(g)[0]
            else:
                from jax.sharding import NamedSharding, PartitionSpec
                target = NamedSharding(topo.group_mesh_2d(g),
                                       PartitionSpec())
            (ra, ca, tr, tc, up, vp, tv, tmask, key) = jax.device_put(
                ((csr_r.idx, csr_r.val, csr_r.mask),
                 (csr_c.idx, csr_c.val, csr_c.mask),
                 tr, tc, up, vp, tv, tmask, key), target)
            csr_r = PaddedCSR(*ra, n_cols=csr_r.n_cols)
            csr_c = PaddedCSR(*ca, n_cols=csr_c.n_cols)
        self._n_dispatched += 1
        if topo.data > 1:
            from repro.core import distributed as DIST
            csrt = (None if self.comm == "gather" else
                    tuple(x[0] for x in _stacked_csrt(
                        ctx, [task], s, topo.data,
                        scatter=(self.comm == "scatter"))))
            res = DIST.run_gibbs_group(
                key, csr_r, csr_c, jnp.asarray(tr), jnp.asarray(tc),
                ctx.block_cfg(task), topo, group=g, U_prior=up, V_prior=vp,
                donate=self.donate, comm=self.comm, csrt=csrt)
        else:
            res = GIBBS.run_gibbs(key, csr_r, csr_c,
                                  jnp.asarray(tr), jnp.asarray(tc),
                                  ctx.block_cfg(task), U_prior=up,
                                  V_prior=vp, donate=self.donate)
        nr, nc = len(blk.row_ids), len(blk.col_ids)
        U_post = RowGaussians(eta=res.U_post.eta[:nr],
                              Lambda=res.U_post.Lambda[:nr])
        V_post = RowGaussians(eta=res.V_post.eta[:nc],
                              Lambda=res.V_post.Lambda[:nc])
        sq = _block_sq_err(res.acc.pred_sum, res.acc.pred_cnt,
                           jnp.asarray(tv), jnp.asarray(tmask))
        # device-resident store write happens AT DISPATCH: successors (and
        # the final jitted aggregation) consume these handles as dataflow
        ctx.U_posts[task.coord] = U_post
        ctx.V_posts[task.coord] = V_post
        out = BlockOutcome(U_post=U_post, V_post=V_post,
                           pred_mean=None, seconds=0.0,
                           sq_err=sq, n_obs=n_obs, health=res.health)
        return sq, out


# Per-block masked Σ(pred-val)² over a (W, n_test) window chunk — the SAME
# scalar as _block_sq_err, batched: one tiny (W,) vector is the chunk's
# completion signal AND its RMSE numerators.
_chunk_sq_err = jax.jit(jax.vmap(_block_sq_err))


def _dummy_prior(n: int, K: int) -> RowGaussians:
    """Placeholder prior rows for flag=0 slots of a window chunk. Never
    selected (the per-block flag routes those blocks to the resampled NW
    hyperprior); only has to be finite so the unused ``where`` branch is
    well-defined."""
    return RowGaussians(eta=jnp.zeros((n, K)),
                        Lambda=jnp.broadcast_to(jnp.eye(K), (n, K, K)))


@dataclass
class _StagedChunk:
    """A window chunk whose host→device transfer has been issued (the
    prefetch): device leaves + per-block metadata, waiting to dispatch."""
    tasks: List[BlockTask]        # true tasks, ≤ W (repeat-padded to W)
    shape: "PP.BlockShapes"
    cfg: BMF.BMFConfig
    dev: Tuple                    # (ri, rv, rm, ci, cv, cm, tr, tc, tv, tm)
    keys: jax.Array               # (W,) typed PRNG keys
    U_prior: RowGaussians         # (W, n_rows, ...) padded (dummies where off)
    V_prior: RowGaussians
    u_use: jax.Array              # (W,) {0,1} prior flags
    v_use: jax.Array
    n_obs: List[int]
    group: int = 0                # topology device group this chunk targets


class StreamingExecutor(Executor):
    """Bounded-window streaming schedule for out-of-memory block grids.

    The stacked executor materializes a whole phase bucket on device at
    once — ``num_blocks_in_bucket × block_bytes`` — which web-scale grids
    (thousands of blocks) cannot co-resident in HBM. This executor runs the
    SAME dependency-driven ready queue as the async scheduler but moves
    blocks through a bounded window of ``W`` donated block buffers:

      * ready blocks are popped critical-path-first (``_ReadyQueue`` over
        ``critical_path_priority``) and grouped into chunks of up to W
        blocks sharing one window shape and chain config (short chunks are
        repeat-padded to exactly W so ONE executable serves every chunk);
      * each chunk's CSR planes/test entries are assembled on the HOST
        (``pp.pad_block_inputs_host``) and shipped with one async
        ``device_put`` — the double-buffered prefetch: the next chunk's
        H2D transfer runs while the current chunk computes;
      * chunks dispatch through ``gibbs.run_gibbs_stacked(donate=True)``:
        XLA recycles the window buffers (U0/V0 alias the U/V outputs, the
        planes return to the allocator), so the live input footprint is
        ``≤ W × (depth + 1) × block_bytes`` — flat in the grid size
        (``peak_window_blocks`` records the realized bound;
        ``bench_roofline --gibbs-peak`` measures it);
      * completion is detected by non-blocking ``is_ready()`` polls on each
        chunk's (W,) squared-error vector, falling back to blocking on the
        OLDEST in-flight chunk only — same contract as the async executor,
        and the same ``_is_resolved`` seam for the conformance fake-delay
        stress;
      * per-phase shape buckets are COALESCED first
        (``pp.BlockShapes.coalesce`` / ``partition.coalesce_shapes``):
        buckets within the waste budget share one window shape, and the
        per-block prior flags (``run_gibbs_stacked(prior_use=...)``) let
        that single executable serve phase-a/b/c blocks despite their
        different prior structures.

    Per-block chains are the stacked executor's vmapped semantics (same
    keys, same padding), so RMSE matches serial to batched-fp tolerance
    and results are bit-identical across runs regardless of how completion
    timing regroups the chunks.

    ``max_waste`` defaults to 1.0 — only bit-identical shapes merge, which
    preserves exact chain parity with the serial/stacked reference (the
    padded row count feeds the NW hyper-resample and the RNG shapes, so
    ANY padding change perturbs the chains). Raising it trades that strict
    parity for fewer window executables and a single recycled buffer pool:
    results remain valid Gibbs chains, just not the reference's draws.
    """
    name = "streaming"

    def __init__(self, window: int = 4, donate: bool = True,
                 max_waste: float = 1.0, priority: bool = True,
                 depth: int = 2, record_trace: bool = False,
                 topology: Optional[Topology] = None, comm: str = "gather"):
        super().__init__(record_trace=record_trace)
        if int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if int(depth) < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.window = int(window)
        self.donate = donate
        self.max_waste = max_waste
        self.priority = priority
        self.depth = int(depth)               # in-flight chunks before block
        self.topology = Topology.from_spec(topology) if topology is not None \
            else Topology(block=1, data=1)
        if comm != "gather":
            # window chunks use prior_use-flagged executables; only the
            # 'gather' intra-group exchange composes with them (and at
            # data == 1 no other mode means anything)
            raise ValueError(
                f"streaming executor supports comm='gather' only, "
                f"got {comm!r}")
        self.comm = comm
        if self.topology.n_devices > 1:
            self.devices = self.topology.devices
        self.peak_window_blocks = 0           # realized live-buffer bound
        self.window_shapes: Optional[Dict[str, "PP.BlockShapes"]] = None

    def run_phase(self, ctx, phase, tasks):
        raise NotImplementedError(
            "StreamingExecutor streams whole graphs through its window "
            "(run_graph), not single phases")

    # -- completion-detection seam (tests fake completion order here) -----
    def _is_resolved(self, coord: Coord, signal) -> bool:
        return signal.is_ready()

    def _group_key(self, ctx, task, shapes):
        cfg = ctx.block_cfg(task)
        return (id(shapes[task.phase]), cfg.n_samples, cfg.burnin)

    def _pop_chunk(self, ctx, ready: _GroupedReadyQueue,
                   tasks) -> List[BlockTask]:
        """Up to W ready blocks sharing the top-priority block's window
        shape and chain config — priority order within the group."""
        return [tasks[c] for c in ready.pop_chunk(self.window)]

    def _group_target(self, g: int):
        """device_put destination for group ``g``'s window buffers: the
        group's device (data == 1) or a replicated sharding over its
        (1, data) submesh — the per-STREAM prefetch lands the H2D transfer
        on the group that will compute the chunk."""
        if self.topology.n_devices == 1:
            return None
        if self.topology.data == 1:
            return self.topology.group(g)[0]
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.topology.group_mesh_2d(g),
                             PartitionSpec())

    def _stage(self, ctx: PhaseContext, chunk: List[BlockTask],
               shapes, group: int = 0) -> _StagedChunk:
        """Assemble one chunk on the host and issue its (async) H2D
        transfer to the target group. Deps are resolved (the chunk came
        off the ready queue), so the device-resident priors are read here
        too — moving them to the group is the phase-boundary O(K²)
        communication, made explicit."""
        s = shapes[chunk[0].phase]
        K = ctx.cfg.K
        W = self.window
        sel = list(range(len(chunk))) + [len(chunk) - 1] * (W - len(chunk))
        host = [PP.pad_block_inputs_host(ctx.part.block(t.i, t.j), s,
                                         ctx.test_p,
                                         poison_nan=ctx.should_poison(t.coord))
                for t in chunk]

        def stack(get):
            return np.stack([get(host[i]) for i in sel])

        host_leaves = (stack(lambda h: h[0].idx), stack(lambda h: h[0].val),
                       stack(lambda h: h[0].mask),
                       stack(lambda h: h[1].idx), stack(lambda h: h[1].val),
                       stack(lambda h: h[1].mask),
                       stack(lambda h: h[2]), stack(lambda h: h[3]),
                       stack(lambda h: h[4]), stack(lambda h: h[5]))
        target = self._group_target(group)
        # ONE async transfer per chunk, onto the chunk's group
        dev = (jax.device_put(host_leaves) if target is None
               else jax.device_put(host_leaves, target))

        ups, vps, uf, vf = [], [], [], []
        for t in chunk:
            up, vp = ctx.priors(t)
            uf.append(float(up is not None))
            vf.append(float(vp is not None))
            ups.append(PP._pad_prior(up, s.n_rows, K) if up is not None
                       else _dummy_prior(s.n_rows, K))
            vps.append(PP._pad_prior(vp, s.n_cols, K) if vp is not None
                       else _dummy_prior(s.n_cols, K))
        sel_tasks = [chunk[i] for i in sel]
        ii = np.array([t.i for t in sel_tasks])
        jj = np.array([t.j for t in sel_tasks])
        U_pri = _stack_trees([ups[i] for i in sel])
        V_pri = _stack_trees([vps[i] for i in sel])
        keys = ctx.keys[ii, jj]
        if target is not None:
            # posteriors may live on another group: colocate prior
            # summaries and keys with the chunk's window buffers
            U_pri, V_pri, keys = jax.device_put((U_pri, V_pri, keys), target)
        return _StagedChunk(
            tasks=chunk, shape=s, cfg=ctx.block_cfg(chunk[0]), dev=dev,
            keys=keys, U_prior=U_pri, V_prior=V_pri,
            u_use=jnp.asarray([uf[i] for i in sel], jnp.float32),
            v_use=jnp.asarray([vf[i] for i in sel], jnp.float32),
            n_obs=[int(h[5].sum()) for h in host], group=group)

    def _dispatch(self, ctx: PhaseContext, st: _StagedChunk):
        """Dispatch one staged chunk; returns (signal, outcomes). The
        window buffers are donated — after this call nothing holds them
        and XLA recycles their storage for the next chunk."""
        ri, rv, rm, ci, cv, cm, tr, tc, tv, tmask = st.dev
        csr_r = PaddedCSR(ri, rv, rm, n_cols=st.shape.n_cols)
        csr_c = PaddedCSR(ci, cv, cm, n_cols=st.shape.n_rows)
        if self.topology.data > 1:
            from repro.core import distributed as DIST
            res = DIST.run_gibbs_stacked_2d(
                st.keys, csr_r, csr_c, tr, tc, st.cfg, self.topology,
                U_prior=st.U_prior, V_prior=st.V_prior,
                prior_use=(st.u_use, st.v_use), donate=self.donate,
                comm=self.comm,
                mesh=self.topology.group_mesh_2d(st.group))
        else:
            res = GIBBS.run_gibbs_stacked(
                st.keys, csr_r, csr_c, tr, tc, st.cfg,
                U_prior=st.U_prior, V_prior=st.V_prior,
                prior_use=(st.u_use, st.v_use), donate=self.donate)
        sq = _chunk_sq_err(res.acc.pred_sum, res.acc.pred_cnt, tv, tmask)
        outs: Dict[Coord, BlockOutcome] = {}
        for b, t in enumerate(st.tasks):      # padded duplicates dropped
            blk = ctx.part.block(t.i, t.j)
            nr, nc = len(blk.row_ids), len(blk.col_ids)
            U_post = RowGaussians(eta=res.U_post.eta[b, :nr],
                                  Lambda=res.U_post.Lambda[b, :nr])
            V_post = RowGaussians(eta=res.V_post.eta[b, :nc],
                                  Lambda=res.V_post.Lambda[b, :nc])
            ctx.U_posts[t.coord] = U_post
            ctx.V_posts[t.coord] = V_post
            outs[t.coord] = BlockOutcome(
                U_post=U_post, V_post=V_post, pred_mean=None, seconds=0.0,
                sq_err=sq[b], n_obs=st.n_obs[b],
                health=(res.health[b] if res.health is not None else None))
        return sq, outs

    def _reset_run_state(self):
        super()._reset_run_state()
        self.peak_window_blocks = 0
        self.window_shapes = None

    def run_graph(self, ctx, graph, verbose: bool = False):
        self._reset_run_state()
        shapes = PP.BlockShapes.coalesce(ctx.shapes, ctx.cfg.K,
                                         self.max_waste)
        tasks, phase_of, waiting, succ, ready = _dep_state(
            ctx, graph, self.priority,
            make_queue=lambda prio, ts: _GroupedReadyQueue(
                prio, lambda c: self._group_key(ctx, ts[c], shapes)))
        self.window_shapes = shapes
        G = self.topology.block
        pol = ctx.policy
        health = _GroupHealth(G, pol.quarantine_after)
        elastic = G > 1    # one group: nowhere to rebalance/steal/twin
        if verbose:
            n_buckets = len({id(s) for s in shapes.values()})
            print(f"[pp:{self.name}] window={self.window} depth={self.depth} "
                  f"{n_buckets} coalesced bucket(s) over {len(shapes)} phase "
                  f"tag(s), {G} stream group(s) x {self.topology.data} "
                  f"device(s)", flush=True)

        # one W-bounded donated window PER DEVICE GROUP: each group runs
        # its own stream of chunks (own prefetch slot + its share of the
        # in-flight chunk flights, capped at ``depth``)
        staged: List[Optional[_StagedChunk]] = [None] * G
        flights: Dict[int, _Flight] = {}    # flight id -> chunk flight
        twin: Dict[int, int] = {}           # speculative twin links (both ways)
        fid_next = [0]
        outcomes: Dict[Coord, BlockOutcome] = {}
        spans: Dict[Coord, Tuple[float, float]] = {}
        first_d: Dict[str, float] = {}
        last_r: Dict[str, float] = {}
        remaining = {ph: len(ts) for ph, ts in graph}
        t0 = time.time()

        def n_inflight(g):
            return sum(1 for f in flights.values() if f.group == g)

        def note_peak():
            live = self.window * (len(flights)
                                  + sum(st is not None for st in staged))
            self.peak_window_blocks = max(self.peak_window_blocks, live)

        est = _block_cost_estimates(ctx, tasks)

        def chunk_cost(ts_):
            return sum(est[t.coord] for t in ts_)

        def deadline(f):
            # per-group watchdog deadline over the chunk's total estimated
            # cost (one executable runs all its members); the group's OWN
            # EWMA rate, cold groups inherit the fastest calibrated one
            return (pol.timeout_floor_s + pol.timeout_slack
                    * health.rate(f.group) * chunk_cost(f.tasks))

        def flight_ready(f):
            if any(ctx.is_hung(t.coord) for t in f.tasks):
                return False
            if f.sup and time.time() < f.sup:
                return False
            return self._is_resolved(f.tasks[0].coord, f.sig)

        def retire(t, out, td, tr_, per, kind=None, group=None):
            c = t.coord
            self._record("resolve", c, group)
            out = _commit_guard(ctx, tasks[c], out, kind=kind)
            if not out.seconds:
                out.seconds = per
            spans[c] = (td - t0, tr_ - t0)
            outcomes[c] = out
            ctx.note_resolved(tasks[c], out)
            ph = phase_of[c]
            remaining[ph] -= 1
            last_r[ph] = tr_ - t0
            if verbose and remaining[ph] == 0:
                ts2 = [t2 for t2 in tasks.values()
                       if phase_of[t2.coord] == ph]
                print(f"[pp:{self.name}] phase {ph}: {len(ts2)} "
                      f"block(s) {_phase_desc(ctx, ts2)} "
                      f"{last_r[ph] - first_d[ph]:.2f}s "
                      f"(dispatch→resolve envelope; phases overlap)",
                      flush=True)
            for s2 in succ[c]:
                waiting[s2] -= 1
                if waiting[s2] == 0:
                    ready.push(s2)

        def launch(ch: _StagedChunk, event: str) -> int:
            """Dispatch a staged chunk on its group; returns the flight
            id. The chunk's dispatch consumes one group ordinal (the
            group-level injection unit)."""
            g = ch.group
            td = time.time()
            for t in ch.tasks:
                self._record(event, t.coord, g)
                first_d.setdefault(phase_of[t.coord], td - t0)
            ordinal = ctx.next_group_ordinal(g)
            sup = ctx.group_suppressed_until(g, ordinal, td)
            sig, outs = self._dispatch(ctx, ch)
            fid = fid_next[0]
            fid_next[0] += 1
            flights[fid] = _Flight(sig=sig, out=outs, td=td, group=g,
                                   sup=sup, tasks=ch.tasks)
            note_peak()
            return fid

        def least_loaded():
            return min(health.healthy(), key=lambda g: (n_inflight(g), g))

        def stage_next(g) -> Optional[_StagedChunk]:
            """Pop + stage the group's next chunk, healing dispatch-failure
            injections at chunk formation (the flagged block never joins
            the window; the rest of the chunk is unaffected)."""
            while ready:
                chunk = self._pop_chunk(ctx, ready, tasks)
                good = []
                for t in chunk:
                    try:
                        ctx.check_dispatch(t.coord)
                        good.append(t)
                    except _DISPATCH_ERRORS:
                        self._record("dispatch", t.coord, g)
                        now = time.time()
                        first_d.setdefault(phase_of[t.coord], now - t0)
                        retire(t, None, now, time.time(), 0.0,
                               kind="dispatch", group=g)
                if good:
                    return self._stage(ctx, good, shapes, group=g)
            return None

        def quarantine_group(g, trigger):
            """Drain group ``g``: its staged window buffers are RELEASED
            (the chunk's blocks return to the ready queue, dropping the
            device leaves), and its in-flight chunks re-stage on healthy
            groups under the same keys (kind="group" — no block retry
            budget consumed)."""
            health.quarantine(g)
            self._record("quarantine", trigger, g)
            self.n_quarantined += 1
            ctx.record_fault(trigger, "group", "quarantined")
            _maybe_degrade_topology(ctx, health)      # may raise (ckpt
            if staged[g] is not None:                 # already flushed)
                for t in staged[g].tasks:
                    ready.push(t.coord)
                staged[g] = None
            for fid in [i for i, f in flights.items() if f.group == g]:
                f = flights.pop(fid)
                tw = twin.pop(fid, None)
                if tw is not None:
                    # its healthy twin flies on: this side just cancels
                    twin.pop(tw, None)
                    for t in f.tasks:
                        self._record("cancel", t.coord, g)
                    self.n_cancels += len(f.tasks)
                    continue
                for t in f.tasks:
                    self._record("expire", t.coord, g)
                    ctx.record_fault(t.coord, "group", "rebalanced")
                st2 = self._stage(ctx, f.tasks, shapes,
                                  group=least_loaded())
                launch(st2, "redispatch")

        def handle_expiries(now):
            """Watchdog sweep over the chunk flights; True on any state
            change (expiry, quarantine, redispatch, terminal retire)."""
            changed = False
            for fid in list(flights):
                f = flights.get(fid)
                if f is None or now - f.td <= deadline(f):
                    continue
                changed = True
                flights.pop(fid)
                tw = twin.pop(fid, None)
                if tw is not None and tw in flights:
                    # the twin flies on — the expired side only cancels
                    twin.pop(tw, None)
                    for t in f.tasks:
                        self._record("cancel", t.coord, f.group)
                    self.n_cancels += len(f.tasks)
                    if elastic and health.note_expiry(f.group):
                        quarantine_group(f.group, f.tasks[0].coord)
                    continue
                for t in f.tasks:
                    self._record("expire", t.coord, f.group)
                if elastic and health.note_expiry(f.group):
                    quarantine_group(f.group, f.tasks[0].coord)
                if all(ctx.cur_attempt(t.coord) < pol.max_retries
                       for t in f.tasks):
                    # re-stage on the least-loaded healthy group with the
                    # same keys — a slow-but-alive chunk re-resolves to
                    # bitwise-identical numbers
                    for t in f.tasks:
                        ctx.record_fault(t.coord, "timeout", "redispatched")
                        ctx.attempts[t.coord] = ctx.cur_attempt(t.coord) + 1
                    st2 = self._stage(ctx, f.tasks, shapes,
                                      group=least_loaded())
                    launch(st2, "redispatch")
                else:
                    for t in f.tasks:
                        retire(t, None, f.td, now, 0.0, kind="timeout",
                               group=f.group)
            return changed

        def maybe_speculate(now):
            """Straggler hedge: an untwinned chunk past ``speculate_at ×``
            its group's calibrated deadline model re-stages on an idle
            healthy group with the SAME keys."""
            if not elastic or pol.speculate_at <= 0.0:
                return
            for fid in list(flights):
                f = flights.get(fid)
                if f is None or fid in twin:
                    continue
                r = health.rate(f.group)
                if (r <= 0.0 or now - f.td
                        <= pol.speculate_at * r * chunk_cost(f.tasks)):
                    continue
                idle = [g for g in health.healthy()
                        if g != f.group and staged[g] is None
                        and n_inflight(g) < self.depth]
                if not idle:
                    continue
                g2 = min(idle, key=lambda g: (n_inflight(g), g))
                for t in f.tasks:
                    self._record("speculate", t.coord, g2)
                self.n_speculations += len(f.tasks)
                try:
                    st2 = self._stage(ctx, f.tasks, shapes, group=g2)
                    td = time.time()
                    ordinal = ctx.next_group_ordinal(g2)
                    sup = ctx.group_suppressed_until(g2, ordinal, td)
                    sig, outs = self._dispatch(ctx, st2)
                except _DISPATCH_ERRORS:
                    for t in f.tasks:
                        self._record("cancel", t.coord, g2)
                    self.n_cancels += len(f.tasks)
                    continue    # the primary still flies; skip the twin
                fid2 = fid_next[0]
                fid_next[0] += 1
                flights[fid2] = _Flight(sig=sig, out=outs, td=td, group=g2,
                                        sup=sup, tasks=f.tasks)
                twin[fid] = fid2
                twin[fid2] = fid
                note_peak()

        def await_flights():
            """Adaptive poll until a chunk resolves or the watchdog
            changes state; ``watchdog=False`` restores the legacy
            block-on-oldest-chunk fallback."""
            if not pol.watchdog:
                f0 = min(flights.values(), key=lambda f: f.td)
                jax.block_until_ready(f0.sig)
                return
            sleep = 5e-5
            while flights:
                if any(flight_ready(f) for f in flights.values()):
                    return
                now = time.time()
                if handle_expiries(now):
                    return
                maybe_speculate(now)
                time.sleep(sleep)
                sleep = min(sleep * 2, 2e-3)

        while (ready or any(st is not None for st in staged) or flights):
            progress = False
            for g in health.healthy():
                # fair staging: every idle group stages ONE chunk before
                # any group prefetches a second — a greedy first group
                # would starve the rest of the mesh whenever the DAG
                # releases blocks a few at a time
                if staged[g] is None and ready:
                    staged[g] = stage_next(g)
                    note_peak()
            for g in health.healthy():
                if staged[g] is not None and n_inflight(g) < self.depth:
                    ch, staged[g] = staged[g], None
                    launch(ch, "dispatch")
                    # per-stream double-buffered prefetch: the group's NEXT
                    # chunk's H2D transfer overlaps this chunk's compute
                    if ready:
                        staged[g] = stage_next(g)
                        note_peak()
                    progress = True
            if elastic and not progress:
                # work stealing: an idle healthy group re-stages the
                # staged chunk of the most-loaded group onto itself
                for g in health.healthy():
                    if (staged[g] is not None or ready
                            or n_inflight(g) >= self.depth):
                        continue
                    victims = [h for h in health.healthy()
                               if h != g and staged[h] is not None]
                    if not victims:
                        continue
                    v = max(victims, key=lambda h: (n_inflight(h), -h))
                    ch, staged[v] = staged[v], None
                    for t in ch.tasks:
                        self._record("steal", t.coord, g)
                    self.n_steals += len(ch.tasks)
                    st2 = self._stage(ctx, ch.tasks, shapes, group=g)
                    launch(st2, "dispatch")
                    progress = True
            if progress or not flights:
                continue
            await_flights()
            for fid in [i for i, f in flights.items() if flight_ready(f)]:
                f = flights.get(fid)
                if f is None:       # its twin already committed this work
                    continue
                tw = twin.pop(fid, None)
                if tw is not None and tw in flights:
                    twin.pop(tw, None)
                    # deterministic winner: canonical group order among
                    # the READY sides (twins share keys, so either is the
                    # fault-free bitwise result)
                    cand = [x for x in (fid, tw)
                            if flights.get(x) is not None
                            and flight_ready(flights[x])]
                    win_id = min(cand, key=lambda x: flights[x].group)
                    lose_id = tw if win_id == fid else fid
                    loser = flights.pop(lose_id)
                    for t in loser.tasks:
                        self._record("cancel", t.coord, loser.group)
                    self.n_cancels += len(loser.tasks)
                    f = flights.pop(win_id)
                    # successors must consume the winner's dataflow, not
                    # whichever twin wrote the store last
                    for t in f.tasks:
                        ctx.U_posts[t.coord] = f.out[t.coord].U_post
                        ctx.V_posts[t.coord] = f.out[t.coord].V_post
                else:
                    flights.pop(fid)
                tr_ = time.time()
                # one executable ran the whole chunk: split its wall evenly
                # across members (mirrors StackedExecutor's bucket split)
                per = (tr_ - f.td) / len(f.tasks)
                health.observe(f.group, (tr_ - f.td) / chunk_cost(f.tasks))
                health.note_resolve(f.group)
                for t in f.tasks:
                    retire(t, f.out[t.coord], f.td, tr_, per,
                           group=f.group)
        phase_times = {ph: last_r[ph] - first_d[ph] for ph in first_d}
        return outcomes, phase_times, spans


EXECUTORS: Dict[str, type] = {
    "serial": SerialExecutor,
    "stacked": StackedExecutor,
    "sharded": ShardedExecutor,
    "async": AsyncExecutor,
    "streaming": StreamingExecutor,
}
"""Executor registry. ``run_pp(executor=<name>)`` resolves here, and the
conformance suite (tests/test_executor_conformance.py) parametrizes over
exactly these names — registering a new executor auto-enrolls it in the
battery (fixed-key RMSE parity, bitwise determinism, dependency-safe
dispatch trace, transfer-guard-clean aggregation). Every executor class
must accept ``record_trace=`` and report dispatch/resolve events honestly.
"""


def make_executor(spec, distributed_mesh=None, block_mesh=None,
                  window=None, topology=None) -> Executor:
    """Resolve run_pp's ``executor=`` argument: a registry name or an
    instance. ``topology`` is the unified 2-D ('block', 'data') placement
    (core.topology.Topology, an ``(block, data)`` pair, or a legacy 1-D
    mesh) consumed by the serial (block must be 1), sharded, async, and
    streaming executors. An intra-block ``distributed_mesh`` is the legacy
    spelling of ``topology=Topology(block=1, data=S)`` and forces the
    serial executor. ``window`` is the streaming executor's window size
    (ignored by the others)."""
    if isinstance(spec, Executor):
        for arg, name in ((distributed_mesh, "distributed_mesh"),
                          (window, "window"), (topology, "topology")):
            if arg is not None:
                raise ValueError(
                    f"{name} with an Executor instance is ambiguous — "
                    f"construct the executor with it yourself or pass the "
                    f"executor by name")
        return spec
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if distributed_mesh is not None:
        if topology is not None:
            raise ValueError("pass distributed_mesh OR topology, not both")
        spec = "serial"
    if spec not in EXECUTORS:
        raise ValueError(f"unknown executor {spec!r} "
                         f"(expected {' | '.join(EXECUTORS)})")
    topo = None if topology is None else Topology.from_spec(topology)
    if spec == "stacked" and topo is not None:
        raise ValueError(
            "the stacked executor is single-executable (no device "
            "placement) — use executor='sharded' with a topology")
    factories = {
        "serial": lambda: SerialExecutor(distributed_mesh, topology=topo),
        "stacked": lambda: StackedExecutor(),
        "sharded": lambda: ShardedExecutor(
            topo if topo is not None else block_mesh),
        "async": lambda: AsyncExecutor(block_mesh=block_mesh,
                                       topology=topo),
        "streaming": lambda: StreamingExecutor(
            topology=topo,
            **({} if window is None else {"window": int(window)})),
    }
    # a registered executor without a dedicated factory gets default
    # construction — never a silent fallthrough to a different class
    factory = factories.get(spec, EXECUTORS[spec])
    return factory()


def _run_meta(key, part: Partition, cfg: BMF.BMFConfig) -> Dict:
    """The fields that determine a PP run's numbers — written to the
    checkpoint's meta.json and validated on resume. Deliberately excludes
    the executor/topology: block chains are executor-independent, so a run
    checkpointed on 8 devices legitimately resumes on 1 (the
    fault-tolerance story) and still finishes bitwise-identical."""
    return {
        "format": 1,
        "I": part.I, "J": part.J, "K": cfg.K,
        "n_samples": cfg.n_samples, "burnin": cfg.burnin,
        "phase_bc_samples": cfg.phase_bc_samples,
        "key": np.asarray(jax.random.key_data(key)).tolist(),
    }


def _restore_resume(ctx: PhaseContext, resume_from, meta: Dict):
    """Load a checkpoint directory's resolved blocks into the context:
    posteriors into the device store (successors read them as priors) and
    finished BlockOutcomes into ``ctx.resumed`` (their tasks are pruned
    from the executed graph). Validates the directory's meta against this
    run first — a mismatch is a usage error, named after resume_from."""
    from repro.checkpoint.ckpt import PPCheckpoint
    saved = PPCheckpoint.read_meta(resume_from)
    for k, v in meta.items():
        if saved.get(k) != v:
            raise ValueError(
                f"resume_from={str(resume_from)!r} was written by a "
                f"different run: {k} is {saved.get(k)!r} there but {v!r} "
                f"here — resume requires identical grid, K, chain config "
                f"and PRNG key")
    for (i, j), d in PPCheckpoint.load_blocks(resume_from).items():
        if not (0 <= i < ctx.part.I and 0 <= j < ctx.part.J):
            raise ValueError(
                f"resume_from={str(resume_from)!r} holds block ({i}, {j}) "
                f"outside this run's {ctx.part.I}x{ctx.part.J} grid")
        U_post = RowGaussians(eta=jnp.asarray(d["U_eta"]),
                              Lambda=jnp.asarray(d["U_Lambda"]))
        V_post = RowGaussians(eta=jnp.asarray(d["V_eta"]),
                              Lambda=jnp.asarray(d["V_Lambda"]))
        ctx.U_posts[(i, j)] = U_post
        ctx.V_posts[(i, j)] = V_post
        ctx.resumed[(i, j)] = BlockOutcome(
            U_post=U_post, V_post=V_post, pred_mean=None, seconds=0.0,
            sq_err=jnp.asarray(float(d["sq"])), n_obs=int(d["n_obs"]),
            health=jnp.asarray(True))


def run_phase_graph(key, part: Partition, cfg: BMF.BMFConfig, test: COO,
                    executor: Executor, verbose: bool = False,
                    policy: Optional[FaultPolicy] = None,
                    fault_plan: Optional[FaultPlan] = None,
                    checkpoint_dir=None, ckpt_every: int = 1,
                    resume_from=None) -> "PP.PPResult":
    """Execute the PP phase graph with ``executor`` and aggregate — the
    engine behind ``pp.run_pp``.

    Fault tolerance: every resolved block passes the chain-health guard
    (``_commit_guard``) under ``policy`` before its posterior reaches any
    successor; ``fault_plan`` is the deterministic injection seam the
    chaos tests drive. ``checkpoint_dir`` persists each resolved block's
    posterior through ``checkpoint.ckpt.PPCheckpoint`` (flushed even when
    a block fault raises), and ``resume_from`` restores such a directory:
    restored blocks are pruned from the graph and the finished run is
    bitwise-identical to an uninterrupted one (float32 posteriors
    round-trip exactly; pending blocks re-run under their original keys).
    """
    I, J = part.I, part.J
    t_start = time.time()
    test_p = apply_permutation(test, part.row_perm, part.col_perm)
    keys = jax.random.split(key, I * J).reshape(I, J)
    shapes = PP.BlockShapes.per_phase(part, test_p)
    ctx = PhaseContext(part=part, cfg=cfg, test_p=test_p, keys=keys,
                       shapes=shapes,
                       policy=policy if policy is not None else FaultPolicy(),
                       fault_plan=fault_plan)
    meta = _run_meta(key, part, cfg)
    if resume_from is not None:
        _restore_resume(ctx, resume_from, meta)
        if verbose and ctx.resumed:
            print(f"[pp] resumed {len(ctx.resumed)} block(s) from "
                  f"{resume_from}", flush=True)
    if checkpoint_dir is not None:
        from repro.checkpoint.ckpt import PPCheckpoint
        ctx.ckpt = PPCheckpoint(checkpoint_dir, every=ckpt_every)
        ctx.ckpt.write_meta(meta)

    full_graph = build_phase_graph(part)
    # a resumed block's task is pruned: the executor never re-runs it, and
    # _dep_state counts only intra-graph deps toward readiness
    graph = [(ph, pending) for ph, tasks in full_graph
             if (pending := [t for t in tasks if t.coord not in ctx.resumed])]
    # static pre-dispatch validation: the graph the executor is about to
    # drain must be acyclic with every dep in-graph or pre-resolved — a
    # rewired prior_from or an over-pruned resume fails HERE, not as a
    # hang inside an executor's ready loop
    from repro.analysis import trace_passes as _TRACE_LINT
    _bad = _TRACE_LINT.check_graph(
        {t.coord: list(t.deps) for _, ts in graph for t in ts},
        resolved=set(ctx.resumed))
    if _bad:
        raise ValueError("invalid phase graph: "
                         + "; ".join(v.message for v in _bad))
    if graph:
        try:
            outcomes, phase_times, spans = executor.run_graph(
                ctx, graph, verbose=verbose)
        finally:
            # a BlockFaultError (or any crash) still lands the buffered
            # blocks on disk — that is what makes the directory resumable
            if ctx.ckpt is not None:
                ctx.ckpt.flush()
    else:
        outcomes, phase_times, spans = {}, {}, {}
    if ctx.ckpt is not None:
        ctx.ckpt.flush()
    outcomes.update(ctx.resumed)

    sq_err, n_test = 0.0, 0
    per_block_rmse = np.zeros((I, J))
    block_times: Dict[Coord, float] = {}
    for _, tasks in full_graph:
        for t in tasks:
            o = outcomes[t.coord]
            block_times[t.coord] = o.seconds
            n, sq = _host_sq(ctx, t, o)
            if n:
                sq_err += sq
                n_test += n
                per_block_rmse[t.i, t.j] = float(np.sqrt(sq / n))

    U_posts = [[ctx.U_posts[(i, j)] for j in range(J)] for i in range(I)]
    V_posts = [[ctx.V_posts[(i, j)] for j in range(J)] for i in range(I)]
    if len(executor.devices) > 1:
        # per-device streams leave posteriors scattered; colocate for the
        # single jitted aggregation executable
        U_posts, V_posts = jax.device_put((U_posts, V_posts),
                                          executor.devices[0])
    U_agg = PP._aggregate_axis(part, U_posts, axis="row")
    V_agg = PP._aggregate_axis(part, V_posts, axis="col")

    rmse = float(np.sqrt(sq_err / max(n_test, 1)))
    return PP.PPResult(rmse=rmse, U_agg=U_agg, V_agg=V_agg,
                       per_block_rmse=per_block_rmse,
                       wall_time_s=time.time() - t_start,
                       phase_times_s=phase_times, n_test=n_test,
                       block_times_s=block_times, executor=executor.name,
                       block_spans_s=spans, faults=list(ctx.faults),
                       resumed_blocks=len(ctx.resumed),
                       group_stats=dict(
                           n_quarantined=executor.n_quarantined,
                           n_steals=executor.n_steals,
                           n_speculations=executor.n_speculations,
                           n_cancels=executor.n_cancels),
                       row_perm=part.row_perm, col_perm=part.col_perm,
                       tau=cfg.tau, K=cfg.K)
