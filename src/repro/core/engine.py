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
                   ``Topology(block=1, data=S)`` shards each block's
                   chain over S devices (``distributed.run_gibbs_group``).
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
  AsyncExecutor    dependency-driven overlap: each block's jitted chain is
                   dispatched the moment its row/col prior posteriors
                   resolve, riding JAX async dispatch — phase-c blocks
                   whose phase-b sources finished early start while the
                   rest of phase b is still running. Completion is
                   detected by non-blocking ``is_ready()`` polls on tiny
                   per-block squared-error scalars; posterior summaries
                   stay device-resident between phases, padded input
                   buffers are donated to XLA, and with several topology
                   GROUPS each block lands on the least-loaded one (groups
                   of >1 device run each chain 'data'-sharded).
  StreamingExecutor the same scheduler, but blocks stream through a
                   bounded window of W donated block buffers: host-side
                   chunk assembly + double-buffered ``device_put``
                   prefetch, ``run_gibbs_stacked(donate=True)`` recycling,
                   live peak ≤ W×(depth+1)×block_bytes per group — flat
                   in the grid size, for grids whose stacked buckets
                   exceed HBM.

The async and streaming executors share ONE scheduler
(``_ElasticScheduler``): readiness counters over ``BlockTask.deps``, a
ready queue popped CRITICAL-PATH-FIRST (``critical_path_priority`` —
estimated block cost plus the longest estimated successor chain, the same
dependency-aware list-schedule depth ``PPResult.modeled_parallel_s``
schedules measured times with; FIFO among ties), and the elastic fault
layer (watchdog, quarantine, work stealing, speculation). They differ
only in the unit they schedule (one block, or a chunk of up to W), how a
unit is staged on a group, how it is dispatched, and how many staged and
in-flight units a group may hold.

Device placement is unified behind ``core.topology.Topology`` — a single
2-D ('block', 'data') mesh whose groups run blocks concurrently while the
'data' axis shards each block's Gibbs sweep (the intra-block distributed
chain of core.distributed). ``topology=`` is the one placement argument;
``topology=Topology(block=2, data=2)`` turns any of
sharded/async/streaming into the paper's combined two-level system.

Executor contract
-----------------
``run_graph(ctx, graph, verbose) -> (outcomes, phase_times_s, spans)`` owns
ordering: it must write each block's posterior summaries into ``ctx.U_posts``
/ ``ctx.V_posts`` before any dependent reads them via ``ctx.priors(task)``.
Barrier executors get that for free from the default implementation, which
runs ``run_phase(ctx, phase, tasks) -> {(i, j): BlockOutcome}`` once per
phase after asserting every dep resolved; the async and streaming
executors replace the whole loop with the elastic scheduler. Executors
never aggregate: ``run_phase_graph`` owns RMSE accumulation and the Qin-et-al.
divide-away aggregation (``pp._aggregate_axis``, one jitted device-resident
reduction).

Fault tolerance (see core/README.md): every block's chain computes a
device-resident health scalar (``gibbs.GibbsResult.health``) checked at
resolve time by ``_commit_guard`` — unhealthy blocks retry through one
shared single-block runner (re-split key, jittered prior), then degrade to
their propagated prior or raise per ``FaultPolicy``. The elastic
scheduler's poll loop is watchdog-policed (cost-model deadlines; timed-out
dispatches re-dispatch on the next device group),
``checkpoint_dir``/``resume_from`` persist and restore per-block
posteriors bitwise, and ``FaultPlan`` is the deterministic injection seam
the chaos tests drive every executor with.

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
      unit formation in the elastic scheduler of async and streaming).

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
    devices: Tuple = ()    # devices the run's posteriors may land on

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
    bit-for-bit today's ``run_pp`` loop). Each block runs the async
    executor's per-block unit (``_group_chain``) on group 0 of
    ``topology`` (block must be 1 — serial runs one block at a time):
    with ``data > 1`` the block's chain is 'data'-sharded over the group
    (``distributed.run_gibbs_group``, comm 'gather'), which draws the
    same chain as one device under the same key."""
    name = "serial"

    def __init__(self, record_trace: bool = False,
                 topology: Optional[Topology] = None):
        super().__init__(record_trace=record_trace)
        topology = topology if topology is not None else Topology(1, 1)
        if topology.block != 1:
            raise ValueError(
                f"serial executor runs one block at a time — a topology "
                f"with block={topology.block} device groups needs the "
                f"sharded/async/streaming executor")
        self.topology = topology
        if topology.n_devices > 1:
            self.devices = topology.devices

    def run_phase(self, ctx, phase, tasks):
        out: Dict[Coord, BlockOutcome] = {}
        for t in tasks:
            self._record("dispatch", t.coord)
            t0 = time.time()
            try:
                ctx.check_dispatch(t.coord)
                res, _, _, _ = _group_chain(ctx, t, self.topology, 0)
                jax.block_until_ready(res.U)
                self._record("resolve", t.coord)
                out[t.coord] = _outcome(res, ctx.part.block(t.i, t.j),
                                        time.time() - t0)
            except _DISPATCH_ERRORS:
                self._record("resolve", t.coord)
                out[t.coord] = _commit_guard(ctx, t, None, kind="dispatch")
        return out


def _task_leaves(ctx: PhaseContext, task: BlockTask):
    """Device-ready leaves for one block — pp.pad_block_inputs is the
    single source of truth for bucket padding, shared with the serial
    executor, so stacked chains are identical to serial ones by
    construction."""
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


def _dep_state(ctx: PhaseContext, graph, priority: bool, make_queue):
    """Ready-queue scaffolding of the elastic scheduler: task/phase maps,
    readiness counters, successor lists, and the priority ready queue
    ``make_queue(prio, tasks)`` (the executor's queue type) seeded with
    the dep-free blocks. Returns ``(tasks, phase_of, waiting, succ,
    ready)``."""
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
    ready = make_queue(prio, tasks)
    for c, w in waiting.items():
        if w == 0:
            ready.push(c)
    return tasks, phase_of, waiting, succ, ready


class _ReadyQueue:
    """Priority queue of the elastic scheduler (the async ready queue and
    every group's staged units): pops in descending critical-path
    (bottom-level) order, FIFO among ties — with priorities disabled it
    degenerates to plain FIFO."""

    def __init__(self, prio: Optional[Dict[Coord, float]] = None):
        import heapq
        self._heapq = heapq
        self._prio = prio or {}
        self._seq = 0
        self._heap: List[Tuple[float, int, Coord]] = []

    def push(self, c: Coord, item=None):
        """Queue ``item`` (default: ``c`` itself) at ``c``'s priority."""
        self._heapq.heappush(self._heap, (-self._prio.get(c, 0.0), self._seq,
                                          c if item is None else item))
        self._seq += 1

    def pop(self):
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
        # a coord pushed back after being taken (its staged chunk was
        # released by a quarantine) is live again
        self._taken.discard(c)
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
class _Unit:
    """One dispatch unit staged on device group ``group``: a single block
    (async) or a window chunk of up to W blocks (streaming). ``staged``
    is the executor's payload for it — the chunk's device buffers
    (streaming), or None (async: a block's planes move at dispatch)."""
    tasks: List[BlockTask]
    group: int
    staged: object = None


@dataclass
class _Flight:
    """One in-flight dispatch of a unit. Two flights of the same unit are
    a speculative twin pair. ``sup`` is the group-level injection verdict
    for this dispatch (0 healthy / wall-clock gate / inf dead), applied at
    the completion-observation seam like ``is_hung``."""
    sig: object                            # completion scalar/vector
    out: Dict[Coord, BlockOutcome]
    td: float                              # dispatch wall time
    unit: _Unit
    sup: float = 0.0

    @property
    def group(self) -> int:
        return self.unit.group


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


class _ElasticScheduler:
    """The dependency-driven elastic schedule of the overlapped executors
    (async, streaming) — one instance per ``run_graph`` call.

    Ready blocks (``_dep_state``: readiness counters, critical-path-first
    queue) are formed into dispatch UNITS and staged on the least-loaded
    healthy device group; each group dispatches its staged units up to
    its in-flight cap, and completion is found by polling each flight's
    signal (``Executor._is_resolved``). Around that loop sit the fault
    layers (``FaultPolicy``): per-group ``_GroupHealth`` rates, watchdog
    deadlines over a unit's estimated cost with expiry and re-dispatch
    under the same keys, quarantine with rebalancing, speculative twins
    that commit the canonical-group winner, work stealing from the
    most-loaded group, and the adaptive-sleep poll. Flights are keyed by
    flight id; ``twin`` links the two flights of a speculative pair both
    ways, and only ever links two live flights.

    The executor supplies what differs between the two:

      ``_form_unit(ctx, ready, tasks)``  the next unit's tasks off the
                                          ready queue (async: one block;
                                          streaming: a chunk of ≤ W);
      ``_stage(ctx, tasks, group)``      stage a unit on a group — its
                                          payload (async: None; streaming:
                                          host assembly + one device_put);
      ``_dispatch(ctx, unit)``           dispatch a staged unit →
                                          (completion signal,
                                          {coord: BlockOutcome});
      ``stage_cap`` / ``inflight_cap``   staged units a group may hold and
                                          flights it may have in the air
                                          (None = unbounded).

    An injected dispatch failure (``FaultPlan.fail_dispatch_at``) is
    healed when its unit is formed — the block never joins a unit; a
    runtime failure of the dispatch itself heals the unit's blocks.
    Every unit dispatch consumes one group ordinal (the unit of the
    group-level injections)."""

    def __init__(self, ex: "_OverlappedExecutor", ctx: PhaseContext, graph,
                 verbose: bool):
        self.ex, self.ctx, self.verbose = ex, ctx, verbose
        self.pol = ctx.policy
        (self.tasks, self.phase_of, self.waiting, self.succ,
         self.ready) = _dep_state(
            ctx, graph, ex.priority,
            make_queue=lambda prio, ts: ex._ready_queue(ctx, prio, ts))
        self.est = _block_cost_estimates(ctx, self.tasks)
        G = ex.topology.block
        self.health = _GroupHealth(G, self.pol.quarantine_after)
        self.elastic = G > 1    # one group: nowhere to rebalance/steal/twin
        # per-group staged units (priority-ordered — the steal pool)
        self.staged = [_ReadyQueue(self.ready._prio) for _ in range(G)]
        self.inflight = [0] * G
        self.flights: Dict[int, _Flight] = {}
        self.twin: Dict[int, int] = {}
        self._next_fid = 0
        self.outcomes: Dict[Coord, BlockOutcome] = {}
        self.spans: Dict[Coord, Tuple[float, float]] = {}
        self.first_d: Dict[str, float] = {}
        self.last_r: Dict[str, float] = {}
        self.remaining = {ph: len(ts) for ph, ts in graph}
        self.t0 = time.time()

    # -- the loop ------------------------------------------------------------

    def run(self):
        while self.ready or any(self.staged) or self.flights:
            self.stage_ready()
            progress = False
            for g in self.health.healthy():
                if self.staged[g] and self.has_room(g):
                    self.launch(self.staged[g].pop(), "dispatch")
                    progress = True
            if self.elastic and not progress:
                progress = self.steal()
            if progress or not self.flights:
                continue
            self.await_progress()
            self.resolve_ready()
        # per-phase envelopes: first dispatch → last resolve. Phases
        # overlap, so these may sum to MORE than the wall time.
        phase_times = {ph: self.last_r[ph] - self.first_d[ph]
                       for ph in self.first_d}
        return self.outcomes, phase_times, self.spans

    # -- group load ----------------------------------------------------------

    def load(self, g: int) -> int:
        return len(self.staged[g]) + self.inflight[g]

    def has_room(self, g: int) -> bool:
        cap = self.ex.inflight_cap
        return cap is None or self.inflight[g] < cap

    def least_loaded(self, groups=None) -> int:
        return min(self.health.healthy() if groups is None else groups,
                   key=lambda g: (self.load(g), g))

    def unit_cost(self, unit: _Unit) -> float:
        return sum(self.est[t.coord] for t in unit.tasks)

    def note_peak(self):
        live = len(self.flights) + sum(len(q) for q in self.staged)
        self.ex.peak_units = max(self.ex.peak_units, live)

    # -- staging and dispatch ------------------------------------------------

    def stage_ready(self):
        """Form units off the ready queue and stage each on the
        least-loaded healthy group that has staging room."""
        cap = self.ex.stage_cap
        while self.ready:
            room = [g for g in self.health.healthy()
                    if cap is None or len(self.staged[g]) < cap]
            if not room:
                return
            g = self.least_loaded(room)
            tasks = []
            for t in self.ex._form_unit(self.ctx, self.ready, self.tasks):
                try:
                    self.ctx.check_dispatch(t.coord)
                    tasks.append(t)
                except _InjectedDispatchFailure:
                    self.ex._record("dispatch", t.coord, g)
                    now = time.time()
                    self.first_d.setdefault(self.phase_of[t.coord],
                                            now - self.t0)
                    self.retire(t, None, now, kind="dispatch", group=g)
            if tasks:
                unit = _Unit(tasks, g, self.ex._stage(self.ctx, tasks, g))
                self.staged[g].push(tasks[0].coord, unit)
                self.note_peak()

    def restage(self, unit: _Unit, g: int) -> _Unit:
        return _Unit(unit.tasks, g, self.ex._stage(self.ctx, unit.tasks, g))

    def dispatch(self, unit: _Unit) -> Optional[_Flight]:
        """Dispatch a staged unit without waiting for anything. The
        outcomes' device handles go into the posterior store AT DISPATCH
        (successors and the final aggregation consume them as dataflow).
        None when the runtime refused the dispatch."""
        g = unit.group
        td = time.time()
        ordinal = self.ctx.next_group_ordinal(g)
        sup = self.ctx.group_suppressed_until(g, ordinal, td)
        try:
            sig, outs = self.ex._dispatch(self.ctx, unit)
        except _DISPATCH_ERRORS:
            return None
        for c, o in outs.items():
            self.ctx.U_posts[c], self.ctx.V_posts[c] = o.U_post, o.V_post
        return _Flight(sig=sig, out=outs, td=td, unit=unit, sup=sup)

    def launch(self, unit: _Unit, event: str):
        """Dispatch (or re-dispatch) ``unit`` as its group's new flight;
        a refused dispatch heals the unit's blocks through retire."""
        td = time.time()
        for t in unit.tasks:
            self.ex._record(event, t.coord, unit.group)
            self.first_d.setdefault(self.phase_of[t.coord], td - self.t0)
        f = self.dispatch(unit)
        if f is None:
            for t in unit.tasks:
                self.retire(t, None, td, kind="dispatch", group=unit.group)
            return
        self.add_flight(f)

    def add_flight(self, f: _Flight) -> int:
        fid = self._next_fid
        self._next_fid += 1
        self.flights[fid] = f
        self.inflight[f.group] += 1
        self.note_peak()
        return fid

    def pop_flight(self, fid: int) -> Tuple[_Flight, Optional[int]]:
        """Remove a flight; returns it with its live twin's id (if any),
        unlinking the pair."""
        f = self.flights.pop(fid)
        self.inflight[f.group] -= 1
        tw = self.twin.pop(fid, None)
        if tw is not None:
            self.twin.pop(tw, None)
        return f, tw

    def cancel(self, f: _Flight):
        for t in f.unit.tasks:
            self.ex._record("cancel", t.coord, f.group)
        self.ex.n_cancels += len(f.unit.tasks)

    # -- completion ----------------------------------------------------------

    def flight_ready(self, f: _Flight) -> bool:
        if any(self.ctx.is_hung(t.coord) for t in f.unit.tasks):
            return False
        if f.sup and time.time() < f.sup:
            return False
        return self.ex._is_resolved(f.unit.tasks[0].coord, f.sig)

    def await_progress(self):
        """Adaptive-sleep poll until a flight resolves or the watchdog
        changes state (expiry/quarantine). ``watchdog=False`` restores the
        legacy block-on-oldest fallback, which deadlocks if the oldest
        in-flight unit died — keep it on."""
        if not self.pol.watchdog:
            f0 = min(self.flights.values(), key=lambda f: f.td)
            jax.block_until_ready(f0.sig)
            return
        sleep = 5e-5
        while self.flights:
            if any(self.flight_ready(f) for f in self.flights.values()):
                return
            now = time.time()
            if self.handle_expiries(now):
                return
            self.speculate(now)
            time.sleep(sleep)
            sleep = min(sleep * 2, 2e-3)

    def resolve_ready(self):
        for fid in [i for i, f in self.flights.items()
                    if self.flight_ready(f)]:
            if fid not in self.flights:    # its twin already committed
                continue
            win = fid
            tw = self.twin.get(fid)
            if tw is not None:
                # deterministic winner: canonical group order among the
                # READY sides — twins share the attempt-0 keys, so either
                # is bitwise the fault-free result, and the rule keeps the
                # committed handles independent of wall-clock order
                other = self.flights[tw]
                if (other.group < self.flights[fid].group
                        and self.flight_ready(other)):
                    win = tw
                self.cancel(self.pop_flight(tw if win == fid else fid)[0])
            f, _ = self.pop_flight(win)
            # the store may hold the other twin's handles (written at its
            # dispatch) — successors must consume the winner's
            for c, o in f.out.items():
                self.ctx.U_posts[c], self.ctx.V_posts[c] = o.U_post, o.V_post
            tr = time.time()
            # per-group EWMA rate; the group's first resolve (compile
            # span) is dropped inside observe()
            self.health.observe(f.group, (tr - f.td) / self.unit_cost(f.unit))
            self.health.note_resolve(f.group)
            # one executable ran the whole unit: split its wall evenly
            # across members (mirrors StackedExecutor's bucket split)
            per = (tr - f.td) / len(f.unit.tasks)
            for t in f.unit.tasks:
                self.retire(t, f.out[t.coord], f.td, per, group=f.group)

    def retire(self, t: BlockTask, out: Optional[BlockOutcome], td: float,
               per: Optional[float] = None, kind: Optional[str] = None,
               group: Optional[int] = None):
        """Commit one block: health guard, outcome, checkpoint, successor
        release."""
        c = t.coord
        self.ex._record("resolve", c, group)
        out = _commit_guard(self.ctx, t, out, kind=kind)
        tr = time.time()
        if not out.seconds:
            out.seconds = per if per is not None else tr - td
        self.spans[c] = (td - self.t0, tr - self.t0)
        self.outcomes[c] = out
        self.ctx.note_resolved(t, out)
        ph = self.phase_of[c]
        self.remaining[ph] -= 1
        self.last_r[ph] = tr - self.t0
        if self.verbose and self.remaining[ph] == 0:
            ts = [t2 for t2 in self.tasks.values()
                  if self.phase_of[t2.coord] == ph]
            print(f"[pp:{self.ex.name}] phase {ph}: {len(ts)} block(s) "
                  f"{_phase_desc(self.ctx, ts)} "
                  f"{self.last_r[ph] - self.first_d[ph]:.2f}s "
                  f"(dispatch→resolve envelope; phases overlap)",
                  flush=True)
        for s in self.succ[c]:
            self.waiting[s] -= 1
            if self.waiting[s] == 0:
                self.ready.push(s)

    # -- the group fault domain ----------------------------------------------

    def deadline(self, f: _Flight) -> float:
        # per-group watchdog deadline over the unit's total estimated cost:
        # generous floor + slack × the group's OWN calibrated rate (EWMA
        # seconds/cost; cold groups inherit the fastest calibrated rate, 0
        # until any group calibrates — early units get the floor alone). A
        # false expiry is benign: re-dispatch reuses the keys, so a
        # slow-but-alive unit still resolves bitwise-identically.
        return (self.pol.timeout_floor_s + self.pol.timeout_slack
                * self.health.rate(f.group) * self.unit_cost(f.unit))

    def note_expiry(self, f: _Flight):
        if self.elastic and self.health.note_expiry(f.group):
            self.quarantine(f.group, f.unit.tasks[0].coord)

    def handle_expiries(self, now: float) -> bool:
        """Watchdog sweep: expire overdue flights, count consecutive
        expiries toward quarantine, re-dispatch or terminally retire.
        Returns True when any state changed."""
        changed = False
        for fid in list(self.flights):
            f = self.flights.get(fid)
            if f is None or now - f.td <= self.deadline(f):
                continue
            changed = True
            f, tw = self.pop_flight(fid)
            if tw is not None:
                # the twin flies on — the expired side only cancels
                self.cancel(f)
                self.note_expiry(f)
                continue
            for t in f.unit.tasks:
                self.ex._record("expire", t.coord, f.group)
            self.note_expiry(f)
            tasks = f.unit.tasks
            if all(self.ctx.cur_attempt(t.coord) < self.pol.max_retries
                   for t in tasks):
                for t in tasks:
                    self.ctx.record_fault(t.coord, "timeout", "redispatched")
                    self.ctx.attempts[t.coord] = (
                        self.ctx.cur_attempt(t.coord) + 1)
                self.launch(self.restage(f.unit, self.least_loaded()),
                            "redispatch")
            else:
                for t in tasks:
                    self.retire(t, None, f.td, kind="timeout", group=f.group)
        return changed

    def quarantine(self, g: int, trigger: Coord):
        """Drain group ``g``: no future dispatch targets it, its staged
        units return to the ready queue (dropping their staged buffers),
        and its in-flight units re-stage on healthy groups under the SAME
        keys (kind="group" — no block retry budget is consumed)."""
        self.health.quarantine(g)
        self.ex._record("quarantine", trigger, g)
        self.ex.n_quarantined += 1
        self.ctx.record_fault(trigger, "group", "quarantined")
        _maybe_degrade_topology(self.ctx, self.health)  # may raise (ckpt
        while self.staged[g]:                           # already flushed)
            for t in self.staged[g].pop().tasks:
                self.ready.push(t.coord)
        for fid in [i for i, f in self.flights.items() if f.group == g]:
            f, tw = self.pop_flight(fid)
            if tw is not None:
                # its healthy twin flies on: this side just cancels
                self.cancel(f)
                continue
            for t in f.unit.tasks:
                self.ex._record("expire", t.coord, g)
                self.ctx.record_fault(t.coord, "group", "rebalanced")
            self.launch(self.restage(f.unit, self.least_loaded()),
                        "redispatch")

    def speculate(self, now: float):
        """Straggler hedge: an untwinned flight past ``speculate_at ×``
        its group's calibrated deadline model is re-staged on an idle
        healthy group and dispatched with the SAME keys (its twin)."""
        if not self.elastic or self.pol.speculate_at <= 0.0:
            return
        for fid in list(self.flights):
            f = self.flights.get(fid)
            if f is None or fid in self.twin:
                continue
            r = self.health.rate(f.group)
            if (r <= 0.0 or now - f.td
                    <= self.pol.speculate_at * r * self.unit_cost(f.unit)):
                continue
            idle = [g for g in self.health.healthy()
                    if g != f.group and not self.staged[g]
                    and self.has_room(g)]
            if not idle:
                continue
            f2 = self.dispatch(self.restage(f.unit, self.least_loaded(idle)))
            if f2 is None:
                continue    # the primary still flies; skip the twin
            for t in f.unit.tasks:
                self.ex._record("speculate", t.coord, f2.group)
            self.ex.n_speculations += len(f.unit.tasks)
            fid2 = self.add_flight(f2)
            self.twin[fid], self.twin[fid2] = fid2, fid

    def steal(self) -> bool:
        """Work stealing: an idle healthy group takes the highest-priority
        staged unit of the most-loaded group, re-stages it on itself and
        dispatches it. Returns True when anything was stolen."""
        stole = False
        for g in self.health.healthy():
            if self.staged[g] or self.ready or not self.has_room(g):
                continue
            victims = [h for h in self.health.healthy()
                       if h != g and self.staged[h]]
            if not victims:
                continue
            v = max(victims, key=lambda h: (self.load(h), -h))
            unit = self.staged[v].pop()
            for t in unit.tasks:
                self.ex._record("steal", t.coord, g)
            self.ex.n_steals += len(unit.tasks)
            self.launch(self.restage(unit, g), "dispatch")
            stole = True
        return stole


def _group_chain(ctx: PhaseContext, task: BlockTask, topo: Topology, g: int,
                 comm: str = "gather", donate: bool = False):
    """One block's jitted chain on device group ``g`` of ``topo`` — the
    per-block dispatch unit of the serial and async executors. With more
    than one device, the block's padded planes plus its O(K²) prior
    summaries move to the group (the paper's phase-boundary communication,
    made explicit); a group of >1 devices runs the chain 'data'-sharded
    (``distributed.run_gibbs_group``), a single device runs
    ``gibbs.run_gibbs``. Returns ``(GibbsResult, tv, tmask, n_obs)``."""
    blk = ctx.part.block(task.i, task.j)
    s = ctx.shapes[task.phase]
    up, vp = ctx.priors(task)
    csr_r, csr_c, tr, tc, tv, tmask, up, vp = PP.pad_block_inputs(
        blk, s, ctx.cfg.K, ctx.test_p, up, vp,
        poison_nan=ctx.should_poison(task.coord))
    n_obs = int(tmask.sum())
    key = ctx.keys[task.i, task.j]
    if topo.n_devices > 1:
        if topo.data == 1:
            target = topo.group(g)[0]
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            target = NamedSharding(topo.group_mesh_2d(g), PartitionSpec())
        (ra, ca, tr, tc, up, vp, tv, tmask, key) = jax.device_put(
            ((csr_r.idx, csr_r.val, csr_r.mask),
             (csr_c.idx, csr_c.val, csr_c.mask),
             tr, tc, up, vp, tv, tmask, key), target)
        csr_r = PaddedCSR(*ra, n_cols=csr_r.n_cols)
        csr_c = PaddedCSR(*ca, n_cols=csr_c.n_cols)
    cfg = ctx.block_cfg(task)
    if topo.data > 1:
        from repro.core import distributed as DIST
        csrt = (None if comm == "gather" else
                tuple(x[0] for x in _stacked_csrt(
                    ctx, [task], s, topo.data, scatter=(comm == "scatter"))))
        res = DIST.run_gibbs_group(
            key, csr_r, csr_c, jnp.asarray(tr), jnp.asarray(tc), cfg, topo,
            group=g, U_prior=up, V_prior=vp, donate=donate, comm=comm,
            csrt=csrt)
    else:
        res = GIBBS.run_gibbs(key, csr_r, csr_c, jnp.asarray(tr),
                              jnp.asarray(tc), cfg, U_prior=up, V_prior=vp,
                              donate=donate)
    return res, tv, tmask, n_obs


class _OverlappedExecutor(Executor):
    """Base of the executors that run ``_ElasticScheduler``: the shared
    constructor state, the completion-detection seam, and the one
    ``run_graph``. Subclasses supply the scheduler's unit hooks and set
    ``stage_cap`` / ``inflight_cap`` at construction."""
    stage_cap: Optional[int] = None
    inflight_cap: Optional[int] = None

    def __init__(self, topology: Topology, donate: bool, record_trace: bool,
                 priority: bool, depth: int, comm: str):
        super().__init__(record_trace=record_trace)
        if int(depth) < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.topology = topology
        self.donate = donate
        self.priority = priority
        self.depth = int(depth)
        self.comm = comm
        self.peak_units = 0     # staged + in-flight units, high-water mark

    def run_phase(self, ctx, phase, tasks):
        raise NotImplementedError(
            f"{type(self).__name__} overlaps phases — it schedules whole "
            f"graphs (run_graph), not single phases")

    # -- completion-detection seam (tests fake completion order here) -----
    def _is_resolved(self, coord: Coord, signal) -> bool:
        return signal.is_ready()

    def _reset_run_state(self):
        super()._reset_run_state()
        self.peak_units = 0

    def _prepare(self, ctx: PhaseContext, verbose: bool):
        """Per-run set-up before the scheduler starts."""

    def run_graph(self, ctx, graph, verbose: bool = False):
        self._reset_run_state()
        self._prepare(ctx, verbose)
        return _ElasticScheduler(self, ctx, graph, verbose).run()


class AsyncExecutor(_OverlappedExecutor):
    """Dependency-driven overlapped schedule riding JAX async dispatch.

    Readiness counters over ``BlockTask.deps`` replace the phase barrier:
    each block is dispatched (one jitted per-block chain, the SAME bucketed
    executable the serial executor compiles — ≤4 compilations per run) the
    moment both of its prior sources have resolved, so phase-c blocks whose
    phase-b dependencies finished early start while the slowest phase-b
    bucket is still running. The host never blocks on bulk results:

      * completion detection polls ``is_ready()`` on a per-block scalar
        (masked Σ(pred-val)², doubling as the block's RMSE numerator);
      * posterior summaries (trimmed device slices) go straight into the
        context store and feed successors without touching the host;
      * padded per-block input buffers are donated to XLA
        (``run_gibbs(donate=True)``): U0/V0 are rewritten in place as the
        U/V outputs, the rest is released at dispatch where the runtime
        supports it — and holding ONE block's planes at a time instead of a
        whole stacked bucket is itself the larger live-footprint cut
        (``bench_roofline --gibbs-peak`` measures both);
      * with >1 device, ready blocks are staged on the LEAST-LOADED
        healthy device group (per-group streams, zero inter-group
        collectives); a group of >1 devices runs the block's chain
        'data'-sharded (``distributed.run_gibbs_group``, intra-group
        collectives only).

    The unit of ``_ElasticScheduler`` is one block; staging is a no-op, so
    a group's whole assigned share sits staged (the steal pool), and with
    several groups each holds at most ``depth`` blocks in flight — the
    room the elastic layer (stealing, quarantine, speculation) works in.
    With ONE group dispatch is unbounded and the elastic layer is inert.

    ``record_trace=True`` appends (event, coord, group) events to
    ``self.trace`` in real order (see ``Executor`` for the schema).
    ``priority=True`` (default) pops the ready queue critical-path-first
    (``critical_path_priority``); ``priority=False`` is plain FIFO.
    """
    name = "async"

    def __init__(self, donate: bool = True, record_trace: bool = False,
                 priority: bool = True, topology: Optional[Topology] = None,
                 comm: str = "gather", depth: int = 2):
        topology = Topology.from_spec(topology)
        super().__init__(topology, donate, record_trace, priority, depth,
                         comm)
        self.devices = topology.devices
        self.inflight_cap = self.depth if topology.block > 1 else None

    def _ready_queue(self, ctx, prio, tasks):
        return _ReadyQueue(prio)

    def _form_unit(self, ctx, ready, tasks) -> List[BlockTask]:
        return [tasks[ready.pop()]]

    def _stage(self, ctx, tasks, group):
        return None

    def _dispatch(self, ctx: PhaseContext, unit: _Unit):
        """Dispatch one block's jitted chain on the unit's group without
        waiting for anything: inputs may still be computing (JAX chains
        the dataflow) and no output is synced. Returns (completion scalar,
        {coord: device outcome})."""
        (task,) = unit.tasks
        res, tv, tmask, n_obs = _group_chain(
            ctx, task, self.topology, unit.group, self.comm, self.donate)
        blk = ctx.part.block(task.i, task.j)
        nr, nc = len(blk.row_ids), len(blk.col_ids)
        sq = _block_sq_err(res.acc.pred_sum, res.acc.pred_cnt,
                           jnp.asarray(tv), jnp.asarray(tmask))
        out = BlockOutcome(
            U_post=RowGaussians(eta=res.U_post.eta[:nr],
                                Lambda=res.U_post.Lambda[:nr]),
            V_post=RowGaussians(eta=res.V_post.eta[:nc],
                                Lambda=res.V_post.Lambda[:nc]),
            pred_mean=None, seconds=0.0, sq_err=sq, n_obs=n_obs,
            health=res.health)
        return sq, {task.coord: out}


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
    shape: "PP.BlockShapes"
    cfg: BMF.BMFConfig
    dev: Tuple                    # (ri, rv, rm, ci, cv, cm, tr, tc, tv, tm)
    keys: jax.Array               # (W,) typed PRNG keys
    U_prior: RowGaussians         # (W, n_rows, ...) padded (dummies where off)
    V_prior: RowGaussians
    u_use: jax.Array              # (W,) {0,1} prior flags
    v_use: jax.Array
    n_obs: List[int]


class StreamingExecutor(_OverlappedExecutor):
    """Bounded-window streaming schedule for out-of-memory block grids.

    The stacked executor materializes a whole phase bucket on device at
    once — ``num_blocks_in_bucket × block_bytes`` — which web-scale grids
    (thousands of blocks) cannot co-resident in HBM. This executor runs the
    SAME elastic scheduler as the async executor but moves blocks through a
    bounded window of ``W`` donated block buffers:

      * the unit is a chunk: the top-priority ready block plus up to W-1
        more sharing its window shape and chain config
        (``_GroupedReadyQueue``); short chunks are repeat-padded to exactly
        W so ONE executable serves every chunk;
      * staging a chunk assembles its CSR planes/test entries on the HOST
        (``pp.pad_block_inputs_host``) and ships them with one async
        ``device_put`` onto the chunk's group. Each group holds ONE staged
        chunk — the double-buffered prefetch: the next chunk's H2D
        transfer runs while the current chunk computes;
      * chunks dispatch through ``gibbs.run_gibbs_stacked(donate=True)``:
        XLA recycles the window buffers (U0/V0 alias the U/V outputs, the
        planes return to the allocator), so with at most ``depth`` chunks
        in flight per group the live input footprint is
        ``≤ W × (depth + 1) × block_bytes`` per group — flat in the grid
        size (``peak_window_blocks`` records the realized bound;
        ``bench_roofline --gibbs-peak`` measures it);
      * completion is detected by non-blocking ``is_ready()`` polls on each
        chunk's (W,) squared-error vector — the same ``_is_resolved``
        seam as the async executor;
      * per-phase shape buckets are COALESCED first
        (``pp.BlockShapes.coalesce`` / ``partition.coalesce_shapes``):
        buckets within the waste budget share one window shape, and the
        per-block prior flags (``run_gibbs_stacked(prior_use=...)``) let
        that single executable serve phase-a/b/c blocks despite their
        different prior structures.

    A chunk is the fault domain: its members time out, re-dispatch and
    are stolen or twinned together. Per-block chains are the stacked
    executor's vmapped semantics (same keys, same padding), so RMSE
    matches serial to batched-fp tolerance and results are bit-identical
    across runs regardless of how completion timing regroups the chunks.

    ``max_waste`` defaults to 1.0 — only bit-identical shapes merge, which
    preserves exact chain parity with the serial/stacked reference (the
    padded row count feeds the NW hyper-resample and the RNG shapes, so
    ANY padding change perturbs the chains). Raising it trades that strict
    parity for fewer window executables and a single recycled buffer pool:
    results remain valid Gibbs chains, just not the reference's draws.
    """
    name = "streaming"
    stage_cap = 1             # one prefetched chunk per group

    def __init__(self, window: int = 4, donate: bool = True,
                 max_waste: float = 1.0, priority: bool = True,
                 depth: int = 2, record_trace: bool = False,
                 topology: Optional[Topology] = None, comm: str = "gather"):
        if int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if comm != "gather":
            # window chunks use prior_use-flagged executables; only the
            # 'gather' intra-group exchange composes with them (and at
            # data == 1 no other mode means anything)
            raise ValueError(
                f"streaming executor supports comm='gather' only, "
                f"got {comm!r}")
        topology = (Topology.from_spec(topology) if topology is not None
                    else Topology(block=1, data=1))
        super().__init__(topology, donate, record_trace, priority, depth,
                         comm)
        self.window = int(window)
        self.max_waste = max_waste
        self.inflight_cap = self.depth
        if topology.n_devices > 1:
            self.devices = topology.devices
        self.window_shapes: Optional[Dict[str, "PP.BlockShapes"]] = None

    @property
    def peak_window_blocks(self) -> int:
        """Realized live-buffer bound of the last run, in blocks: staged
        plus in-flight chunks, W blocks each."""
        return self.window * self.peak_units

    def _reset_run_state(self):
        super()._reset_run_state()
        self.window_shapes = None

    def _prepare(self, ctx, verbose):
        self.window_shapes = PP.BlockShapes.coalesce(ctx.shapes, ctx.cfg.K,
                                                     self.max_waste)
        if verbose:
            n_buckets = len({id(s) for s in self.window_shapes.values()})
            print(f"[pp:{self.name}] window={self.window} depth={self.depth} "
                  f"{n_buckets} coalesced bucket(s) over "
                  f"{len(self.window_shapes)} phase tag(s), "
                  f"{self.topology.block} stream group(s) x "
                  f"{self.topology.data} device(s)", flush=True)

    def _ready_queue(self, ctx, prio, tasks):
        def chunk_key(c):
            cfg = ctx.block_cfg(tasks[c])
            return (id(self.window_shapes[tasks[c].phase]), cfg.n_samples,
                    cfg.burnin)
        return _GroupedReadyQueue(prio, chunk_key)

    def _form_unit(self, ctx, ready, tasks) -> List[BlockTask]:
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
               group: int) -> _StagedChunk:
        """Assemble one chunk on the host and issue its (async) H2D
        transfer to the target group. Deps are resolved (the chunk came
        off the ready queue), so the device-resident priors are read here
        too — moving them to the group is the phase-boundary O(K²)
        communication, made explicit."""
        s = self.window_shapes[chunk[0].phase]
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
            shape=s, cfg=ctx.block_cfg(chunk[0]), dev=dev,
            keys=keys, U_prior=U_pri, V_prior=V_pri,
            u_use=jnp.asarray([uf[i] for i in sel], jnp.float32),
            v_use=jnp.asarray([vf[i] for i in sel], jnp.float32),
            n_obs=[int(h[5].sum()) for h in host])

    def _dispatch(self, ctx: PhaseContext, unit: _Unit):
        """Dispatch one staged chunk; returns (signal, outcomes). The
        window buffers are donated — after this call nothing holds them
        and XLA recycles their storage for the next chunk."""
        st = unit.staged
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
                mesh=self.topology.group_mesh_2d(unit.group))
        else:
            res = GIBBS.run_gibbs_stacked(
                st.keys, csr_r, csr_c, tr, tc, st.cfg,
                U_prior=st.U_prior, V_prior=st.V_prior,
                prior_use=(st.u_use, st.v_use), donate=self.donate)
        sq = _chunk_sq_err(res.acc.pred_sum, res.acc.pred_cnt, tv, tmask)
        outs: Dict[Coord, BlockOutcome] = {}
        for b, t in enumerate(unit.tasks):      # padded duplicates dropped
            blk = ctx.part.block(t.i, t.j)
            nr, nc = len(blk.row_ids), len(blk.col_ids)
            outs[t.coord] = BlockOutcome(
                U_post=RowGaussians(eta=res.U_post.eta[b, :nr],
                                    Lambda=res.U_post.Lambda[b, :nr]),
                V_post=RowGaussians(eta=res.V_post.eta[b, :nc],
                                    Lambda=res.V_post.Lambda[b, :nc]),
                pred_mean=None, seconds=0.0,
                sq_err=sq[b], n_obs=st.n_obs[b],
                health=(res.health[b] if res.health is not None else None))
        return sq, outs


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


def make_executor(spec, window=None, topology=None) -> Executor:
    """Resolve run_pp's ``executor=`` argument: a registry name or an
    instance. ``topology`` is the 2-D ('block', 'data') placement
    (core.topology.Topology, an ``(block, data)`` pair, or a 1-D mesh)
    consumed by the serial (block must be 1), sharded, async, and
    streaming executors. ``window`` is the streaming executor's window
    size (ignored by the others)."""
    if isinstance(spec, Executor):
        for arg, name in ((window, "window"), (topology, "topology")):
            if arg is not None:
                raise ValueError(
                    f"{name} with an Executor instance is ambiguous — "
                    f"construct the executor with it yourself or pass the "
                    f"executor by name")
        return spec
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if spec not in EXECUTORS:
        raise ValueError(f"unknown executor {spec!r} "
                         f"(expected {' | '.join(EXECUTORS)})")
    topo = None if topology is None else Topology.from_spec(topology)
    if spec == "stacked" and topo is not None:
        raise ValueError(
            "the stacked executor is single-executable (no device "
            "placement) — use executor='sharded' with a topology")
    factories = {
        "serial": lambda: SerialExecutor(topology=topo),
        "stacked": lambda: StackedExecutor(),
        "sharded": lambda: ShardedExecutor(topo),
        "async": lambda: AsyncExecutor(topology=topo),
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
