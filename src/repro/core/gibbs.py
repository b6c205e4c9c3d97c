"""Gibbs sampler for BMF — single-block (jit, lax.fori_loop) version.

One sweep:
  1. (optional) resample NW hyperparameters for U and V given current factors
  2. sample all rows of U | V  (parallel across rows — batched einsums)
  3. sample all rows of V | U

Running accumulators (post-burn-in): predictive sums on the test entries
(for RMSE of the posterior-mean predictor), factor means and outer-product
sums (for Posterior Propagation summarization).
"""
from __future__ import annotations

import contextlib
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bmf as BMF
from repro.core import posterior as POST
from repro.core.posterior import NormalWishart, RowGaussians
from repro.data.sparse import PaddedCSR


class GibbsAccumulators(NamedTuple):
    pred_sum: jnp.ndarray      # (n_test,) sum over kept samples of u·v
    pred_cnt: jnp.ndarray      # scalar
    U_sum: jnp.ndarray         # (N, K)
    U_outer: jnp.ndarray       # (N, K, K)
    V_sum: jnp.ndarray         # (D, K)
    V_outer: jnp.ndarray       # (D, K, K)


class GibbsResult(NamedTuple):
    U: jnp.ndarray
    V: jnp.ndarray
    acc: GibbsAccumulators
    U_post: RowGaussians       # summarized per-row posteriors
    V_post: RowGaussians
    # chain-health scalar (bool; (B,) under the stacked paths): every
    # finiteness-relevant output — final factors, summarized posterior
    # natural params, and the predictive sums — reduced with jnp.all ∘
    # isfinite. One O(N·K²) reduction per CHAIN (vs n_samples sweeps of
    # O(nnz·K²) work), so the guard is ~free; a NaN'd Cholesky or a
    # diverged sweep anywhere in the chain flips it to False. None only on
    # legacy construction sites that predate the guard.
    health: Optional[jnp.ndarray] = None


def chain_health(*trees) -> jnp.ndarray:
    """All-finite reduction over arbitrary pytrees -> bool scalar (batched
    leaves reduce over their trailing axes only if the caller vmaps)."""
    ok = jnp.ones((), jnp.bool_)
    for leaf in jax.tree_util.tree_leaves(trees):
        ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok


def _summarize(sum_, outer, cnt, ridge=1e-4):
    mean = sum_ / cnt
    cov = outer / cnt - jnp.einsum("nk,nl->nkl", mean, mean)
    K = mean.shape[-1]
    # The ridge keeps the moment estimate PD for the Cholesky below, but an
    # ABSOLUTE 1e-4 is meaningless against the row's scale: a near-singular
    # row whose variances sit at 1e4 gets a 1e-8-relative nudge (still
    # numerically indefinite), while a 1e-6-scale row gets drowned.  Scale
    # it by the row's largest diagonal — the same eigenvalue-magnitude
    # rationale as the serving store's PD projection — floored at the old
    # absolute value so O(1)-scale rows (every existing chain) are
    # bit-for-bit unchanged.
    mag = jnp.max(jnp.abs(jnp.diagonal(cov, axis1=-2, axis2=-1)),
                  axis=-1, keepdims=True)
    row_ridge = ridge * jnp.maximum(mag, 1.0)                    # (N, 1)
    cov = cov + row_ridge[..., None] * jnp.eye(K, dtype=cov.dtype)
    # Cholesky factor/solve: O(K³/3) per row + triangular solves, no
    # explicit inverse
    return POST.from_moments_cov(mean, cov, ridge=0.0)


def _run_gibbs_dispatch(key, csr_rows_arrs, csr_cols_arrs, test_rows,
                        test_cols, cfg, n_cols_r, n_cols_c, n_samples, burnin,
                        U_prior, V_prior, U0, V0):
    # n_samples/burnin are traced: one executable serves any chain length
    # (warm-up runs, reduced phase-b/c chains, ...)
    csr_rows = PaddedCSR(*csr_rows_arrs, n_cols=n_cols_r)
    csr_cols = PaddedCSR(*csr_cols_arrs, n_cols=n_cols_c)
    return _run_gibbs_impl(key, csr_rows, csr_cols, test_rows, test_cols,
                           cfg, n_samples, burnin, U_prior, V_prior, U0, V0)


_STATIC = ("cfg", "n_cols_r", "n_cols_c")
# Donated positions: the padded CSR planes, test indices, and the factor
# initializations — all per-call buffers the caller never reuses (U0/V0
# additionally alias the U/V outputs exactly). Priors are deliberately NOT
# donated: PP shares one propagated posterior across every block of a
# row/col group and reads it again at final aggregation, so donating it
# from one block's dispatch would invalidate the others' inputs.
_DONATE_SINGLE = (1, 2, 3, 4, 12, 13)

_run_gibbs_jit = jax.jit(_run_gibbs_dispatch, static_argnames=_STATIC)
_run_gibbs_jit_donated = jax.jit(_run_gibbs_dispatch, static_argnames=_STATIC,
                                 donate_argnums=_DONATE_SINGLE)


@contextlib.contextmanager
def _quiet_donation():
    """The CSR planes/test indices have no same-shape output to alias, so
    XLA notes them as 'not usable' — expected: on TPU/GPU their donation
    still invalidates the caller's handle at dispatch (allocator churn);
    the CPU runtime ignores unusable donations. U0/V0 alias the U/V
    outputs on every backend."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def run_gibbs(key,
              csr_rows: PaddedCSR,      # R rows:    users x items
              csr_cols: PaddedCSR,      # R^T rows:  items x users
              test_rows: jnp.ndarray,   # (n_test,) user ids
              test_cols: jnp.ndarray,   # (n_test,) item ids
              cfg: BMF.BMFConfig,
              U_prior: Optional[RowGaussians] = None,
              V_prior: Optional[RowGaussians] = None,
              U0: Optional[jnp.ndarray] = None,
              V0: Optional[jnp.ndarray] = None,
              donate: bool = False) -> GibbsResult:
    """Run cfg.n_samples sweeps (cfg.burnin of them discarded).

    U_prior / V_prior: propagated per-row priors (PP phases b/c). When None,
    the factor gets the NW hierarchical prior resampled each sweep.

    The whole chain is one cached jitted executable keyed on (shapes, cfg) —
    the PP scheduler buckets all blocks to common shapes precisely so every
    block reuses this compilation.

    donate=True donates the padded CSR planes, test indices, and U0/V0 to
    XLA: U0/V0 are rewritten in place as the U/V outputs (every backend),
    and where the runtime supports it (TPU/GPU) the remaining donated
    buffers are invalidated at dispatch instead of living until the Python
    refs drop — cutting peak HBM and allocator churn on the PP hot path.
    Callers that reuse any of those buffers across calls must keep the
    default. Propagated priors are never donated (shared across a PP
    row/col group and read again at final aggregation).
    """
    N, D, K = csr_rows.n_rows, csr_cols.n_rows, cfg.K
    k0, key = jax.random.split(key)
    if U0 is None or V0 is None:
        U0_, V0_ = BMF.init_factors(k0, N, D, K)
        U0 = U0 if U0 is not None else U0_
        V0 = V0 if V0 is not None else V0_
    cfg_key = cfg._replace(n_samples=0, burnin=0, phase_bc_samples=None)
    fn = _run_gibbs_jit_donated if donate else _run_gibbs_jit
    with (_quiet_donation() if donate else contextlib.nullcontext()):
        return fn(key,
                  (csr_rows.idx, csr_rows.val, csr_rows.mask),
                  (csr_cols.idx, csr_cols.val, csr_cols.mask),
                  test_rows, test_cols, cfg_key,
                  csr_rows.n_cols, csr_cols.n_cols,
                  jnp.asarray(cfg.n_samples, jnp.int32),
                  jnp.asarray(cfg.burnin, jnp.int32),
                  U_prior, V_prior, U0, V0)


def _run_gibbs_stacked_dispatch(key_data, csr_rows_arrs, csr_cols_arrs,
                                test_rows, test_cols, cfg, n_cols_r, n_cols_c,
                                n_samples, burnin, U_prior, V_prior, U0, V0,
                                u_use=None, v_use=None, mesh=None):
    """Batched (leading block axis) chain runner.

    Every array argument carries a leading axis B; ``mesh`` (hashable,
    static) optionally shard_maps that axis over a 1-D 'block' device mesh —
    same-phase PP blocks then run concurrently on separate devices with NO
    collectives inside the phase (communication stays at phase boundaries,
    which live on the host between calls).

    ``u_use`` / ``v_use`` are optional per-block {0,1} flags: when given
    (streaming window chunks), block b uses the fixed prior where its flag
    is 1 and the resampled NW hyperprior where it is 0 — one executable
    then serves blocks of EVERY phase tag (see ``_run_gibbs_impl``).

    Keys travel as raw uint32 key data so the leaves are plain arrays for
    vmap/shard_map; per-block semantics are EXACTLY ``_run_gibbs_impl``'s.
    """
    def batched(kd, rows_arrs, cols_arrs, tr, tc, ns, bi, up, vp, u0, v0,
                uu, vv):
        def one(kd1, ra, ca, tr1, tc1, up1, vp1, u01, v01, uu1, vv1):
            return _run_gibbs_impl(
                jax.random.wrap_key_data(kd1),
                PaddedCSR(*ra, n_cols=n_cols_r),
                PaddedCSR(*ca, n_cols=n_cols_c),
                tr1, tc1, cfg, ns, bi, up1, vp1, u01, v01, uu1, vv1)
        return jax.vmap(one)(kd, rows_arrs, cols_arrs, tr, tc, up, vp,
                             u0, v0, uu, vv)

    if mesh is None:
        return batched(key_data, csr_rows_arrs, csr_cols_arrs, test_rows,
                       test_cols, n_samples, burnin, U_prior, V_prior, U0, V0,
                       u_use, v_use)
    from jax.sharding import PartitionSpec as P
    blk = P("block")
    fsh = jax.shard_map(batched, mesh=mesh,
                        in_specs=(blk, blk, blk, blk, blk, P(), P(),
                                  blk, blk, blk, blk, blk, blk),
                        out_specs=blk, check_vma=False)
    return fsh(key_data, csr_rows_arrs, csr_cols_arrs, test_rows, test_cols,
               n_samples, burnin, U_prior, V_prior, U0, V0, u_use, v_use)


_STATIC_STACKED = ("cfg", "n_cols_r", "n_cols_c", "mesh")
# Stacked donation mirrors _DONATE_SINGLE: per-bucket stacked CSR planes,
# test indices, and vmapped U0/V0 (aliasing the stacked U/V outputs).
# Stacked priors are fresh jnp.stack copies at every call site, but stay
# un-donated for symmetry with the single-block contract.
_DONATE_STACKED = (1, 2, 3, 4, 12, 13)

_run_gibbs_stacked_jit = jax.jit(_run_gibbs_stacked_dispatch,
                                 static_argnames=_STATIC_STACKED)
_run_gibbs_stacked_jit_donated = jax.jit(_run_gibbs_stacked_dispatch,
                                         static_argnames=_STATIC_STACKED,
                                         donate_argnums=_DONATE_STACKED)


def run_gibbs_stacked(keys,
                      csr_rows: PaddedCSR,      # (B, N, M) leaves
                      csr_cols: PaddedCSR,      # (B, D, M_c) leaves
                      test_rows: jnp.ndarray,   # (B, n_test)
                      test_cols: jnp.ndarray,   # (B, n_test)
                      cfg: BMF.BMFConfig,
                      U_prior: Optional[RowGaussians] = None,  # (B, N, ...) or None
                      V_prior: Optional[RowGaussians] = None,
                      block_mesh=None, donate: bool = False,
                      prior_use: Optional[Tuple] = None) -> GibbsResult:
    """Batched analogue of ``run_gibbs``: one jitted vmapped executable runs
    B identically-shaped blocks' chains at once (the PP StackedExecutor's
    hot path — ``BlockShapes.per_phase`` guarantees the common shapes).

    ``keys`` is a (B,) typed PRNG key array; per-block key handling (split
    for init, then the chain) mirrors ``run_gibbs`` exactly, so block b of
    the stacked result reproduces ``run_gibbs(keys[b], ...)``.

    ``block_mesh``: optional 1-D Mesh with axis 'block'; B must be a
    multiple of the mesh size (callers pad the batch). The returned
    GibbsResult's leaves all carry the leading B axis.

    ``donate`` mirrors ``run_gibbs``: the stacked CSR planes, test indices,
    and U0/V0 are donated to XLA (same caller-must-not-reuse contract).

    ``prior_use``: optional ``(u_use, v_use)`` per-block {0,1} flag arrays
    (B,). With flags, ``U_prior``/``V_prior`` must be full (B, ...) arrays
    (dummy rows where a block has no propagated prior) and block b follows
    its flags: 1 = the fixed propagated prior, 0 = the hierarchical NW
    prior resampled each sweep — bit-identical per block to the dedicated
    with/without-prior executables, because the hyper-sampling keys are
    split unconditionally either way. This is the streaming executor's
    buffer-shape reuse lever: ONE window executable serves phase a, b and
    c blocks instead of one executable per prior structure.
    """
    N, D, K = csr_rows.idx.shape[1], csr_cols.idx.shape[1], cfg.K
    ks = jax.vmap(jax.random.split)(keys)                     # (B, 2)
    U0, V0 = jax.vmap(lambda k: BMF.init_factors(k, N, D, K))(ks[:, 0])
    cfg_key = cfg._replace(n_samples=0, burnin=0, phase_bc_samples=None)
    u_use, v_use = prior_use if prior_use is not None else (None, None)
    fn = _run_gibbs_stacked_jit_donated if donate else _run_gibbs_stacked_jit
    with (_quiet_donation() if donate else contextlib.nullcontext()):
        return fn(
            jax.random.key_data(ks[:, 1]),
            (csr_rows.idx, csr_rows.val, csr_rows.mask),
            (csr_cols.idx, csr_cols.val, csr_cols.mask),
            test_rows, test_cols, cfg_key, csr_rows.n_cols, csr_cols.n_cols,
            jnp.asarray(cfg.n_samples, jnp.int32),
            jnp.asarray(cfg.burnin, jnp.int32),
            U_prior, V_prior, U0, V0, u_use, v_use, mesh=block_mesh)


def _run_gibbs_impl(key, csr_rows, csr_cols, test_rows, test_cols, cfg,
                    n_samples, burnin, U_prior, V_prior, U0, V0,
                    u_use=None, v_use=None,
                    u_sampler=None, v_sampler=None,
                    n_rows=None, n_cols=None) -> GibbsResult:
    """Chain body shared by every executor path.

    ``u_sampler`` / ``v_sampler`` are the factor-step seams:
    ``sampler(key, csr, other, prior) -> factor``, defaulting to the
    single-device ``BMF.sample_factor``. The intra-block distributed
    sweep (core.distributed) swaps in 'data'-mesh-sharded samplers —
    everything else (key splitting, prior selection, accumulators,
    summaries) is THIS code, so the composed chains share the reference
    semantics by construction. ``n_rows`` / ``n_cols`` override the
    factor sizes when ``csr_rows`` / ``csr_cols`` hold only a device's
    local shard (the carry factors stay full-size and replicated).

    Each layer of a sweep runs under a ``jax.named_scope`` — ``bmf_prior``,
    ``bmf_u_step`` / ``bmf_v_step`` (whatever sampler the seam holds),
    ``bmf_accumulate`` (with ``bmf_predict`` inside), and after the loop
    ``bmf_summarize`` — so every executor path carries them. Scopes are op
    metadata only: the compiled program is the same, and a profiler trace
    names each device op by its scope path."""
    N = csr_rows.n_rows if n_rows is None else n_rows
    D = csr_cols.n_rows if n_cols is None else n_cols
    K = cfg.K
    nw = POST.default_nw(K)
    if cfg.sweep_fused:
        # one-kernel sweep: the whole factor step in a single pass (Pallas
        # on TPU, the bitwise-identical striped-XLA fallback elsewhere).
        # The noise stream matches sample_factor's draw exactly, so this is
        # a pure execution-strategy switch for every executor that leaves
        # these seams at their defaults.
        from repro.kernels.bmf_sweep import ops as SWEEP
        default_sampler = lambda k, csr, other, prior: \
            SWEEP.sample_factor_fused(k, csr, other, cfg.tau, prior,
                                      dtype=cfg.sweep_dtype)
    else:
        default_sampler = lambda k, csr, other, prior: BMF.sample_factor(
            k, csr, other, cfg.tau, prior, cfg.use_kernel)
    if u_sampler is None:
        u_sampler = default_sampler
    if v_sampler is None:
        v_sampler = default_sampler

    acc0 = GibbsAccumulators(
        pred_sum=jnp.zeros_like(test_rows, dtype=jnp.float32),
        pred_cnt=jnp.zeros((), jnp.float32),
        U_sum=jnp.zeros((N, K)), U_outer=jnp.zeros((N, K, K)),
        V_sum=jnp.zeros((D, K)), V_outer=jnp.zeros((D, K, K)))

    def pick_prior(fixed, use, kh, X, n):
        """Prior for one factor this sweep. ``use=None`` keeps the two
        dedicated structures (fixed prior XOR NW resample); a traced
        ``use`` flag selects per block between the fixed prior and the
        resampled hyperprior — both sides are elementwise identical to the
        dedicated paths (the hyper key was split unconditionally), so
        flagged executables are bit-compatible per block."""
        if fixed is not None and use is None:
            return fixed
        mu, Lam = BMF.sample_hyper(kh, X, nw)
        hier = POST.broadcast_prior(mu, Lam, n)
        if fixed is None:
            return hier
        return jax.tree.map(lambda f, h: jnp.where(use, f, h), fixed, hier)

    def sweep(i, carry):
        key, U, V, acc = carry
        key, kh1, kh2, ku, kv = jax.random.split(key, 5)

        with jax.named_scope("bmf_prior"):
            u_prior = pick_prior(U_prior, u_use, kh1, U, N)
            v_prior = pick_prior(V_prior, v_use, kh2, V, D)

        with jax.named_scope("bmf_u_step"):
            U = u_sampler(ku, csr_rows, V, u_prior)
        with jax.named_scope("bmf_v_step"):
            V = v_sampler(kv, csr_cols, U, v_prior)

        with jax.named_scope("bmf_accumulate"):
            keep = (i >= burnin).astype(jnp.float32)
            with jax.named_scope("bmf_predict"):
                pred = BMF.predict(U, V, test_rows, test_cols)
            acc = GibbsAccumulators(
                pred_sum=acc.pred_sum + keep * pred,
                pred_cnt=acc.pred_cnt + keep,
                U_sum=acc.U_sum + keep * U,
                U_outer=acc.U_outer + keep * jnp.einsum("nk,nl->nkl", U, U),
                V_sum=acc.V_sum + keep * V,
                V_outer=acc.V_outer + keep * jnp.einsum("nk,nl->nkl", V, V))
        return (key, U, V, acc)

    key, U, V, acc = jax.lax.fori_loop(
        0, n_samples, sweep, (key, U0, V0, acc0))

    with jax.named_scope("bmf_summarize"):
        cnt = jnp.maximum(acc.pred_cnt, 1.0)
        U_post = _summarize(acc.U_sum, acc.U_outer, cnt)
        V_post = _summarize(acc.V_sum, acc.V_outer, cnt)
        health = chain_health(U, V, U_post, V_post, acc.pred_sum)
    return GibbsResult(U=U, V=V, acc=acc, U_post=U_post, V_post=V_post,
                       health=health)


def rmse_from_acc(acc: GibbsAccumulators, test_vals: jnp.ndarray) -> jnp.ndarray:
    pred = acc.pred_sum / jnp.maximum(acc.pred_cnt, 1.0)
    return jnp.sqrt(jnp.mean((pred - test_vals) ** 2))


class TracedChain(NamedTuple):
    """What the static analyzer needs from one lowering: the jax Traced
    object (``.jaxpr`` feeds the jaxpr passes, ``.lower().compile()`` the
    HLO passes), the flat XLA-parameter labels in order, the labels
    donate_argnums covers, and the subset that must alias an output."""
    traced: object
    param_labels: Tuple[str, ...]
    donated_labels: Tuple[str, ...]
    must_alias: Tuple[str, ...]


def _flat_param_labels(named_args) -> Tuple[str, ...]:
    """Flatten [(name, pytree-of-avals)] into per-XLA-parameter labels:
    the jit entry's parameter order IS the flattened order of its dynamic
    args, so label i names HLO parameter i."""
    labels = []
    for name, tree in named_args:
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) == 1:
            labels.append(name)
        else:
            labels.extend(f"{name}.{i}" for i in range(len(leaves)))
    return tuple(labels)


def _donated_labels(named_args, donate_argnums) -> Tuple[str, ...]:
    out = []
    for pos in donate_argnums:
        name, tree = named_args[pos]
        n = len(jax.tree_util.tree_leaves(tree))
        out.extend([name] if n == 1 else [f"{name}.{i}" for i in range(n)])
    return tuple(out)


def trace_chain(cfg: BMF.BMFConfig, n_rows: int, n_cols: int, m_rows: int,
                m_cols: int, n_test: int, *, batch: Optional[int] = None,
                donate: bool = False, u_prior: bool = True,
                v_prior: bool = True, prior_use: bool = False,
                mesh=None) -> TracedChain:
    """Lowering hook for the static analyzer (repro.analysis /
    launch.bmf_lint): trace the EXACT executable ``run_gibbs``
    (batch=None) or ``run_gibbs_stacked`` (batch=B) dispatches, at
    abstract shapes. ``prior_use`` adds the streaming executor's
    per-block prior-use flags (stacked only); ``mesh`` shard_maps the
    batch over a 1-D 'block' mesh (the sharded executor's data=1 path)."""
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    K = cfg.K
    cfg_key = cfg._replace(n_samples=0, burnin=0, phase_bc_samples=None)

    def shp(*dims):
        return dims if batch is None else (batch,) + dims

    csr_r = (S(shp(n_rows, m_rows), i32), S(shp(n_rows, m_rows), f32),
             S(shp(n_rows, m_rows), f32))
    csr_c = (S(shp(n_cols, m_cols), i32), S(shp(n_cols, m_cols), f32),
             S(shp(n_cols, m_cols), f32))
    tr, tc = S(shp(n_test), i32), S(shp(n_test), i32)
    ns, bi = S((), i32), S((), i32)
    up = (RowGaussians(eta=S(shp(n_rows, K), f32),
                       Lambda=S(shp(n_rows, K, K), f32)) if u_prior else None)
    vp = (RowGaussians(eta=S(shp(n_cols, K), f32),
                       Lambda=S(shp(n_cols, K, K), f32)) if v_prior else None)
    U0, V0 = S(shp(n_rows, K), f32), S(shp(n_cols, K), f32)

    if batch is None:
        key = jax.eval_shape(lambda: jax.random.key(0))
        named = [("key", key), ("csr_rows", csr_r), ("csr_cols", csr_c),
                 ("test_rows", tr), ("test_cols", tc), ("n_samples", ns),
                 ("burnin", bi), ("U_prior", up), ("V_prior", vp),
                 ("U0", U0), ("V0", V0)]
        fn = _run_gibbs_jit_donated if donate else _run_gibbs_jit
        with (_quiet_donation() if donate else contextlib.nullcontext()):
            traced = fn.trace(key, csr_r, csr_c, tr, tc, cfg_key,
                              n_cols, n_rows, ns, bi, up, vp, U0, V0)
        # donate positions -> named entries: the dispatch signature
        # interleaves the static args (cfg, n_cols_r, n_cols_c) at 5-7
        dpos = (1, 2, 3, 4, 9, 10)
    else:
        kd = S((batch, 2), jnp.uint32)
        uu = S((batch,), f32) if prior_use else None
        named = [("key_data", kd), ("csr_rows", csr_r), ("csr_cols", csr_c),
                 ("test_rows", tr), ("test_cols", tc), ("n_samples", ns),
                 ("burnin", bi), ("U_prior", up), ("V_prior", vp),
                 ("U0", U0), ("V0", V0), ("u_use", uu), ("v_use", uu)]
        fn = _run_gibbs_stacked_jit_donated if donate \
            else _run_gibbs_stacked_jit
        with (_quiet_donation() if donate else contextlib.nullcontext()):
            traced = fn.trace(kd, csr_r, csr_c, tr, tc, cfg_key,
                              n_cols, n_rows, ns, bi, up, vp, U0, V0,
                              uu, uu, mesh=mesh)
        dpos = (1, 2, 3, 4, 9, 10)
    donated = _donated_labels(named, dpos) if donate else ()
    must = tuple(lb for lb in ("U0", "V0") if lb in donated)
    return TracedChain(traced=traced,
                       param_labels=_flat_param_labels(named),
                       donated_labels=donated, must_alias=must)
