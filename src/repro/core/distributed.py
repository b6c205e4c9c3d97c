"""Distributed BMF within one block (ref [16]/[17], adapted MPI→shard_map).

One block's Gibbs chain sharded over the 'data' axis of a device group.
The chain body is ``gibbs._run_gibbs_impl`` — the same code every other
path runs — with its factor-sampler seams swapped for data-sharded ones:

  - the block's users (rows of U) and their ratings are sharded over the
    'data' mesh axis (padded CSR rows, padded to a multiple of the axis);
  - U-step: each device samples its local U rows against a replicated V
    from the SLICE of the reference noise draw, then one all_gather
    rebuilds the full factor (``_sharded_u_sampler``);
  - V-step (``COMM_MODES``): 'gather' runs the reference V-step on the
    gathered factor, so the chain is the single-device chain; 'psum'
    reduces per-shard partial item statistics (τ Σ u uᵀ, τ Σ r u) with one
    psum — 2·D·(K²+K)·4 bytes per sweep, independent of #ratings: the
    paper's "limited communication" property, made explicit; 'scatter'
    reduce-scatters them and all_gathers the sampled V.

Key handling, the NW hyperprior, accumulators and summaries are the
reference code, so the chain draws the reference chain's numbers in
'gather' mode (``run_gibbs_group`` matches ``gibbs.run_gibbs`` under the
same key).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS
from repro.core import posterior as POST
from repro.core.posterior import RowGaussians
from repro.core.topology import BLOCK_AXIS, DATA_AXIS
from repro.data.sparse import PaddedCSR


def make_block_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D device mesh with axis 'block' for the PP phase-graph
    ShardedExecutor (core.engine): same-phase blocks are placed on separate
    devices and no collective runs inside a phase — posterior summaries
    cross phase boundaries through the host, which IS the paper's entire
    communication budget. The data==1 degenerate form of the unified 2-D
    ('block', 'data') placement (core.topology.Topology / the composed
    executables below, which add the intra-block 'data' axis)."""
    from repro.core.topology import Topology
    n = n_devices or len(jax.devices())
    return Topology(block=n, data=1).block_mesh()


def item_stats_local(U_loc, csr_t_loc: PaddedCSR, tau: float,
                     use_kernel: bool = False):
    """Per-item partial stats from this device's ratings.

    U_loc: (N_loc, K); csr_t_loc: this shard's TRANSPOSED padded CSR —
    rows = items, columns = *local* user slots (built host-side by
    ``shard_transposed_planes``). Returns (D, K, K), (D, K) partial sums
    (pre-reduction). Reuses bmf.sufficient_stats, i.e. the same
    fused-gather hot path (Pallas kernel / chunked scan) as the U-step —
    a segment_sum formulation would materialize an (nnz, K, K) outer
    product tensor (§Perf H6a).
    """
    return BMF.sufficient_stats(csr_t_loc, U_loc, tau, use_kernel)


# ---------------------------------------------------------------------------
# Composed 2-D ('block', 'data') chains — block-parallel executors with the
# intra-block distributed sweep inside each block (the paper's combined
# system: PP block parallelism × ref [16]/[17] distributed BMF)
# ---------------------------------------------------------------------------

#: intra-block communication modes for the composed chains.
#:   'gather'  — exchange the freshly sampled factor: each 'data' shard
#:               samples its local U rows and all_gathers them (ref [17]'s
#:               asynchronous factor communication, made synchronous); the
#:               V-step then runs replicated on the full factor, so the
#:               chain is the single-device reference chain bit-for-bit
#:               (executor parity mode). Comm/sweep: N·K floats.
#:   'psum'    — paper-faithful ref [16]: per-shard partial item stats,
#:               one psum, every shard samples the same replicated V.
#:               Comm/sweep: D·(K²+K) floats (+ the N·K factor gather).
#:   'scatter' — beyond-paper §Perf H6: psum_scatter the stats, sample
#:               only local item rows, all_gather the sampled V.
COMM_MODES = ("gather", "psum", "scatter")


def _pad_rows_to(arr, n: int):
    pad = n - arr.shape[0]
    if pad <= 0:
        return arr
    return jnp.concatenate([arr, jnp.zeros((pad,) + arr.shape[1:],
                                           arr.dtype)], 0)


def shard_transposed_planes(rows, cols, vals, n_shards: int, n_rows_pad: int,
                            n_items: int, max_nnz: int):
    """Host-side per-shard TRANSPOSED padded-CSR planes for the composed
    V-step partial stats ('psum'/'scatter' modes): shard s holds
    items × its LOCAL users (rows [s·N_loc, (s+1)·N_loc) of the padded
    row space), so ``item_stats_local`` works on (n_items, max_nnz)
    planes whose column ids index the shard's local U rows.

    rows/cols/vals: COO triplets in BLOCK-local coordinates (numpy).
    Returns (idx, val, mask) numpy arrays of shape
    (n_shards, n_items, max_nnz)."""
    import numpy as np
    from repro.data.sparse import COO, coo_to_padded_csr

    N_loc = n_rows_pad // n_shards
    shard_of = rows // N_loc
    idxs, valss, masks = [], [], []
    for s in range(n_shards):
        sel = shard_of == s
        coo_t = COO(row=cols[sel].astype(np.int32),
                    col=(rows[sel] - s * N_loc).astype(np.int32),
                    val=vals[sel].astype(np.float32),
                    n_rows=n_items, n_cols=N_loc)
        csr = coo_to_padded_csr(coo_t, max_nnz=max_nnz,
                                n_rows_pad=n_items, n_cols_pad=N_loc,
                                as_numpy=True)
        idxs.append(csr.idx)
        valss.append(csr.val)
        masks.append(csr.mask)
    return (np.stack(idxs), np.stack(valss), np.stack(masks))


def _sharded_u_sampler(cfg: BMF.BMFConfig, N: int, N_pad: int,
                       n_shards: int):
    """U-step over the 'data' axis: local conditional stats from the
    shard's row planes, the SLICE of the full replicated noise draw, one
    all_gather of the freshly sampled rows. Because the noise is the
    single-device draw and the per-row math is row-local, the gathered
    factor reproduces the reference ``BMF.sample_factor`` rows exactly —
    this sampler is shared by every comm mode."""
    K = cfg.K
    N_loc = N_pad // n_shards

    def u_sampler(ku, csr_loc, V, u_prior):
        lo = jax.lax.axis_index(DATA_AXIS) * N_loc
        pr_eta = jax.lax.dynamic_slice_in_dim(
            _pad_rows_to(u_prior.eta, N_pad), lo, N_loc, 0)
        pr_lam = jax.lax.dynamic_slice_in_dim(
            _pad_rows_to(u_prior.Lambda, N_pad), lo, N_loc, 0)
        # the reference draw: sample_rows(ku, cond_full) pulls
        # normal(ku, (N, K)) — replicate it and slice this shard's rows
        # (padded rows get zero noise; their samples are never read)
        z = _pad_rows_to(jax.random.normal(ku, (N, K), jnp.float32), N_pad)
        z_loc = jax.lax.dynamic_slice_in_dim(z, lo, N_loc, 0)
        if cfg.sweep_fused:
            # one-kernel sweep on the local row shard: the per-row math is
            # row-local and the noise slice is the reference stream, so the
            # gathered factor matches the single-device fused step exactly
            from repro.kernels.bmf_sweep import ops as SWEEP
            U_loc = SWEEP.fused_sweep(
                z_loc, csr_loc.idx, csr_loc.val, csr_loc.mask,
                pr_eta, pr_lam, V, cfg.tau, dtype=cfg.sweep_dtype)
        else:
            Lam_c, eta_c = BMF.sufficient_stats(csr_loc, V, cfg.tau,
                                                cfg.use_kernel)
            cond = RowGaussians(eta=pr_eta + eta_c, Lambda=pr_lam + Lam_c)
            U_loc = POST.sample_rows_noise(cond, z_loc)
        U_full = jax.lax.all_gather(U_loc, DATA_AXIS, tiled=True)
        return U_full[:N]

    return u_sampler


def _sharded_v_sampler(cfg: BMF.BMFConfig, D: int, D_pad: int, N_pad: int,
                       n_shards: int, scatter: bool):
    """V-step over the 'data' axis from per-shard transposed planes:
    partial item stats reduced by psum ('psum' — ref [16] Fig. 2,
    replicated sampling under a shared key) or psum_scatter + local
    sampling + all_gather ('scatter' — §Perf H6 half-ring-bytes).

    This step stays UNFUSED under ``cfg.sweep_fused``: the psum/scatter
    collective splits the Λ/η accumulate from the sample across devices,
    which is exactly the fusion boundary the one-kernel sweep removes on
    a single device — there is no single pass to fuse here (documented in
    kernels/bmf_precision/README.md)."""
    K = cfg.K
    N_loc = N_pad // n_shards
    D_loc = D_pad // n_shards

    def v_sampler(kv, csrt_loc, U_full, v_prior):
        idx = jax.lax.axis_index(DATA_AXIS)
        U_loc = jax.lax.dynamic_slice_in_dim(
            _pad_rows_to(U_full, N_pad), idx * N_loc, N_loc, 0)
        Lam_part, eta_part = item_stats_local(U_loc, csrt_loc, cfg.tau,
                                              cfg.use_kernel)
        pr_eta = _pad_rows_to(v_prior.eta, D_pad)
        pr_lam = _pad_rows_to(v_prior.Lambda, D_pad)
        if scatter:
            Lam_loc = jax.lax.psum_scatter(Lam_part, DATA_AXIS,
                                           scatter_dimension=0, tiled=True)
            eta_loc = jax.lax.psum_scatter(eta_part, DATA_AXIS,
                                           scatter_dimension=0, tiled=True)
            d_lo = idx * D_loc
            cond = RowGaussians(
                eta=jax.lax.dynamic_slice_in_dim(pr_eta, d_lo, D_loc, 0)
                + eta_loc,
                Lambda=jax.lax.dynamic_slice_in_dim(pr_lam, d_lo, D_loc, 0)
                + Lam_loc)
            kv_dev = jax.random.fold_in(kv, idx)
            V_loc = POST.sample_rows(kv_dev, cond)
            V_full = jax.lax.all_gather(V_loc, DATA_AXIS, tiled=True)
            return V_full[:D]
        Lam_items = jax.lax.psum(Lam_part, DATA_AXIS)
        eta_items = jax.lax.psum(eta_part, DATA_AXIS)
        cond = RowGaussians(eta=pr_eta + eta_items,
                            Lambda=pr_lam + Lam_items)
        return POST.sample_rows(kv, cond)[:D]   # same key -> same V everywhere

    return v_sampler


def _run_gibbs_2d_dispatch(key_data, csr_rows_arrs, csr_cols_arrs,
                           csrt_arrs, test_rows, test_cols, cfg,
                           n_cols_r, n_cols_c, n_samples, burnin,
                           U_prior, V_prior, U0, V0, u_use, v_use,
                           mesh=None, comm="gather", n_rows=0, n_cols=0):
    """Composed chain runner: one executable shard_maps the stacked block
    batch over the 'block' axis while each block's chain runs the
    intra-block distributed sweep over the 'data' axis.

    Leaf layout (B = stacked blocks, padded to a multiple of the block
    axis; N_pad = bucket rows padded to a multiple of the data axis):

      csr_rows_arrs  (B, N_pad, M)        P('block', 'data')  row shards
      csr_cols_arrs  (B, D, M_c) | None   P('block')          'gather' only
      csrt_arrs      (B, S, D_pad, M_c) | None  P('block', 'data')
                                          'psum'/'scatter' partial-stat
                                          planes (items × local users)
      priors / U0 / V0 / tests            P('block')          replicated
                                          over 'data'

    Inside a shard the per-block chain is ``gibbs._run_gibbs_impl`` with
    the data-sharded factor samplers swapped in — key handling, prior
    selection, accumulators and summaries are literally the reference
    code, which is what makes the 'gather' mode chain-identical to the
    serial executor. Every intra-phase collective this executable contains
    runs on the 'data' axis; nothing ever reduces over 'block'
    (``bmf_dryrun --pp-engine`` asserts that from the compiled HLO).
    """
    n_shards = mesh.shape[DATA_AXIS]
    N, D = n_rows, n_cols
    N_pad = csr_rows_arrs[0].shape[1]
    D_pad = (csrt_arrs[0].shape[2] if csrt_arrs is not None else D)
    u_sampler = _sharded_u_sampler(cfg, N, N_pad, n_shards)
    v_sampler = (None if comm == "gather" else
                 _sharded_v_sampler(cfg, D, D_pad, N_pad, n_shards,
                                    scatter=(comm == "scatter")))

    def per_shard(kd, ra, ca, ta, tr, tc, ns, bi, up, vp, u0, v0, uu, vv):
        def one(kd1, ra1, ca1, ta1, tr1, tc1, up1, vp1, u01, v01, uu1, vv1):
            csr_loc = PaddedCSR(*ra1, n_cols=n_cols_r)
            if comm == "gather":
                csr_v = PaddedCSR(*ca1, n_cols=n_cols_c)
            else:
                # (1, D_pad, M_c) leading local-shard dim from shard_map
                csr_v = PaddedCSR(ta1[0][0], ta1[1][0], ta1[2][0],
                                  n_cols=N_pad // n_shards)
            return GIBBS._run_gibbs_impl(
                jax.random.wrap_key_data(kd1), csr_loc, csr_v,
                tr1, tc1, cfg, ns, bi, up1, vp1, u01, v01, uu1, vv1,
                u_sampler=u_sampler, v_sampler=v_sampler,
                n_rows=N, n_cols=D)
        return jax.vmap(one)(kd, ra, ca, ta, tr, tc, up, vp, u0, v0, uu, vv)

    blk, blkdata = P(BLOCK_AXIS), P(BLOCK_AXIS, DATA_AXIS)
    in_specs = (blk, blkdata, blk, blkdata, blk, blk, P(), P(),
                blk, blk, blk, blk, blk, blk)
    fsh = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                        out_specs=blk, check_vma=False)
    return fsh(key_data, csr_rows_arrs, csr_cols_arrs, csrt_arrs,
               test_rows, test_cols, n_samples, burnin,
               U_prior, V_prior, U0, V0, u_use, v_use)


_STATIC_2D = ("cfg", "n_cols_r", "n_cols_c", "mesh", "comm", "n_rows",
              "n_cols")
# Mirrors gibbs._DONATE_STACKED: the stacked CSR/test planes plus U0/V0
# (U0/V0 alias the U/V outputs); priors stay un-donated (shared across a
# PP row/col group and read again at final aggregation).
_DONATE_2D = (1, 2, 3, 4, 5, 13, 14)

_run_gibbs_2d_jit = jax.jit(_run_gibbs_2d_dispatch,
                            static_argnames=_STATIC_2D)
_run_gibbs_2d_jit_donated = jax.jit(_run_gibbs_2d_dispatch,
                                    static_argnames=_STATIC_2D,
                                    donate_argnums=_DONATE_2D)


def run_gibbs_stacked_2d(keys,
                         csr_rows: PaddedCSR,      # (B, N, M) leaves
                         csr_cols: PaddedCSR,      # (B, D, M_c) leaves
                         test_rows, test_cols, cfg: BMF.BMFConfig,
                         topology,
                         U_prior: Optional[RowGaussians] = None,
                         V_prior: Optional[RowGaussians] = None,
                         donate: bool = False,
                         prior_use: Optional[tuple] = None,
                         comm: str = "gather",
                         csrt=None,
                         mesh: Optional[Mesh] = None) -> GIBBS.GibbsResult:
    """2-D analogue of ``gibbs.run_gibbs_stacked``: B identically-shaped
    blocks' chains run as ONE executable on ``topology``'s
    ('block', 'data') mesh — the batch splits over device groups, each
    block's sweep is data-sharded inside its group.

    B must be a multiple of ``topology.block`` (callers pad the batch,
    exactly like the 1-D sharded path). Row planes are padded here to a
    multiple of ``topology.data`` with empty rows — padding that never
    enters the chain semantics (zero-mask CSR rows, zero noise, results
    trimmed), so per-block chains in 'gather' mode reproduce
    ``run_gibbs_stacked`` / ``run_gibbs`` under the same keys.

    ``comm``: see ``COMM_MODES``. 'psum'/'scatter' need ``csrt`` — the
    (B, S, D_pad, M_c) per-shard transposed planes from
    ``shard_transposed_planes`` (host-assembled by the executor).
    ``mesh`` optionally overrides ``topology.mesh`` (the dry-run passes a
    pre-built faked mesh)."""
    if comm not in COMM_MODES:
        raise ValueError(f"comm={comm!r} not in {COMM_MODES}")
    mesh = topology.mesh if mesh is None else mesh
    n_shards = mesh.shape[DATA_AXIS]
    N, D, K = csr_rows.idx.shape[1], csr_cols.idx.shape[1], cfg.K
    N_pad = ((N + n_shards - 1) // n_shards) * n_shards

    def pad_plane(x):
        if x.shape[1] == N_pad:
            return x
        pad = jnp.zeros((x.shape[0], N_pad - x.shape[1]) + x.shape[2:],
                        x.dtype)
        return jnp.concatenate([x, pad], axis=1)

    rows_arrs = tuple(pad_plane(x) for x in
                      (csr_rows.idx, csr_rows.val, csr_rows.mask))
    if comm == "gather":
        cols_arrs = (csr_cols.idx, csr_cols.val, csr_cols.mask)
        csrt_arrs = None
    else:
        if csrt is None:
            raise ValueError(f"comm={comm!r} needs the per-shard transposed "
                             f"planes (shard_transposed_planes)")
        cols_arrs = None
        csrt_arrs = tuple(jnp.asarray(x) for x in csrt)
        if csrt_arrs[0].shape[1] != n_shards:
            raise ValueError(f"csrt shard dim {csrt_arrs[0].shape[1]} != "
                             f"data axis {n_shards}")
    ks = jax.vmap(jax.random.split)(keys)
    U0, V0 = jax.vmap(lambda k: BMF.init_factors(k, N, D, K))(ks[:, 0])
    cfg_key = cfg._replace(n_samples=0, burnin=0, phase_bc_samples=None)
    u_use, v_use = prior_use if prior_use is not None else (None, None)
    fn = _run_gibbs_2d_jit_donated if donate else _run_gibbs_2d_jit
    with (GIBBS._quiet_donation() if donate
          else contextlib.nullcontext()):
        return fn(jax.random.key_data(ks[:, 1]), rows_arrs, cols_arrs,
                  csrt_arrs, test_rows, test_cols, cfg_key,
                  csr_rows.n_cols, csr_cols.n_cols,
                  jnp.asarray(cfg.n_samples, jnp.int32),
                  jnp.asarray(cfg.burnin, jnp.int32),
                  U_prior, V_prior, U0, V0, u_use, v_use,
                  mesh=mesh, comm=comm, n_rows=N, n_cols=D)


def run_gibbs_group(key, csr_rows: PaddedCSR, csr_cols: PaddedCSR,
                    test_rows, test_cols, cfg: BMF.BMFConfig,
                    topology, group: int = 0,
                    U_prior: Optional[RowGaussians] = None,
                    V_prior: Optional[RowGaussians] = None,
                    donate: bool = False, comm: str = "gather",
                    csrt=None) -> GIBBS.GibbsResult:
    """One block's chain data-sharded over a single topology group — the
    serial and async executors' dispatch unit on a group of >1 devices
    (``engine._group_chain``). Implemented as the B=1
    stacked 2-D executable on the group's (1, data) submesh, so every
    group shares one compilation per (bucket, group) and the chain
    matches ``run_gibbs`` under the same key (the stacked batched key
    handling is the single-block handling)."""
    stack = lambda x: jnp.expand_dims(x, 0) if x is not None else None
    stack_csr = lambda c: PaddedCSR(idx=stack(c.idx), val=stack(c.val),
                                    mask=stack(c.mask), n_cols=c.n_cols)
    pri = lambda p: (None if p is None else
                     RowGaussians(eta=stack(p.eta), Lambda=stack(p.Lambda)))
    res = run_gibbs_stacked_2d(
        jnp.expand_dims(key, 0), stack_csr(csr_rows), stack_csr(csr_cols),
        stack(jnp.asarray(test_rows)), stack(jnp.asarray(test_cols)), cfg,
        topology, U_prior=pri(U_prior), V_prior=pri(V_prior),
        donate=donate, comm=comm,
        csrt=None if csrt is None else tuple(x[None] for x in csrt),
        mesh=topology.group_mesh_2d(group))
    return jax.tree.map(lambda x: x[0], res)


def sweep_comm_bytes(D: int, K: int) -> int:
    """The paper's 'limited communication': bytes reduced per Gibbs sweep."""
    return 4 * (D * (K * K + K) + 2 * (K * K + K))


def sweep_comm_bytes_scatter(D: int, K: int) -> int:
    """Beyond-paper scatter-V variant (§Perf H6): a ring reduce-scatter
    moves half the bytes of a ring all-reduce, plus the tiny sampled-V
    gather."""
    return 4 * (D * (K * K + K) // 2 + D * K + 2 * (K * K + K))


def trace_chain_2d(cfg: BMF.BMFConfig, topology, n_rows: int, n_cols: int,
                   m_rows: int, m_cols: int, n_test: int, *,
                   batch: Optional[int] = None, comm: str = "gather",
                   donate: bool = False, u_prior: bool = True,
                   v_prior: bool = True,
                   prior_use: bool = False) -> "GIBBS.TracedChain":
    """Lowering hook for the static analyzer: trace the EXACT composed
    executable ``run_gibbs_stacked_2d`` dispatches — B blocks over the
    'block' axis, each chain data-sharded over the 'data' axis — at
    abstract shapes. Mirrors ``gibbs.trace_chain``'s contract (see
    ``TracedChain``); ``batch`` defaults to ``topology.block``."""
    if comm not in COMM_MODES:
        raise ValueError(f"comm={comm!r} not in {COMM_MODES}")
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    B = topology.block if batch is None else batch
    n_shards = topology.data
    K = cfg.K
    cfg_key = cfg._replace(n_samples=0, burnin=0, phase_bc_samples=None)
    N_pad = ((n_rows + n_shards - 1) // n_shards) * n_shards
    D_pad = ((n_cols + n_shards - 1) // n_shards) * n_shards

    rows = (S((B, N_pad, m_rows), i32), S((B, N_pad, m_rows), f32),
            S((B, N_pad, m_rows), f32))
    if comm == "gather":
        cols = (S((B, n_cols, m_cols), i32), S((B, n_cols, m_cols), f32),
                S((B, n_cols, m_cols), f32))
        csrt = None
    else:
        cols = None
        csrt = (S((B, n_shards, D_pad, m_cols), i32),
                S((B, n_shards, D_pad, m_cols), f32),
                S((B, n_shards, D_pad, m_cols), f32))
    tr, tc = S((B, n_test), i32), S((B, n_test), i32)
    ns, bi = S((), i32), S((), i32)
    up = (RowGaussians(eta=S((B, n_rows, K), f32),
                       Lambda=S((B, n_rows, K, K), f32)) if u_prior else None)
    vp = (RowGaussians(eta=S((B, n_cols, K), f32),
                       Lambda=S((B, n_cols, K, K), f32)) if v_prior else None)
    U0, V0 = S((B, n_rows, K), f32), S((B, n_cols, K), f32)
    uu = S((B,), f32) if prior_use else None
    named = [("key_data", S((B, 2), jnp.uint32)),
             ("csr_rows", rows), ("csr_cols", cols), ("csrt", csrt),
             ("test_rows", tr), ("test_cols", tc), ("n_samples", ns),
             ("burnin", bi), ("U_prior", up), ("V_prior", vp),
             ("U0", U0), ("V0", V0), ("u_use", uu), ("v_use", uu)]
    fn = _run_gibbs_2d_jit_donated if donate else _run_gibbs_2d_jit
    with (GIBBS._quiet_donation() if donate else contextlib.nullcontext()):
        traced = fn.trace(named[0][1], rows, cols, csrt, tr, tc, cfg_key,
                          n_cols, n_rows, ns, bi, up, vp, U0, V0, uu, uu,
                          mesh=topology.mesh, comm=comm,
                          n_rows=n_rows, n_cols=n_cols)
    # _DONATE_2D positions -> named entries (statics interleave at 6-8)
    dpos = (1, 2, 3, 4, 5, 10, 11)
    donated = GIBBS._donated_labels(named, dpos) if donate else ()
    # U0 cannot alias in the composed lowering: every sweep rebuilds the
    # full U as an all_gather of the data-sharded sampled rows, and a
    # collective's output is a fresh buffer — donating U0 only releases
    # it. V0 (gather mode runs the reference V-step) aliases in place.
    must = tuple(lb for lb in ("V0",) if lb in donated)
    return GIBBS.TracedChain(traced=traced,
                             param_labels=GIBBS._flat_param_labels(named),
                             donated_labels=donated, must_alias=must)
