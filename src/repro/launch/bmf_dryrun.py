import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Production-mesh dry-run for the paper's OWN workload: one block's
data-sharded Gibbs chain at real-Netflix scale, lowered on the 256-chip
'data' ring (one PP block spanning a pod's worth of chips).

Records roofline terms for the paper-faithful (psum) and beyond-paper
(scatter-V, §Perf H6) item-stat exchanges — the artifact behind the
EXPERIMENTS §Scaling saturation analysis.

--pp-engine additionally lowers the phase-graph engine's sharded phase-c
bucket (core.engine.ShardedExecutor: one batched Gibbs chain shard_map'd
over a 'block' mesh) and records that NO collective appears inside the
phase — the engine moves posterior summaries only at phase boundaries,
which is the paper's entire communication budget. It also lowers the
COMPOSED 2-D topology executable (core.topology: blocks over the 'block'
axis, each block's sweep distributed over the 'data' axis) and asserts
from the HLO replica groups that every collective is confined to a
'data' row — the scatter-V / factor-gather exchange inside one block —
with zero collectives crossing the 'block' axis. It also lowers the
ASYNC executor's unit of work — one interior block's DONATED per-block
chain executable (core.engine.AsyncExecutor dispatches these
dependency-driven onto per-device streams) — and records the
input_output_alias map XLA builds from the donation: aliased bytes are
buffers the chain reuses in place, donated-but-unaliased bytes are
released back to the allocator at dispatch.

  python -m repro.launch.bmf_dryrun [--shards 256] [--k 100] [--pp-engine]
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import analysis as LINT
from repro.core import bmf as BMF
from repro.core import distributed as DIST
from repro.roofline import analysis as ROOF
from repro.roofline import jaxpr_cost as JCOST

OUT = Path(__file__).resolve().parents[3] / "benchmarks" / "bmf_dryrun_results.json"


def lower_sweep(n_shards: int, N: int, D: int, M: int, K: int, comm: str):
    """Lower one block's chain data-sharded over a 'data' ring of
    ``n_shards`` devices — the one chain body (``gibbs._run_gibbs_impl``
    with the data-sharded samplers), as the B=1 composed executable
    ``distributed.run_gibbs_group`` dispatches — with the paper-faithful
    'psum' or the beyond-paper 'scatter' (§Perf H6) item-stat exchange.
    Flop terms count the sweep loop's body once (chain_len=1)."""
    rec = lower_pp_phase_2d(1, n_shards, N, D, M, K, chain_len=1, comm=comm)
    D_pad = ((D + n_shards - 1) // n_shards) * n_shards
    rec.update(variant=f"data_sharded_chain[{comm}]", n_shards=n_shards,
               analytic_comm_bytes=(DIST.sweep_comm_bytes_scatter
                                    if comm == "scatter"
                                    else DIST.sweep_comm_bytes)(D_pad, K))
    return rec


def lower_pp_phase(n_blocks: int, N: int, D: int, M: int, K: int,
                   chain_len: int):
    """Lower the engine's sharded phase-c bucket: B=n_blocks interior
    blocks, each (N/block-rows × D/block-cols), ONE chain executable
    shard_map'd over the 'block' mesh. Expect zero collective bytes —
    same-phase blocks never talk to each other."""
    from repro.core import gibbs as GIBBS
    from repro.core.distributed import make_block_mesh
    from repro.core.posterior import RowGaussians

    mesh = make_block_mesh(n_blocks)
    cfg = BMF.BMFConfig(K=K)._replace(n_samples=0, burnin=0,
                                      phase_bc_samples=None)
    B = n_blocks
    m_c = max(8, (M * N // D // 8) * 8)
    n_test = 1024
    S = jax.ShapeDtypeStruct
    key_data = S((B, 2), jnp.uint32)
    prior_u = (S((B, N, K), jnp.float32), S((B, N, K, K), jnp.float32))
    prior_v = (S((B, D, K), jnp.float32), S((B, D, K, K), jnp.float32))
    args = (
        key_data,
        (S((B, N, M), jnp.int32), S((B, N, M), jnp.float32),
         S((B, N, M), jnp.float32)),
        (S((B, D, m_c), jnp.int32), S((B, D, m_c), jnp.float32),
         S((B, D, m_c), jnp.float32)),
        S((B, n_test), jnp.int32), S((B, n_test), jnp.int32),
        S((), jnp.int32), S((), jnp.int32),
        RowGaussians(eta=prior_u[0], Lambda=prior_u[1]),
        RowGaussians(eta=prior_v[0], Lambda=prior_v[1]),
        S((B, N, K), jnp.float32), S((B, D, K), jnp.float32),
    )
    traced = GIBBS._run_gibbs_stacked_jit.trace(
        args[0], args[1], args[2], args[3], args[4], cfg, D, N,
        args[5], args[6], args[7], args[8], args[9], args[10], mesh=mesh)
    jcost = JCOST.jaxpr_cost(traced.jaxpr, mult=chain_len)
    compiled = traced.lower().compile()
    coll = ROOF.collective_bytes(compiled.as_text())
    terms = ROOF.terms_from(jcost, compiled.as_text(), n_blocks)
    return {
        "variant": "pp_phase_c_sharded",
        "n_blocks": n_blocks, "N": N, "D": D, "M": M, "K": K,
        "chain_len": chain_len,
        "roofline": terms.as_dict(),
        "collectives": coll,
        "intra_phase_collective_bytes": float(sum(coll.values())),
    }


def lower_pp_phase_2d(n_block: int, n_data: int, N: int, D: int, M: int,
                      K: int, chain_len: int, comm: str = "scatter"):
    """Lower the COMPOSED executable — the unified 2-D topology's unit of
    work: B=n_block interior blocks shard_map'd over the 'block' axis while
    each block's Gibbs sweep runs the intra-block distributed chain over
    the 'data' axis (distributed.run_gibbs_stacked_2d). Asserts, from the
    compiled HLO's replica groups, the paper's communication structure:
    every intra-phase collective is CONFINED to a 'data' row (the
    scatter-V / psum / factor-gather exchanges inside one block's chain)
    and ZERO collectives run on the 'block' axis — blocks never talk."""
    from repro.core import gibbs as GIBBS
    from repro.core import distributed as DIST
    from repro.core.posterior import RowGaussians
    from repro.core.topology import Topology

    topo = Topology(block=n_block, data=n_data)
    cfg = BMF.BMFConfig(K=K)._replace(n_samples=0, burnin=0,
                                      phase_bc_samples=None)
    B, S = n_block, n_data
    N_pad = ((N + S - 1) // S) * S
    D_pad = ((D + S - 1) // S) * S if comm == "scatter" else D
    m_c = max(8, (M * N // D // 8) * 8)
    n_test = 1024
    Sd = jax.ShapeDtypeStruct
    rows = (Sd((B, N_pad, M), jnp.int32), Sd((B, N_pad, M), jnp.float32),
            Sd((B, N_pad, M), jnp.float32))
    if comm == "gather":
        cols = (Sd((B, D, m_c), jnp.int32), Sd((B, D, m_c), jnp.float32),
                Sd((B, D, m_c), jnp.float32))
        csrt = None
    else:
        cols = None
        csrt = (Sd((B, S, D_pad, m_c), jnp.int32),
                Sd((B, S, D_pad, m_c), jnp.float32),
                Sd((B, S, D_pad, m_c), jnp.float32))
    args = (
        Sd((B, 2), jnp.uint32), rows, cols, csrt,
        Sd((B, n_test), jnp.int32), Sd((B, n_test), jnp.int32),
        Sd((), jnp.int32), Sd((), jnp.int32),
        RowGaussians(eta=Sd((B, N, K), jnp.float32),
                     Lambda=Sd((B, N, K, K), jnp.float32)),
        RowGaussians(eta=Sd((B, D, K), jnp.float32),
                     Lambda=Sd((B, D, K, K), jnp.float32)),
        Sd((B, N, K), jnp.float32), Sd((B, D, K), jnp.float32),
    )
    traced = DIST._run_gibbs_2d_jit.trace(
        args[0], args[1], args[2], args[3], args[4], args[5], cfg, D, N,
        args[6], args[7], args[8], args[9], args[10], args[11], None, None,
        mesh=topo.mesh, comm=comm, n_rows=N, n_cols=D)
    jcost = JCOST.jaxpr_cost(traced.jaxpr, mult=chain_len)
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    coll = ROOF.collective_bytes(hlo)
    terms = ROOF.terms_from(jcost, hlo, n_block * n_data)
    # 'data'-axis rows in flattened mesh order: group g = [g*S, (g+1)*S)
    data_rows = [list(range(g * S, (g + 1) * S)) for g in range(B)]
    # the confinement + per-comm-budget invariant now lives in the pass
    # registry (analysis.hlo_passes); dryrun enrolls its lowering like
    # any other artifact instead of hand-rolling the check
    violations = LINT.analyze(LINT.HLOArtifact(
        label=f"pp_phase_c_composed_2d[{comm}]", hlo_text=hlo, comm=comm,
        allowed_groups=data_rows))
    assert not violations, (
        "composed executable fails the collective lint:\n"
        + "\n".join(str(v) for v in violations))
    confinement = ROOF.collectives_confined_to_groups(hlo, data_rows)
    return {
        "variant": "pp_phase_c_composed_2d",
        "comm": comm,
        "topology": [n_block, n_data],
        "N": N, "D": D, "M": M, "K": K, "chain_len": chain_len,
        "roofline": terms.as_dict(),
        "collectives": coll,
        "collective_axis_check": {
            "n_collectives": confinement["n_collectives"],
            "n_confined_to_data_axis": confinement["n_confined"],
            "n_crossing_block_axis": confinement["n_crossing"],
        },
    }


def lower_pp_window(window: int, n_blocks: int, N: int, D: int, M: int,
                    K: int, chain_len: int):
    """Lower the STREAMING executor's unit of work — one window chunk:
    the stacked chain at batch W with per-block prior-use flags and
    donated buffers (gibbs._run_gibbs_stacked_jit_donated, exactly what
    StreamingExecutor dispatches per chunk) — and the full-bucket stacked
    executable at batch B=n_blocks for comparison. XLA's buffer assignment
    (arg + temp + out − alias) shows the streaming point: the per-dispatch
    peak scales with W, flat in the grid size, while the stacked bucket
    scales with B."""
    import warnings

    from repro.core import gibbs as GIBBS
    from repro.core.posterior import RowGaussians

    cfg = BMF.BMFConfig(K=K)._replace(n_samples=0, burnin=0,
                                      phase_bc_samples=None)
    m_c = max(8, (M * N // D // 8) * 8)
    n_test = 1024
    S = jax.ShapeDtypeStruct

    def effective_peak(B, flags):
        args = (
            S((B, 2), jnp.uint32),
            (S((B, N, M), jnp.int32), S((B, N, M), jnp.float32),
             S((B, N, M), jnp.float32)),
            (S((B, D, m_c), jnp.int32), S((B, D, m_c), jnp.float32),
             S((B, D, m_c), jnp.float32)),
            S((B, n_test), jnp.int32), S((B, n_test), jnp.int32),
            S((), jnp.int32), S((), jnp.int32),
            RowGaussians(eta=S((B, N, K), jnp.float32),
                         Lambda=S((B, N, K, K), jnp.float32)),
            RowGaussians(eta=S((B, D, K), jnp.float32),
                         Lambda=S((B, D, K, K), jnp.float32)),
            S((B, N, K), jnp.float32), S((B, D, K), jnp.float32),
        )
        uu = S((B,), jnp.float32) if flags else None
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            traced = GIBBS._run_gibbs_stacked_jit_donated.trace(
                args[0], args[1], args[2], args[3], args[4], cfg, D, N,
                args[5], args[6], args[7], args[8], args[9], args[10],
                uu, uu, mesh=None)
            ma = traced.lower().compile().memory_analysis()
        return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)

    win = effective_peak(window, flags=True)
    bucket = effective_peak(n_blocks, flags=False)
    return {
        "variant": "pp_window_streaming_donated",
        "window": window, "n_blocks": n_blocks,
        "N": N, "D": D, "M": M, "K": K, "chain_len": chain_len,
        "window_effective_peak_bytes": int(win),
        "stacked_bucket_effective_peak_bytes": int(bucket),
        "peak_ratio": float(win / max(bucket, 1)),
    }


def lower_pp_block_async(N: int, D: int, M: int, K: int, chain_len: int):
    """Lower the async executor's per-block unit: ONE interior (phase-c)
    block's chain with donated input buffers (gibbs._run_gibbs_jit_donated
    — the exact executable AsyncExecutor dispatches per readiness event).
    Records the donation outcome from the compiled module: alias bytes
    (inputs XLA rewrites in place — U0/V0 onto the U/V outputs) and the
    donated-but-unaliased remainder (padded CSR planes/test indices, whose
    buffers return to the allocator at dispatch instead of run end). A
    single-block executable trivially has zero intra-phase collectives —
    async streams only communicate O(K²) summaries at readiness edges."""
    from repro.core import gibbs as GIBBS
    from repro.core.posterior import RowGaussians

    cfg = BMF.BMFConfig(K=K)._replace(n_samples=0, burnin=0,
                                      phase_bc_samples=None)
    m_c = max(8, (M * N // D // 8) * 8)
    n_test = 1024
    S = jax.ShapeDtypeStruct
    csr_r = (S((N, M), jnp.int32), S((N, M), jnp.float32),
             S((N, M), jnp.float32))
    csr_c = (S((D, m_c), jnp.int32), S((D, m_c), jnp.float32),
             S((D, m_c), jnp.float32))
    args = (
        jax.eval_shape(lambda: jax.random.key(0)),
        csr_r, csr_c,
        S((n_test,), jnp.int32), S((n_test,), jnp.int32),
        S((), jnp.int32), S((), jnp.int32),
        RowGaussians(eta=S((N, K), jnp.float32),
                     Lambda=S((N, K, K), jnp.float32)),
        RowGaussians(eta=S((D, K), jnp.float32),
                     Lambda=S((D, K, K), jnp.float32)),
        S((N, K), jnp.float32), S((D, K), jnp.float32),
    )
    import warnings
    with warnings.catch_warnings():
        # the un-aliasable donations (CSR planes, test indices) are noted
        # by XLA; expected — see gibbs._quiet_donation
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        traced = GIBBS._run_gibbs_jit_donated.trace(
            args[0], args[1], args[2], args[3], args[4], cfg, D, N,
            args[5], args[6], args[7], args[8], args[9], args[10])
        jcost = JCOST.jaxpr_cost(traced.jaxpr, mult=chain_len)
        compiled = traced.lower().compile()
    hlo = compiled.as_text()
    ma = compiled.memory_analysis()
    alias_bytes = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
    def nbytes(s):
        return int(np.dtype(s.dtype).itemsize) * int(np.prod(s.shape))

    donated_bytes = (sum(nbytes(s) for s in csr_r + csr_c)
                     + nbytes(args[3]) + nbytes(args[4])
                     + nbytes(args[9]) + nbytes(args[10]))
    coll = ROOF.collective_bytes(hlo)
    terms = ROOF.terms_from(jcost, hlo, 1)
    return {
        "variant": "pp_block_async_donated",
        "N": N, "D": D, "M": M, "K": K, "chain_len": chain_len,
        "roofline": terms.as_dict(),
        "collectives": coll,
        "intra_phase_collective_bytes": float(sum(coll.values())),
        "has_input_output_alias": "input_output_alias=" in hlo,
        "alias_bytes": alias_bytes,
        "donated_input_bytes": donated_bytes,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=256)
    ap.add_argument("--k", type=int, default=100)
    # real-Netflix dims; M = padded nnz/row budget after balance permutation
    ap.add_argument("--n", type=int, default=480_256)
    ap.add_argument("--d", type=int, default=17_792)
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--pp-engine", action="store_true",
                    help="also lower the sharded phase-c bucket "
                         "(16 interior blocks of a 5x5 grid)")
    ap.add_argument("--samples", type=int, default=60,
                    help="chain length used to scale --pp-engine flop terms")
    ap.add_argument("--window", type=int, default=4,
                    help="streaming window W lowered by --pp-engine")
    ap.add_argument("--topo", type=int, nargs=2, default=(16, 16),
                    metavar=("BLOCK", "DATA"),
                    help="('block','data') shape of the composed 2-D "
                         "executable lowered by --pp-engine")
    args = ap.parse_args()

    results = []
    for comm in ("psum", "scatter"):
        rec = lower_sweep(args.shards, args.n, args.d, args.m, args.k, comm)
        results.append(rec)
        rf = rec["roofline"]
        print(f"{rec['variant']} compute={rf['compute_s']:.3e}s "
              f"memory={rf['memory_s']:.3e}s collective={rf['collective_s']:.3e}s "
              f"dominant={rf['dominant']} "
              f"(analytic comm {rec['analytic_comm_bytes']/1e6:.0f} MB)")
    if args.pp_engine:
        # 5x5 grid of the same matrix -> 16 interior (phase-c) blocks
        rec = lower_pp_phase(16, args.n // 5 + 1, args.d // 5 + 1,
                             max(8, args.m // 4), args.k, args.samples)
        results.append(rec)
        print(f"{rec['variant']} blocks={rec['n_blocks']} "
              f"intra-phase collective bytes="
              f"{rec['intra_phase_collective_bytes']:.0f} "
              f"(phase boundary is the only communication)")
        # the composed 2-D topology executable: BLOCK groups x DATA-way
        # intra-block sharding (default 16x16 = 256 of the 512 faked
        # chips), scatter-V / factor-gather inside each block, ZERO
        # 'block'-axis collectives (asserted from the HLO replica groups)
        tb, td = args.topo
        for comm in ("scatter", "gather"):
            rec = lower_pp_phase_2d(tb, td, args.n // 5 + 1,
                                    args.d // 5 + 1, max(8, args.m // 4),
                                    args.k, args.samples, comm=comm)
            results.append(rec)
            chk = rec["collective_axis_check"]
            print(f"{rec['variant']}[{comm}] topology={tb}x{td} "
                  f"collectives={chk['n_collectives']} "
                  f"confined-to-'data'={chk['n_confined_to_data_axis']} "
                  f"crossing-'block'={chk['n_crossing_block_axis']}")
        rec = lower_pp_block_async(args.n // 5 + 1, args.d // 5 + 1,
                                   max(8, args.m // 4), args.k, args.samples)
        results.append(rec)
        print(f"{rec['variant']} alias_bytes={rec['alias_bytes']} "
              f"donated={rec['donated_input_bytes']/1e6:.0f}MB "
              f"intra-phase collective bytes="
              f"{rec['intra_phase_collective_bytes']:.0f}")
        rec = lower_pp_window(args.window, 16, args.n // 5 + 1,
                              args.d // 5 + 1, max(8, args.m // 4), args.k,
                              args.samples)
        results.append(rec)
        print(f"{rec['variant']} W={rec['window']} "
              f"window peak={rec['window_effective_peak_bytes']/1e6:.0f}MB "
              f"vs stacked bucket="
              f"{rec['stacked_bucket_effective_peak_bytes']/1e6:.0f}MB "
              f"(x{rec['peak_ratio']:.2f})")
    OUT.write_text(json.dumps(results, indent=1))
    print("->", OUT)


if __name__ == "__main__":
    main()
