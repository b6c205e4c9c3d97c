"""Paper Table 1 (bottom rows): rows/sec and ratings/sec of the Gibbs
sampler per dataset — measured on this host, derived = both metrics.

``--use-kernel both`` (default) runs the XLA-gather baseline AND the
zero-materialization fused path (Pallas on TPU, N-striped symmetric
matmul elsewhere) back to back so the two hot paths are directly comparable in
one run; ``--json-out`` additionally writes the records as JSON (the CI
smoke check uploads them as the BENCH_throughput.json artifact).

``--distributed`` additionally measures one block's chain data-sharded over
all devices (core.distributed.run_gibbs_group) with its paper-faithful
psum and beyond-paper scatter-V item-stat exchanges, crossed with the
kernel paths.
Fakes a 4-device CPU mesh via XLA_FLAGS when no multi-device platform is
present (must happen before the first jax backend touch)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# --distributed wants >1 device; the flag only takes effect before the
# backend initializes, hence the pre-import peek at argv
if "--distributed" in sys.argv and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import jax
import numpy as np

from repro.core import bmf as BMF
from repro.core import gibbs as GIBBS
from repro.data import synthetic as SYN
from repro.data.sparse import coo_to_padded_csr, train_test_split

from benchmarks.common import emit

# --use-kernel flag value -> list of use_kernel settings to run (shared
# with bench_roofline so the two benchmarks can't drift)
KERNEL_PATHS = {"on": [True], "off": [False], "both": [False, True]}


def path_name(use_kernel: bool) -> str:
    """Label records by the implementation actually measured: off TPU,
    use_kernel=True dispatches to the N-striped XLA fallback, not the
    Pallas kernel."""
    if not use_kernel:
        return "xla_gather"
    return "fused_pallas" if jax.default_backend() == "tpu" else "striped_xla"


def run(dataset: str, n_probe: int = 8, use_kernel: bool = False,
        sweep_fused: bool = False, sweep_dtype: str = "fp32"):
    coo, p = SYN.generate(dataset, seed=51)
    train, _ = train_test_split(coo, 0.1, seed=52)
    csr_r = coo_to_padded_csr(train)
    csr_c = coo_to_padded_csr(train.transpose())
    K = min(p.K, 16)
    cfg = BMF.BMFConfig(K=K, n_samples=n_probe, burnin=0,
                        use_kernel=use_kernel, sweep_fused=sweep_fused,
                        sweep_dtype=sweep_dtype)
    dummy = np.zeros(1, np.int32)
    # warmup + compile (synced so no warmup tail leaks into the timed region)
    jax.block_until_ready(
        GIBBS.run_gibbs(jax.random.key(0), csr_r, csr_c, dummy, dummy,
                        cfg._replace(n_samples=1)))
    t0 = time.time()
    jax.block_until_ready(
        GIBBS.run_gibbs(jax.random.key(0), csr_r, csr_c, dummy, dummy, cfg).U)
    dt = (time.time() - t0) / n_probe
    rows_per_s = (train.n_rows + train.n_cols) / dt
    ratings_per_s = 2 * train.nnz / dt   # each rating visited in both factors
    path = "fused_sweep" if sweep_fused else path_name(use_kernel)
    tag = f"{path}/{sweep_dtype}" if sweep_fused else path
    emit(f"table1_throughput/{dataset}/{tag}", dt,
         f"rows_per_s={rows_per_s:.0f};ratings_per_s={ratings_per_s:.0f};K={K}")
    rec = {"dataset": dataset, "path": path, "use_kernel": use_kernel,
           "sec_per_sweep": dt, "rows_per_s": rows_per_s,
           "ratings_per_s": ratings_per_s, "K": K, "nnz": train.nnz,
           "n_rows": train.n_rows, "n_cols": train.n_cols,
           "max_nnz_row": csr_r.max_nnz, "backend": jax.default_backend()}
    if sweep_fused:
        rec["sweep_dtype"] = sweep_dtype
    return rec


def run_distributed(dataset: str, n_probe: int, use_kernel: bool,
                    scatter_v: bool):
    """Data-sharded chain throughput: scatter-V × kernel paths."""
    from repro.core import distributed as DIST
    from repro.core.topology import Topology
    coo, p = SYN.generate(dataset, seed=51)
    train, _ = train_test_split(coo, 0.1, seed=52)
    csr_r = coo_to_padded_csr(train)
    csr_c = coo_to_padded_csr(train.transpose())
    K = min(p.K, 16)
    n_dev = len(jax.devices())
    topo = Topology(block=1, data=n_dev)
    n_rows_pad = -(-train.n_rows // n_dev) * n_dev
    n_cols_pad = (-(-train.n_cols // n_dev) * n_dev if scatter_v
                  else train.n_cols)
    csrt = DIST.shard_transposed_planes(train.row, train.col, train.val,
                                        n_dev, n_rows_pad, n_cols_pad,
                                        int(csr_c.idx.shape[1]))
    dummy = np.zeros(1, np.int32)

    def chain_secs(n):
        cfg = BMF.BMFConfig(K=K, n_samples=n, burnin=0,
                            use_kernel=use_kernel)
        t0 = time.time()
        jax.block_until_ready(DIST.run_gibbs_group(
            jax.random.key(0), csr_r, csr_c, dummy, dummy, cfg, topo,
            comm="scatter" if scatter_v else "psum", csrt=csrt).U)
        return time.time() - t0

    # the chain length is a traced argument, so every call after the
    # first reuses one executable: the difference of an (n_probe+1)-sweep
    # and a 1-sweep call isolates n_probe steady-state sweeps
    chain_secs(1)                                  # compile + warmup
    t_one = chain_secs(1)
    t_many = chain_secs(n_probe + 1)
    dt = max(t_many - t_one, 1e-9) / n_probe
    variant = "dist_scatter_v" if scatter_v else "dist_psum"
    path = f"{variant}/{path_name(use_kernel)}"
    ratings_per_s = 2 * train.nnz / dt
    emit(f"table1_throughput/{dataset}/{path}", dt,
         f"ratings_per_s={ratings_per_s:.0f};K={K};devices={n_dev}")
    return {"dataset": dataset, "path": path, "use_kernel": use_kernel,
            "scatter_v": scatter_v, "n_devices": n_dev,
            "sec_per_sweep": dt, "ratings_per_s": ratings_per_s,
            "rows_per_s": (train.n_rows + train.n_cols) / dt, "K": K,
            "nnz": train.nnz, "n_rows": train.n_rows,
            "n_cols": train.n_cols, "max_nnz_row": csr_r.max_nnz,
            "backend": jax.default_backend(),
            "comm_bytes_per_sweep": (
                DIST.sweep_comm_bytes_scatter(train.n_cols, K) if scatter_v
                else DIST.sweep_comm_bytes(train.n_cols, K))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", nargs="+", default=["movielens", "amazon"])
    ap.add_argument("--use-kernel", choices=["on", "off", "both", "fused"],
                    default="both",
                    help="fused zero-materialization path, XLA-gather "
                         "baseline, or both for a side-by-side; 'fused' "
                         "measures ONLY the one-kernel Gibbs sweep "
                         "(kernels/bmf_sweep, fp32 + bf16 rows)")
    ap.add_argument("--distributed", action="store_true",
                    help="also measure the data-sharded chain, psum and "
                         "scatter-V variants crossed with the kernel paths")
    ap.add_argument("--n-probe", type=int, default=8)
    ap.add_argument("--json-out", default=None,
                    help="also write records to this JSON file")
    args = ap.parse_args()
    recs = []
    for d in args.datasets:
        for uk in KERNEL_PATHS.get(args.use_kernel, []):
            recs.append(run(d, n_probe=args.n_probe, use_kernel=uk))
            if args.distributed:
                for sv in (False, True):
                    recs.append(run_distributed(d, n_probe=args.n_probe,
                                                use_kernel=uk, scatter_v=sv))
        # the one-kernel sweep rides along with 'both' (artifact
        # regeneration keeps every hot path side by side) and is the sole
        # subject of 'fused' (the CI smoke): fp32 and bf16 rows each
        if args.use_kernel in ("both", "fused"):
            for dt in ("fp32", "bf16"):
                recs.append(run(d, n_probe=args.n_probe,
                                sweep_fused=True, sweep_dtype=dt))
    if args.json_out:
        payload = {"benchmark": "table1_throughput",
                   "backend": jax.default_backend(),
                   "records": recs}
        if args.distributed:
            payload["note"] = (
                "this run faked a multi-device CPU mesh via XLA_FLAGS "
                f"host_platform_device_count ({len(jax.devices())} devices); "
                "dist_* records measure the data-sharded chain there, and the "
                "plain-path records of the same process share that env")
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2)


if __name__ == "__main__":
    main()
