"""BMF-PP training driver — the paper's end-to-end pipeline.

Usage:
  PYTHONPATH=src python -m repro.launch.bmf_train \
      --dataset movielens --blocks 4 --samples 60 \
      [--executor serial|stacked|sharded|async|streaming] [--topology B D]

--executor picks the phase-graph engine executor (core.engine): 'stacked'
(default) runs each PP phase's shape bucket as ONE vmapped Gibbs call;
'sharded' additionally spreads that batch over all local devices on a
'block' mesh (set XLA_FLAGS=--xla_force_host_platform_device_count=N to
fake a mesh on CPU); 'async' overlaps phases b/c with a dependency-driven
scheduler (per-device streams when >1 device, donated buffers,
device-resident posteriors); 'streaming' bounds the live device footprint
to a window of --window donated block buffers (prefetched host planes,
critical-path-first dispatch) for grids whose stacked buckets don't fit
device memory; 'serial' is the reference per-block loop.

--topology B D places the run on the unified 2-D ('block','data') mesh
(core.topology.Topology): B device groups run blocks concurrently while
each block's Gibbs sweep is sharded over the D devices of its group —
the paper's combined system (block-parallel PP x intra-block distributed
BMF). Composes with --executor sharded (2-D shard_map), async (group
streams), streaming (one donated window per group), and serial (B=1:
``--executor serial --topology 1 N`` shards each block's chain over N
devices, one block at a time).

Fault tolerance: --on-fault/--max-retries set the engine's chain-health
policy (core/README.md "Fault tolerance"); --ckpt-dir persists each
resolved block's posteriors so a killed run restarts with --resume and
finishes bitwise-identical to an uninterrupted one.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.core import bmf as BMF
from repro.core import pp as PP
from repro.core.partition import nnz_balance_stats, partition, suggest_grid
from repro.data import synthetic as SYN
from repro.data.sparse import train_test_split
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="movielens",
                    choices=list(SYN.PRESETS))
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--samples", type=int, default=60)
    ap.add_argument("--k", type=int, default=0, help="0 = preset K (capped 16)")
    ap.add_argument("--executor", default="stacked",
                    choices=["serial", "stacked", "sharded", "async",
                             "streaming"],
                    help="phase-graph engine executor (core.engine)")
    ap.add_argument("--window", type=int, default=0,
                    help="streaming executor window size W (0 = default)")
    ap.add_argument("--topology", type=int, nargs=2, default=None,
                    metavar=("BLOCK", "DATA"),
                    help="2-D ('block','data') placement: BLOCK device "
                         "groups x DATA devices per group (unified "
                         "core.topology mesh); '1 N' shards each block's "
                         "chain over N devices")
    ap.add_argument("--phase-bc-samples", type=int, default=0)
    ap.add_argument("--fused-sweep", action="store_true",
                    help="one-kernel Gibbs sweep (kernels/bmf_sweep): the "
                         "whole factor step in one pass — Pallas on TPU, "
                         "bitwise-identical striped XLA elsewhere")
    ap.add_argument("--sweep-dtype", default="fp32",
                    choices=["fp32", "bf16"],
                    help="fused-sweep precision: bf16 runs the gather + "
                         "precision accumulate in bf16 (f32 factorization "
                         "always); only meaningful with --fused-sweep")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-dir", default="",
                    help="block-level phase-graph checkpoint directory: "
                         "each resolved block's posteriors persist there "
                         "(atomic per-block files), making the run "
                         "resumable with --resume")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="flush block checkpoints every N resolves "
                         "(a kill loses at most N-1 blocks)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --ckpt-dir: restored blocks are "
                         "skipped and the finished run is bitwise-identical "
                         "to an uninterrupted one")
    ap.add_argument("--on-fault", default="raise",
                    choices=["raise", "degrade"],
                    help="after --max-retries failed re-runs of a faulty "
                         "block: raise, or degrade it to its propagated "
                         "prior (recorded in the fault ledger)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="bounded re-runs of an unhealthy block "
                         "(re-split key + jittered prior)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir (the directory the "
                         "interrupted run checkpointed into)")
    use_compile_cache()

    coo, p = SYN.generate(args.dataset, seed=args.seed)
    train, test = train_test_split(coo, 0.1, seed=args.seed + 1)
    K = args.k or min(p.K, 16)
    cfg = BMF.BMFConfig(K=K, n_samples=args.samples,
                        burnin=args.samples // 3,
                        phase_bc_samples=args.phase_bc_samples or None,
                        sweep_fused=args.fused_sweep,
                        sweep_dtype=args.sweep_dtype)

    I, J = suggest_grid(train.n_rows, train.n_cols, args.blocks)
    part = partition(train, I, J)
    print(f"dataset={args.dataset} N={train.n_rows} D={train.n_cols} "
          f"nnz={train.nnz} grid={I}x{J} K={K}")
    print("block nnz balance:", nnz_balance_stats(part))

    topology = None
    if args.topology:
        from repro.core.topology import Topology
        topology = Topology(block=args.topology[0], data=args.topology[1])
        print(topology.describe())
    if args.executor == "sharded":
        print(f"sharded executor: {len(jax.devices())}-way block mesh")
    elif args.executor == "async":
        print(f"async executor: dependency-driven overlap, "
              f"{len(jax.devices())} device stream(s)")
    elif args.executor == "streaming":
        print(f"streaming executor: bounded window of "
              f"{args.window or 4} donated block buffers, "
              f"critical-path-first dispatch")

    res = PP.run_pp(jax.random.key(args.seed), part, cfg, test,
                    verbose=True, executor=args.executor, window=args.window or None,
                    topology=topology, on_fault=args.on_fault,
                    max_retries=args.max_retries,
                    checkpoint_dir=args.ckpt_dir or None,
                    ckpt_every=args.ckpt_every,
                    resume_from=(args.ckpt_dir if args.resume else None))
    print(f"executor={res.executor}  RMSE={res.rmse:.4f}  "
          f"wall={res.wall_time_s:.1f}s  "
          f"phases={ {k: round(v, 2) for k, v in res.phase_times_s.items()} }")
    if res.resumed_blocks:
        print(f"resumed {res.resumed_blocks} block(s) from {args.ckpt_dir}")
    if res.faults:
        print(f"faults: {len(res.faults)} event(s), "
              f"{res.n_retries} retr{'y' if res.n_retries == 1 else 'ies'} — "
              + "; ".join(f"{f.kind}@{f.coord}:{f.action}"
                          for f in res.faults))
    print(f"modeled 16-worker wall: {res.modeled_parallel_s(16):.1f}s")
    if res.block_spans_s:
        print(f"measured critical path: {res.critical_path_s():.1f}s "
              f"(dispatch→resolve spans, dependency chain)")

    if args.ckpt:
        ckpt.save(args.ckpt, {"U_eta": res.U_agg.eta, "U_Lam": res.U_agg.Lambda,
                              "V_eta": res.V_agg.eta, "V_Lam": res.V_agg.Lambda},
                  extra={"rmse": res.rmse, "grid": [I, J]})
        print("checkpoint ->", args.ckpt)


if __name__ == "__main__":
    main()
