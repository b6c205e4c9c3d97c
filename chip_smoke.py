#!/usr/bin/env python3
"""Chip smoke test: BMF-PP training and serving at full MovieLens-20M size
on a TPU, through the entry points a user calls.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # the composed 2x2 topology vs one device

One chip, in order:
  1. device check — exits non-zero when JAX finds no TPU (no CPU fallback);
  2. kernel parity on the device over one real block's padded planes:
     ``fused_sweep`` Pallas vs its striped-XLA path, and
     ``precision_accum_fused`` vs ``precision_accum_chunked``;
  3. training: ``run_pp`` on the Table-1 MovieLens shape (138,493 users x
     27,278 items, 144 ratings per user, scale 1-5, K=10), 4x4 grid,
     streaming executor, with the one-pass sweep kernel doing every factor
     step; asserts a finite held-out RMSE below the mean predictor's and an
     empty fault ledger;
  4. serving: a ``PosteriorStore`` built from the result answers mean-mode
     top-K requests through ``MicroBatchRouter``; each answer is checked
     against the dense numpy top-K (``bmf_serve.check_parity``).

``--four-chips`` runs only the paper's combined system — PP groups x
intra-block 'data' sharding, ``Topology(2, 2)``, comm 'gather' — and the
same chain on one device of that host, on 1/16 of the rows and items, and
compares their RMSEs.

The sample count is cut to fit the time limit; each cut is printed.

The last line of stdout is one JSON object: ``{"ok": true, "device":
{"platform", "kind", "count"}}``; everything else is printed before it.
Data is generated from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bmf as BMF  # noqa: E402
from repro.core import posterior as POST  # noqa: E402
from repro.core import pp as PP  # noqa: E402
from repro.core.partition import partition  # noqa: E402
from repro.core.topology import Topology  # noqa: E402
from repro.data import synthetic as SYN  # noqa: E402
from repro.data.sparse import train_test_split  # noqa: E402
from repro.kernels.bmf_precision import ops as PREC  # noqa: E402
from repro.kernels.bmf_sweep import ops as SWEEP  # noqa: E402
from repro.kernels.route import pallas_route  # noqa: E402
from repro.launch.bmf_serve import build_requests, check_parity  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serving import MicroBatchRouter, PosteriorStore  # noqa: E402
from bench.compile_stats import CompileStats  # noqa: E402

# paper Table 1, MovieLens-20M, at full size
MOVIELENS_20M = SYN.DatasetPreset("movielens-20m", n_rows=138_493,
                                  n_cols=27_278, ratings_per_row=144,
                                  scale_lo=1, scale_hi=5, K=10, true_rank=8)
# 4x4 streaming window of one block: phase a's window executable compiles to
# 2.69 GiB of arguments + 7.53 GiB of temporaries for a v5e (W=2: 20 GiB).
# Each factor step costs ~0.74 ms per 8-row tile on the chip, so a 4x4 grid
# (each row in 4 blocks) takes half the time per sample of an 8x8 one.
GRID = (4, 4)
WINDOW = 1
# cut from BMFConfig's 60 samples / 20 burn-in so the run fits the time
# limit; rows are not cut
SAMPLES, BURNIN = 5, 2
# the four-chip comparison runs both chains on 1/16 of the rows and items
# (same ratings per row, scale and K): it checks placement and collectives
# on real devices, and four chips cost four times the chip time
FOUR_CHIP_CUT = 16
# kernel-vs-XLA-path tolerance on the device; both paths pin f32 matmuls
PARITY_TOL = 1e-3
# composed 2x2 vs one device, same chain ('gather' mode), as in
# tests/test_executor_conformance.py
FOUR_CHIP_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_devices: int):
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU found ({e})")
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); this script runs on the chip only")
    if len(devs) < n_devices:
        sys.exit(f"chip_smoke: needs {n_devices} TPU devices, found "
                 f"{len(devs)}")
    return devs


def make_data(preset, seed: int):
    t0 = time.time()
    coo, _ = SYN.generate(preset, seed=seed)
    train, test = train_test_split(coo, 0.1, seed=seed + 1)
    t1 = time.time()
    part = partition(train, *GRID)
    log(f"data: {preset.name} {train.n_rows}x{train.n_cols} "
        f"train={train.nnz} test={test.nnz} grid={GRID[0]}x{GRID[1]} "
        f"generate={t1 - t0:.1f}s partition={time.time() - t1:.1f}s")
    return train, test, part


def kernel_parity(part, K: int, seed: int) -> dict:
    """Pallas kernels vs their XLA paths on one real block's padded planes,
    same inputs, on the device. Returns the max abs errors."""
    blk = part.block(1, 1)
    shapes = PP.BlockShapes.per_phase(part, None)[blk.phase]
    csr_r, _, *_ = PP.pad_block_inputs(blk, shapes, K, None, None, None)
    N, M = csr_r.idx.shape
    D = shapes.n_cols
    kz, kv, kp = jax.random.split(jax.random.key(seed), 3)
    z = jax.random.normal(kz, (N, K))
    V = 0.5 * jax.random.normal(kv, (D, K))
    mu, Lam = POST.sample_nw(kp, POST.default_nw(K))
    prior = POST.broadcast_prior(mu, Lam, N)
    args = (z, csr_r.idx, csr_r.val, csr_r.mask, prior.eta, prior.Lambda, V)
    sweep = jax.jit(lambda *a, force: SWEEP.fused_sweep(*a, 2.0, force=force),
                    static_argnames="force")
    U_pal = sweep(*args, force="pallas")
    U_ref = sweep(*args, force="ref")
    prec_f = jax.jit(lambda *a: PREC.precision_accum_fused(*a, 2.0))
    prec_c = jax.jit(lambda *a: PREC.precision_accum_chunked(*a, 2.0))
    Lf, ef = prec_f(csr_r.idx, csr_r.val, csr_r.mask, V)
    Lc, ec = prec_c(csr_r.idx, csr_r.val, csr_r.mask, V)
    err = {
        "fused_sweep": float(jnp.max(jnp.abs(U_pal - U_ref))),
        "precision_lambda": float(jnp.max(jnp.abs(Lf - Lc))),
        "precision_eta": float(jnp.max(jnp.abs(ef - ec))),
    }
    scale = {"fused_sweep": float(jnp.max(jnp.abs(U_ref))),
             "precision_lambda": float(jnp.max(jnp.abs(Lc))),
             "precision_eta": float(jnp.max(jnp.abs(ec)))}
    log(f"kernel parity: block (1,1) planes {N}x{M}, K={K}: " + ", ".join(
        f"{k} max|err|={v:.3e} (max|ref|={scale[k]:.3e})"
        for k, v in err.items()))
    for k, v in err.items():
        if not v <= PARITY_TOL * max(scale[k], 1.0):
            raise AssertionError(f"kernel parity {k}: {v} > "
                                 f"{PARITY_TOL} x {scale[k]}")
    return err


def train(part, test, cfg, key, topology=None):
    t0 = time.time()
    res = PP.run_pp(key, part, cfg, test, executor="streaming",
                    window=WINDOW, topology=topology, verbose=True)
    wall = time.time() - t0
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"train: executor={res.executor} topology="
        f"{topology.describe() if topology else 'one device'} "
        f"wall={wall:.1f}s phases="
        f"{ {k: round(v, 2) for k, v in res.phase_times_s.items()} } "
        f"peak_bytes_in_use(dev0)={peak} rmse={res.rmse:.6f} "
        f"faults={len(res.faults)}")
    if res.faults:
        raise AssertionError(f"fault ledger not empty: {res.faults}")
    if not np.isfinite(res.rmse):
        raise AssertionError(f"held-out RMSE is not finite: {res.rmse}")
    return res


def serve(res, train_coo, seed: int, n_requests: int = 48) -> None:
    t0 = time.time()
    store = PosteriorStore.from_pp_result(res, jax.random.key(seed + 2))
    jax.block_until_ready(store)
    t1 = time.time()
    router = MicroBatchRouter(store, k=10, mode="mean", latency_budget_s=0.0,
                              max_batch=16, max_seen=64, seed=seed + 3)
    reqs = build_requests(train_coo, n_requests, 64, seed + 4)
    tickets = [router.submit(r) for r in reqs]
    router.flush()
    log(f"serve: store {store.n_users} users x {store.n_items} items "
        f"built in {t1 - t0:.1f}s; {len(reqs)} mean-mode request(s) in "
        f"{time.time() - t1:.1f}s over {len(router.dispatches)} dispatch(es)")
    check_parity(router, tickets, reqs, store)


def chain_config(K: int) -> BMF.BMFConfig:
    full = BMF.BMFConfig(K=K)
    log(f"cut: samples {full.n_samples} -> {SAMPLES}, burn-in {full.burnin} "
        f"-> {BURNIN} (time limit)")
    log(f"chain: {SAMPLES} samples ({BURNIN} burn-in), one-pass sweep, "
        f"streaming window {WINDOW}")
    return BMF.BMFConfig(K=K, n_samples=SAMPLES, burnin=BURNIN,
                         sweep_fused=True)


def one_chip(args) -> None:
    K = MOVIELENS_20M.K
    route = {k: pallas_route(k, K) for k in ("precision", "sweep", "sample")}
    log(f"route at K={K}: " + ", ".join(
        f"{k}={'pallas' if v else 'xla'}" for k, v in route.items()))
    if not all(route.values()):
        raise AssertionError(f"K={K} factor steps would not take the "
                             f"Pallas route on this device: {route}")
    train_coo, test, part = make_data(MOVIELENS_20M, args.seed)
    kernel_parity(part, K, args.seed)
    res = train(part, test, chain_config(K), jax.random.key(args.seed))
    rmse_mean = float(np.sqrt(np.mean((test.val - train_coo.val.mean())
                                      ** 2)))
    log(f"held-out RMSE {res.rmse:.6f} vs mean predictor {rmse_mean:.6f}")
    if not res.rmse < rmse_mean:
        raise AssertionError("PP must beat the mean predictor")
    serve(res, train_coo, args.seed)


def four_chips(args) -> None:
    full = MOVIELENS_20M
    preset = dataclasses.replace(
        full, name=f"{full.name}/{FOUR_CHIP_CUT}",
        n_rows=full.n_rows // FOUR_CHIP_CUT,
        n_cols=full.n_cols // FOUR_CHIP_CUT)
    log(f"cut: rows {full.n_rows} -> {preset.n_rows}, items {full.n_cols} "
        f"-> {preset.n_cols} (chip time; {full.ratings_per_row} ratings "
        f"per row, scale {full.scale_lo}-{full.scale_hi} and K={full.K} kept)")
    train_coo, test, part = make_data(preset, args.seed)
    cfg = chain_config(preset.K)
    key = jax.random.key(args.seed)
    topo = Topology(2, 2, devices=tuple(jax.devices()[:4]))
    composed = train(part, test, cfg, key, topology=topo)
    # placement on real devices: every chip of the topology held its share
    # (code that ran only on a faked CPU mesh could leave all on chip 0)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in topo.devices]
    log(f"four chips: peak_bytes_in_use per device {peaks}")
    if not all(peaks):
        raise AssertionError(f"a device of the 2x2 topology was never used: "
                             f"{peaks}")
    single = train(part, test, cfg, key,
                   topology=Topology(1, 1, devices=(jax.devices()[0],)))
    diff = abs(composed.rmse - single.rmse)
    log(f"four chips: rmse 2x2={composed.rmse:.6f} one device="
        f"{single.rmse:.6f} |diff|={diff:.3e} (bound {FOUR_CHIP_TOL})")
    if not diff <= FOUR_CHIP_TOL:
        raise AssertionError(f"composed vs one-device RMSE differ by {diff}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the composed Topology(2, 2) path and the "
                         "same chain on one device (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    n = 4 if args.four_chips else 1
    devs = require_tpu(n)
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{use_compile_cache()}")
    compiles = CompileStats()
    t0 = time.time()
    (four_chips if args.four_chips else one_chip)(args)
    secs, n, hits = compiles.snapshot()
    log(f"compile: {n} backend compile(s) in {secs:.1f}s, {hits} "
        f"persistent-cache hit(s)")
    log(f"chip_smoke: all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
