"""BMF-PP serving driver — train, build the posterior store, serve top-K.

Usage (smoke scale, CPU):
  PYTHONPATH=src python -m repro.launch.bmf_serve \
      --dataset movielens --blocks 4 --samples 20 \
      --mode thompson --requests 256 --check

Pipeline: ``run_pp`` with the chosen executor, then
``PosteriorStore.from_pp_result`` (one jitted device gather — posteriors
never visit the host), then a ``MicroBatchRouter`` pumping ``--requests``
recommendation requests built from real users (each masks its own
training items as seen). Reports per-request p50/p99 latency and QPS.

``--check`` (mean mode) verifies every served top-K against a dense numpy
brute-force ranking over the store means: each returned item's score must
be within 1e-5 of the k-th best brute-force score — the CLI twin of the
``tests/test_serving.py`` parity battery.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import bmf as BMF
from repro.core import pp as PP
from repro.core.partition import partition, suggest_grid
from repro.data import synthetic as SYN
from repro.data.sparse import train_test_split
from repro.launch.compile_cache import use_compile_cache
from repro.serving import MicroBatchRouter, PosteriorStore, Request
from repro.serving.scoring import MODES


def build_requests(train, n_requests: int, max_seen: int, seed: int):
    """One request per (cycled) user: mask the user's training items, in
    training order (truncated to the router's seen cap)."""
    order = np.argsort(train.row, kind="stable")
    rows, cols = train.row[order], train.col[order]
    users, starts = np.unique(rows, return_index=True)
    ends = np.append(starts[1:], len(rows))
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_requests):
        i = int(rng.integers(len(users)))
        seen = cols[starts[i]:min(ends[i], starts[i] + max_seen)]
        out.append(Request(user_id=int(users[i]),
                           seen=[int(c) for c in seen]))
    return out


def check_parity(router, tickets, reqs, store, tol: float = 1e-5):
    """Brute-force dense reference over store means: every served item's
    score must reach the k-th best masked score (tolerance absorbs
    jax-vs-numpy matmul reduction-order noise)."""
    U = np.asarray(store.U_mean)
    V = np.asarray(store.V_mean)
    k = router.k
    for t, r in zip(tickets, reqs):
        scores = U[r.user_id] @ V.T
        scores[np.asarray(r.seen, int)] = -np.inf
        kth = np.sort(scores)[::-1][min(k, len(scores)) - 1]
        served = scores[t.ids[t.valid]]
        assert served.size == min(k, int(np.isfinite(scores).sum()))
        assert (served >= kth - tol).all(), (r.user_id, served, kth)
    print(f"parity check OK: {len(tickets)} request(s) match the dense "
          f"brute-force top-{k} within {tol}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="movielens",
                    choices=list(SYN.PRESETS))
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--samples", type=int, default=20)
    ap.add_argument("--k", type=int, default=0, help="0 = preset K (cap 16)")
    ap.add_argument("--executor", default="stacked",
                    choices=["serial", "stacked", "sharded", "async",
                             "streaming"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=8,
                    help="item posterior sample slots S in the store")
    ap.add_argument("--mode", default="mean", choices=list(MODES))
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-seen", type=int, default=64)
    ap.add_argument("--latency-budget-ms", type=float, default=2.0)
    ap.add_argument("--check", action="store_true",
                    help="verify served top-K against a dense numpy "
                         "brute-force ranking (mean mode)")
    args = ap.parse_args()
    use_compile_cache()

    coo, p = SYN.generate(args.dataset, seed=args.seed)
    train, test = train_test_split(coo, 0.1, seed=args.seed + 1)
    K = args.k or min(p.K, 16)
    cfg = BMF.BMFConfig(K=K, n_samples=args.samples,
                        burnin=args.samples // 3)
    I, J = suggest_grid(train.n_rows, train.n_cols, args.blocks)
    part = partition(train, I, J)
    print(f"dataset={args.dataset} N={train.n_rows} M={train.n_cols} "
          f"grid={I}x{J} K={K} executor={args.executor}")

    t0 = time.time()
    res = PP.run_pp(jax.random.key(args.seed), part, cfg, test,
                    executor=args.executor)
    print(f"trained: RMSE={res.rmse:.4f} wall={time.time() - t0:.1f}s")

    t0 = time.time()
    store = PosteriorStore.from_pp_result(
        res, jax.random.key(args.seed + 2), n_slots=args.slots)
    jax.block_until_ready(store)
    print(f"store: {store.n_users} users x {store.n_items} items, "
          f"K={store.K}, {store.n_slots} sample slot(s), "
          f"built in {time.time() - t0:.2f}s")

    router = MicroBatchRouter(store, k=args.topk, mode=args.mode,
                              latency_budget_s=args.latency_budget_ms / 1e3,
                              max_batch=args.max_batch,
                              max_seen=args.max_seen,
                              seed=args.seed + 3)
    print(f"router: {len(router.plan_signatures)} executable bucket(s): "
          f"{router.plan_signatures}")

    reqs = build_requests(train, args.requests, args.max_seen,
                          args.seed + 4)
    # warm the full-batch executable so measured latency is serving, not
    # compilation
    for r in reqs[:args.max_batch]:
        router.submit(r)
    router.flush()
    router.latencies_s.clear()
    router.dispatches.clear()

    t0 = time.time()
    for r in reqs:
        router.submit(r)
        router.poll()
    router.flush()
    wall = time.time() - t0
    lat = np.asarray(router.latencies_s)
    print(f"served {len(lat)} request(s) in {wall:.2f}s  "
          f"QPS={len(lat) / max(wall, 1e-9):.0f}  "
          f"p50={np.percentile(lat, 50) * 1e3:.2f}ms  "
          f"p99={np.percentile(lat, 99) * 1e3:.2f}ms  "
          f"dispatches={len(router.dispatches)}")

    if args.check:
        router2 = MicroBatchRouter(store, k=args.topk, mode="mean",
                                   latency_budget_s=0.0,
                                   max_batch=args.max_batch,
                                   max_seen=args.max_seen,
                                   seed=args.seed + 5)
        check_reqs = reqs[:min(64, len(reqs))]
        tickets = [router2.submit(r) for r in check_reqs]
        router2.flush()
        check_parity(router2, tickets, check_reqs, store)


if __name__ == "__main__":
    main()
