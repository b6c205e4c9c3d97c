"""Jitted batched top-K scoring over a ``PosteriorStore``.

One executable per (batch shape, k, mode): gather user posterior rows →
fold-in conditional over in-request feedback → score against the item
factors → mask seen items → ``lax.top_k``. Two modes share everything up
to the score matrix:

  mean      μ_u = (Λ_u + jitter·I)⁻¹ η_u, scores = μ_u @ V_meanᵀ — exact
            posterior-mean ranking, bitwise-deterministic (no RNG input).
  thompson  u ~ N(μ_u, Λ_u⁻¹) per request (fresh draw from the per-request
            PRNG key), scored against ONE stored item-posterior sample
            slot picked by the same key — Thompson sampling over the joint
            posterior, the uncertainty-exploiting policy the paper's
            Bayesian treatment buys.

Fold-in conditional (why serving can personalize without retraining): for
feedback (j, r) supplied with the request, the user row's conditional
posterior given the trained item factors V is the conjugate update

    Λ ← Λ + τ Σ_f m_f v_f v_fᵀ        η ← η + τ Σ_f m_f r_f v_f

against the fixed V_mean — the same likelihood form the Gibbs sweep uses
(``bmf.sufficient_stats``), so a cold-start request (user_id < 0, identity
prior) folded over its history approximates the trained row. Requests are
FIXED-shape: seen/fold lists are padded and masked, so the router's shape
buckets map 1:1 to executables.

Seen-item masking uses an out-of-bounds scatter-drop: padded seen slots
redirect to column index M, which ``mode="drop"`` discards — no (B, M)
one-hot mask materialization. The whole path never forms anything larger
than the (B, M, K) gathered sample slots (``scoring_budget`` is the lint
budget; ``trace_scoring`` the lowering hook ``bmf_lint`` feeds the jaxpr
passes).

Invariants (lint-enforced): no dense (N, M) score matrix — scoring is per
REQUEST batch, never all-users; no host callback inside the jitted body.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import posterior as POST
from repro.core.posterior import RowGaussians
from repro.serving.store import PosteriorStore, _posterior_mean

MODES = ("mean", "thompson")

# Scores and the fold-in are full f32 products: XLA's default TPU precision
# runs an f32 dot as one bf16 pass (~1e-3 relative error), which reorders
# near-tied items and breaks the exact mean-mode ranking. No effect on CPU.
F32_DOT = jax.lax.Precision.HIGHEST


class RequestBatch(NamedTuple):
    """One fixed-shape scoring batch. Pad rows with user_id = -1 and
    all-zero masks; pad slots in seen/fold lists with mask 0."""
    user_ids: jnp.ndarray   # (B,)   i32, -1 = cold-start (identity prior)
    seen_idx: jnp.ndarray   # (B, L) i32 item ids to exclude from top-K
    seen_mask: jnp.ndarray  # (B, L) f32 1 = real, 0 = padding
    fold_idx: jnp.ndarray   # (B, F) i32 fold-in feedback item ids
    fold_val: jnp.ndarray   # (B, F) f32 fold-in ratings
    fold_mask: jnp.ndarray  # (B, F) f32
    key_data: jnp.ndarray   # (B, 2) u32 raw per-request PRNG key data


class TopK(NamedTuple):
    ids: jnp.ndarray        # (B, k) i32 item ids, best first
    scores: jnp.ndarray     # (B, k) f32, -inf on invalid slots
    valid: jnp.ndarray      # (B, k) bool — False when < k scorable items


def _fold_in(g: RowGaussians, batch: RequestBatch, V_mean, tau):
    """Conjugate per-request conditional update against fixed item means."""
    v = V_mean[batch.fold_idx]                               # (B, F, K)
    m = batch.fold_mask
    Lam = g.Lambda + tau * jnp.einsum("bf,bfk,bfl->bkl", m, v, v,
                                      precision=F32_DOT)
    eta = g.eta + tau * jnp.einsum("bf,bf,bfk->bk", m, batch.fold_val, v,
                                   precision=F32_DOT)
    return RowGaussians(eta=eta, Lambda=Lam)


@partial(jax.jit, static_argnames=("k", "mode", "jitter"))
def score_topk(store: PosteriorStore, batch: RequestBatch, k: int,
               mode: str = "mean", jitter: float = 1e-6) -> TopK:
    if mode not in MODES:
        raise ValueError(f"unknown scoring mode {mode!r} (expected {MODES})")
    B = batch.user_ids.shape[0]
    M, K = store.V_mean.shape

    cold = batch.user_ids < 0
    uid = jnp.where(cold, 0, batch.user_ids)
    eye = jnp.eye(K, dtype=store.U.Lambda.dtype)
    g = RowGaussians(
        eta=jnp.where(cold[:, None], 0.0, store.U.eta[uid]),
        Lambda=jnp.where(cold[:, None, None], eye, store.U.Lambda[uid]))
    g = _fold_in(g, batch, store.V_mean, store.tau)

    if mode == "mean":
        mu = _posterior_mean(g, jitter)                      # (B, K)
        scores = jnp.matmul(mu, store.V_mean.T,
                            precision=F32_DOT)               # (B, M)
    else:
        keys = jax.random.wrap_key_data(batch.key_data)      # (B,) keys
        kz = jax.vmap(jax.random.fold_in, (0, None))(keys, 0)
        ks = jax.vmap(jax.random.fold_in, (0, None))(keys, 1)
        z = jax.vmap(lambda kk: jax.random.normal(kk, (K,)))(kz)
        u = POST.sample_rows_noise(g, z, jitter=jitter)      # (B, K)
        slot = jax.vmap(lambda kk: jax.random.randint(
            kk, (), 0, store.n_slots))(ks)                   # (B,)
        scores = jnp.einsum("bk,bmk->bm", u, store.V_samples[slot],
                            precision=F32_DOT)

    # seen masking: padded slots redirect to out-of-bounds column M, which
    # scatter mode="drop" discards — no (B, M) one-hot intermediate
    seen_col = jnp.where(batch.seen_mask > 0, batch.seen_idx, M)
    scores = scores.at[jnp.arange(B)[:, None], seen_col].set(
        -jnp.inf, mode="drop")

    vals, idx = jax.lax.top_k(scores, k)   # stable: lowest index wins ties
    return TopK(ids=idx.astype(jnp.int32), scores=vals,
                valid=vals > -jnp.inf)


# ---------------------------------------------------------------------------
# static-analyzer hooks (launch.bmf_lint)
# ---------------------------------------------------------------------------


class TracedScoring(NamedTuple):
    """What the analyzer needs from one scoring lowering: the jax Traced
    object (``.jaxpr`` feeds the jaxpr passes) plus flat parameter labels
    for report readability."""
    traced: object
    param_labels: Tuple[str, ...]


def scoring_budget(n_users: int, n_items: int, K: int, batch: int,
                   n_slots: int, slack: float = 2.0) -> int:
    """Largest buffer the scoring executable legitimately holds: the store
    precision tensors (N·K² f32), the resident sample slots (S·M·K), or
    the per-batch gathered slots (B·M·K) — whichever is bigger, times
    ``slack`` for layout headroom. The banned formulation scores ALL users
    against all items at once (the dense N×M matrix): at lint dims that is
    > slack× over every legitimate buffer, so it trips the
    materialization pass."""
    store_side = max(n_users, n_items) * K * K
    slots = n_slots * n_items * K
    gathered = batch * n_items * K
    return int(slack * 4 * max(store_side, slots, gathered))


def abstract_store(n_users: int, n_items: int, K: int,
                   n_slots: int) -> PosteriorStore:
    """A shape-only store (ShapeDtypeStructs): feeds ``trace_scoring`` and
    lets the lint driver build a ``MicroBatchRouter`` bucket plan without
    training anything (the router only reads n_items/K from the store)."""
    S_ = jax.ShapeDtypeStruct
    f32 = jnp.float32
    return PosteriorStore(
        U=RowGaussians(eta=S_((n_users, K), f32),
                       Lambda=S_((n_users, K, K), f32)),
        V=RowGaussians(eta=S_((n_items, K), f32),
                       Lambda=S_((n_items, K, K), f32)),
        U_mean=S_((n_users, K), f32), V_mean=S_((n_items, K), f32),
        V_samples=S_((n_slots, n_items, K), f32), tau=S_((), f32))


def trace_scoring(n_users: int, n_items: int, K: int, batch: int,
                  n_seen: int, n_fold: int, n_slots: int, k: int,
                  mode: str) -> TracedScoring:
    """Trace the EXACT executable ``score_topk`` dispatches for one shape
    bucket, at abstract shapes — the serving analogue of
    ``gibbs.trace_chain``."""
    S_ = jax.ShapeDtypeStruct
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    store = abstract_store(n_users, n_items, K, n_slots)
    reqs = RequestBatch(
        user_ids=S_((batch,), i32),
        seen_idx=S_((batch, n_seen), i32), seen_mask=S_((batch, n_seen), f32),
        fold_idx=S_((batch, n_fold), i32), fold_val=S_((batch, n_fold), f32),
        fold_mask=S_((batch, n_fold), f32),
        key_data=S_((batch, 2), u32))
    traced = score_topk.trace(store, reqs, k=k, mode=mode)
    labels = tuple(f"store.{f}" for f in ("U.eta", "U.Lambda", "V.eta",
                                          "V.Lambda", "U_mean", "V_mean",
                                          "V_samples", "tau"))
    labels += tuple(f"batch.{f}" for f in RequestBatch._fields)
    return TracedScoring(traced=traced, param_labels=labels)
