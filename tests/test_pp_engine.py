"""Phase-graph PP engine: graph structure, executor parity, aggregation
algebra, verbose reporting, and the occupancy-sorted partition wiring."""
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import guards as GUARDS
from repro.core import bmf as BMF
from repro.core import engine as ENG
from repro.core import gibbs as GIBBS
from repro.core import posterior as POST
from repro.core import pp as PP
from repro.core.partition import partition
from repro.data import synthetic as SYN
from repro.data.sparse import train_test_split

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# graph structure
# ---------------------------------------------------------------------------


def _graph_for(I, J):
    part = types.SimpleNamespace(I=I, J=J)
    return ENG.build_phase_graph(part)


def test_phase_graph_covers_grid_once():
    for I, J in ((1, 1), (2, 2), (3, 2), (4, 4)):
        graph = _graph_for(I, J)
        coords = [t.coord for _, tasks in graph for t in tasks]
        assert len(coords) == I * J
        assert len(set(coords)) == I * J


def test_phase_graph_deps_precede():
    """Every task's deps must be scheduled in a strictly earlier phase —
    the invariant that makes within-phase execution embarrassingly
    parallel."""
    graph = _graph_for(3, 4)
    done = set()
    for _, tasks in graph:
        for t in tasks:
            assert set(t.deps) <= done, (t, done)
        done |= {t.coord for t in tasks}


def test_phase_graph_prior_sources():
    graph = dict(_graph_for(3, 3))
    (a,) = graph["a"]
    assert a.deps == ()
    for t in graph["b"]:
        assert t.deps == ((0, 0),)
        # first block-column propagates V, first block-row propagates U
        if t.j == 0:
            assert t.u_prior_from is None and t.v_prior_from == (0, 0)
        else:
            assert t.u_prior_from == (0, 0) and t.v_prior_from is None
    for t in graph["c"]:
        assert t.u_prior_from == (t.i, 0)
        assert t.v_prior_from == (0, t.j)


# ---------------------------------------------------------------------------
# aggregation algebra (satellite: divide-away exactness)
# ---------------------------------------------------------------------------


def _int_gaussians(rng, n, k, lo=-8, hi=8):
    """Integer-valued natural params: float32 adds/subtracts on small
    integers are exact, so the divide-away identity can be checked with
    zero tolerance."""
    return POST.RowGaussians(
        eta=jnp.asarray(rng.integers(lo, hi, (n, k)).astype(np.float32)),
        Lambda=jnp.asarray(rng.integers(lo, hi, (n, k, k)).astype(np.float32)))


@pytest.mark.parametrize("seed", range(5))
def test_aggregate_divides_away_priors_exactly(seed):
    """Qin et al. 2019 eq. 5: posts[i][j>=1] = prior_i * likelihood_ij in
    natural params; aggregation must return prior_i * prod_j likelihood_ij
    EXACTLY — the (J-1) multiply-counted prior copies are divided away."""
    rng = np.random.default_rng(seed)
    I, J, n, k = int(rng.integers(1, 4)), int(rng.integers(1, 4)), 5, 3
    part = types.SimpleNamespace(I=I, J=J)

    priors = [_int_gaussians(rng, n, k) for _ in range(I)]
    liks = [[_int_gaussians(rng, n, k) for _ in range(J)] for _ in range(I)]
    posts = [[priors[i] if j == 0 else POST.product(priors[i], liks[i][j])
              for j in range(J)] for i in range(I)]

    agg = PP._aggregate_axis(part, posts, axis="row")
    for i in range(I):
        expect = priors[i]
        for j in range(1, J):
            expect = POST.product(expect, liks[i][j])
        np.testing.assert_array_equal(
            np.asarray(agg.eta[i * n:(i + 1) * n]), np.asarray(expect.eta))
        np.testing.assert_array_equal(
            np.asarray(agg.Lambda[i * n:(i + 1) * n]),
            np.asarray(expect.Lambda))


def test_aggregate_col_axis_symmetry():
    rng = np.random.default_rng(11)
    I, J, n, k = 3, 2, 4, 2
    part = types.SimpleNamespace(I=I, J=J)
    priors = [_int_gaussians(rng, n, k) for _ in range(J)]
    liks = [[_int_gaussians(rng, n, k) for _ in range(J)] for _ in range(I)]
    posts = [[priors[j] if i == 0 else POST.product(priors[j], liks[i][j])
              for j in range(J)] for i in range(I)]
    agg = PP._aggregate_axis(part, posts, axis="col")
    for j in range(J):
        expect = priors[j]
        for i in range(1, I):
            expect = POST.product(expect, liks[i][j])
        np.testing.assert_array_equal(
            np.asarray(agg.eta[j * n:(j + 1) * n]), np.asarray(expect.eta))


# ---------------------------------------------------------------------------
# executor parity + verbose (satellite: serial == stacked under a fixed key)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mini_run():
    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    cfg = BMF.BMFConfig(K=p.K, n_samples=10, burnin=3)
    part = partition(train, 2, 2)
    return part, cfg, test


def test_serial_stacked_identical_rmse(mini_run):
    part, cfg, test = mini_run
    key = jax.random.key(1)
    r_ser = PP.run_pp(key, part, cfg, test, executor="serial")
    r_stk = PP.run_pp(key, part, cfg, test, executor="stacked")
    assert r_ser.executor == "serial" and r_stk.executor == "stacked"
    # identical keys + identical padding -> identical chains (up to batched
    # fp scheduling)
    assert abs(r_ser.rmse - r_stk.rmse) < 1e-5, (r_ser.rmse, r_stk.rmse)
    np.testing.assert_allclose(r_ser.per_block_rmse, r_stk.per_block_rmse,
                               atol=1e-4)
    # natural params are ill-conditioned (ridge-scale covariance inverses);
    # the aggregated posterior MEANS are the well-conditioned comparison
    np.testing.assert_allclose(np.asarray(r_ser.U_agg.mean),
                               np.asarray(r_stk.U_agg.mean),
                               atol=5e-3)
    assert r_ser.n_test == r_stk.n_test > 0
    assert set(r_ser.phase_times_s) == set(r_stk.phase_times_s) == {"a", "b", "c"}


def test_run_pp_verbose_reports_phases(mini_run, capsys):
    part, cfg, test = mini_run
    fast = cfg._replace(n_samples=2, burnin=0)
    PP.run_pp(jax.random.key(0), part, fast, test, executor="stacked",
              verbose=True)
    out = capsys.readouterr().out
    for phase in ("phase a", "phase b", "phase c"):
        assert phase in out, out
    assert "block(s)" in out and "[pp:stacked]" in out
    # shape buckets are reported
    assert "m=" in out


def test_executor_instance_and_unknown(mini_run):
    part, cfg, test = mini_run
    fast = cfg._replace(n_samples=2, burnin=0)
    res = PP.run_pp(jax.random.key(0), part, fast, test,
                    executor=ENG.StackedExecutor())
    assert res.executor == "stacked"
    with pytest.raises(ValueError):
        PP.run_pp(jax.random.key(0), part, fast, test, executor="warp")


SERIAL_GROUP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax
    import numpy as np
    from repro.core import bmf as BMF, pp as PP
    from repro.core.partition import partition
    from repro.core.topology import Topology
    from repro.data import synthetic as SYN
    from repro.data.sparse import train_test_split

    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    cfg = BMF.BMFConfig(K=p.K, n_samples=6, burnin=2)
    part = partition(train, 2, 2)
    key = jax.random.key(1)
    topo = Topology(block=1, data=2)
    r_ser = PP.run_pp(key, part, cfg, test, executor="serial",
                      topology=topo)
    r_asy = PP.run_pp(key, part, cfg, test, executor="async",
                      topology=topo)
    eq = {f"{f}.{leaf}": bool(np.array_equal(
              np.asarray(getattr(getattr(r_ser, f), leaf)),
              np.asarray(getattr(getattr(r_asy, f), leaf))))
          for f in ("U_agg", "V_agg") for leaf in ("eta", "Lambda")}
    print(json.dumps({"n_devices": len(jax.devices()),
                      "executors": [r_ser.executor, r_asy.executor],
                      "equal": eq, "rmse": [r_ser.rmse, r_asy.rmse]}))
""")


def test_serial_data_sharded_matches_async():
    """``executor="serial"`` on ``Topology(1, 2)`` runs each block through
    the one chain body, data-sharded exactly like an async group dispatch
    (``distributed.run_gibbs_group``, comm 'gather', same keys and
    shapes): its aggregated posteriors are bitwise the async executor's
    on the same topology."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", SERIAL_GROUP_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 2
    assert rec["executors"] == ["serial", "async"]
    assert all(rec["equal"].values()), rec
    assert abs(rec["rmse"][0] - rec["rmse"][1]) < 1e-5, rec


def test_window_with_instance_rejected():
    """window= only configures the named streaming executor; silently
    dropping it on a pre-built instance would hand the user a different
    window than they asked for."""
    assert ENG.make_executor("streaming", window=7).window == 7
    with pytest.raises(ValueError):
        ENG.make_executor(ENG.StreamingExecutor(), window=7)


def test_executor_instances_reusable_across_runs(mini_run):
    """run_graph resets per-run state: reusing one instance (warmup +
    timed runs) must not accumulate traces or peak counters."""
    part, cfg, test = mini_run
    fast = cfg._replace(n_samples=2, burnin=0)
    key = jax.random.key(3)
    ex = ENG.AsyncExecutor(record_trace=True)
    PP.run_pp(key, part, fast, test, executor=ex)
    n_events = len(ex.trace)
    PP.run_pp(key, part, fast, test, executor=ex)
    assert len(ex.trace) == n_events == 2 * part.I * part.J
    st = ENG.StreamingExecutor(window=2, record_trace=True)
    PP.run_pp(key, part, fast, test, executor=st)
    assert len(st.trace) == 2 * part.I * part.J
    first_peak = st.peak_window_blocks
    PP.run_pp(key, part, fast, test, executor=st)
    assert st.peak_window_blocks == first_peak
    assert len(st.trace) == 2 * part.I * part.J


def test_grouped_ready_queue_chunks_by_group():
    groups = {(0, 0): "a", (1, 0): "a", (2, 0): "a", (0, 1): "b",
              (1, 1): "b"}
    prio = {(0, 0): 1.0, (1, 0): 3.0, (2, 0): 2.0, (0, 1): 9.0,
            (1, 1): 8.0}
    q = ENG._GroupedReadyQueue(prio, groups.__getitem__)
    for c in groups:
        q.push(c)
    # lead = highest priority overall; chunk filled from ITS group only,
    # in priority order — other groups untouched
    assert q.pop_chunk(3) == [(0, 1), (1, 1)]
    assert len(q) == 3
    assert q.pop_chunk(2) == [(1, 0), (2, 0)]
    assert q.pop_chunk(2) == [(0, 0)]
    assert not q


# ---------------------------------------------------------------------------
# async executor (tentpole: dependency-driven overlap of phases b/c)
# ---------------------------------------------------------------------------


def test_serial_async_identical_rmse(mini_run):
    part, cfg, test = mini_run
    key = jax.random.key(1)
    r_ser = PP.run_pp(key, part, cfg, test, executor="serial")
    r_asy = PP.run_pp(key, part, cfg, test, executor="async")
    assert r_asy.executor == "async"
    assert abs(r_ser.rmse - r_asy.rmse) < 1e-5, (r_ser.rmse, r_asy.rmse)
    np.testing.assert_allclose(r_ser.per_block_rmse, r_asy.per_block_rmse,
                               atol=1e-4)
    # same bucketed per-block executables, same keys -> the device-resident
    # aggregation is BIT-identical to the serial reference
    np.testing.assert_array_equal(np.asarray(r_ser.U_agg.eta),
                                  np.asarray(r_asy.U_agg.eta))
    np.testing.assert_array_equal(np.asarray(r_ser.V_agg.Lambda),
                                  np.asarray(r_asy.V_agg.Lambda))
    # aggregated posteriors never left the device
    assert isinstance(r_asy.U_agg.eta, jax.Array)
    # overlapped run records dispatch→resolve spans for every block
    coords = {(i, j) for i in range(part.I) for j in range(part.J)}
    assert set(r_asy.block_spans_s) == coords
    for td, tr in r_asy.block_spans_s.values():
        assert 0.0 <= td <= tr
    assert set(r_asy.phase_times_s) == {"a", "b", "c"}


class _ShuffledAsync(ENG.AsyncExecutor):
    """Fake-delay executor: each completion poll flips a seeded coin per
    in-flight block, deferring its OBSERVED resolution even when the device
    finished long ago — randomizing the completion order the scheduler
    reacts to (the fallback path force-resolves the oldest in-flight block,
    so progress is always made)."""

    def __init__(self, seed, **kw):
        super().__init__(record_trace=True, **kw)
        self._rng = np.random.default_rng(seed)

    def _is_resolved(self, coord, signal):
        return bool(self._rng.random() < 0.4) and signal.is_ready()


@pytest.fixture(scope="module")
def mini_3x3():
    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    cfg = BMF.BMFConfig(K=p.K, n_samples=4, burnin=1)
    part = partition(train, 3, 3)
    key = jax.random.key(2)
    r_ser = PP.run_pp(key, part, cfg, test, executor="serial")
    return part, cfg, test, key, r_ser


@pytest.mark.parametrize("seed", range(3))
def test_async_completion_order_stress(mini_3x3, seed):
    """Randomized completion order must never let a block dispatch before
    both its prior sources resolved, and the divide-away aggregation must
    stay bit-identical to the serial reference regardless of order."""
    part, cfg, test, key, r_ser = mini_3x3
    ex = _ShuffledAsync(seed)
    r_asy = PP.run_pp(key, part, cfg, test, executor=ex)

    graph = {t.coord: t for _, ts in ENG.build_phase_graph(part) for t in ts}
    resolved = set()
    dispatched = set()
    for ev, c, *_ in ex.trace:
        if ev == "dispatch":
            assert set(graph[c].deps) <= resolved, \
                f"{c} dispatched before deps {graph[c].deps} resolved"
            dispatched.add(c)
        else:
            assert c in dispatched
            resolved.add(c)
    assert resolved == set(graph)          # every block ran exactly once
    assert len(ex.trace) == 2 * len(graph)

    np.testing.assert_array_equal(np.asarray(r_ser.U_agg.eta),
                                  np.asarray(r_asy.U_agg.eta))
    np.testing.assert_array_equal(np.asarray(r_ser.V_agg.eta),
                                  np.asarray(r_asy.V_agg.eta))
    assert abs(r_ser.rmse - r_asy.rmse) < 1e-5


# ---------------------------------------------------------------------------
# streaming executor (tentpole: bounded window for out-of-memory grids)
# ---------------------------------------------------------------------------


def test_streaming_window_bounds_live_blocks(mini_3x3):
    """The streaming executor's realized live-buffer bound: at no point do
    more than window x (depth + 1) blocks' worth of input buffers exist
    (in-flight chunks + the prefetched one) — the property that lets
    grids with num_blocks x block_bytes >> HBM run at a flat peak."""
    part, cfg, test, key, r_ser = mini_3x3
    ex = ENG.StreamingExecutor(window=2, depth=2)
    r_str = PP.run_pp(key, part, cfg, test, executor=ex)
    assert 0 < ex.peak_window_blocks <= 2 * (2 + 1)
    assert abs(r_str.rmse - r_ser.rmse) < 1e-5
    # 9 blocks through windows of 2: at least 5 chunks => the bound binds
    assert ex.peak_window_blocks < part.I * part.J


def test_streaming_chunks_record_spans_and_phases(mini_3x3):
    part, cfg, test, key, _ = mini_3x3
    r = PP.run_pp(key, part, cfg, test, executor="streaming", window=3)
    coords = {(i, j) for i in range(part.I) for j in range(part.J)}
    assert set(r.block_spans_s) == coords
    for td, tr in r.block_spans_s.values():
        assert 0.0 <= td <= tr
    assert set(r.phase_times_s) == {"a", "b", "c"}
    assert r.executor == "streaming"


def test_streaming_coalesced_buckets_still_sample(mini_3x3):
    """max_waste > 1 merges phase buckets into fewer window shapes (the
    one-window-shape-serves-many-blocks lever). Padding then differs from
    the reference buckets, so chains are DIFFERENT (the NW hyper-resample
    sees the padded rows) but must remain a valid sampler: RMSE stays in
    the same range, and every phase tag maps to a coalesced shape that
    dominates its own bucket."""
    part, cfg, test, key, r_ser = mini_3x3
    ex = ENG.StreamingExecutor(window=2, max_waste=4.0)
    r = PP.run_pp(key, part, cfg, test, executor=ex)
    shapes = PP.BlockShapes.per_phase(
        part, None)  # row/col/m dims don't depend on the test split
    n_groups = len({id(s) for s in ex.window_shapes.values()})
    assert n_groups < len(ex.window_shapes)       # something coalesced
    for tag, merged in ex.window_shapes.items():
        assert merged.n_rows >= shapes[tag].n_rows
        assert merged.n_cols >= shapes[tag].n_cols
        assert merged.m_rows >= shapes[tag].m_rows
    assert abs(r.rmse - r_ser.rmse) < 0.15        # same model, other draws


def test_stacked_prior_use_flags_bit_match_dedicated():
    """gibbs.run_gibbs_stacked(prior_use=...): a flagged chunk mixing
    with-prior and without-prior blocks must reproduce the DEDICATED
    stacked executables (fixed-prior pytree / no-prior pytree) bit-exactly
    per block — the invariant that lets one streaming window executable
    serve every phase tag. (Comparison is stacked-vs-stacked: the single-
    block executable differs in benign vmap fp scheduling.)"""
    from repro.core.posterior import RowGaussians
    from repro.data.sparse import PaddedCSR, coo_to_padded_csr

    coo, p = SYN.generate("mini", seed=21)
    csr_r = coo_to_padded_csr(coo)
    csr_c = coo_to_padded_csr(coo.transpose())
    cfg = BMF.BMFConfig(K=4, n_samples=3, burnin=1)
    keys = jax.random.split(jax.random.key(9), 2)
    rng = np.random.default_rng(5)
    prior_u = RowGaussians(
        eta=jnp.asarray(rng.normal(size=(coo.n_rows, 4)).astype(np.float32)),
        Lambda=jnp.broadcast_to(2.0 * jnp.eye(4), (coo.n_rows, 4, 4)))
    prior_v = RowGaussians(
        eta=jnp.asarray(rng.normal(size=(coo.n_cols, 4)).astype(np.float32)),
        Lambda=jnp.broadcast_to(3.0 * jnp.eye(4), (coo.n_cols, 4, 4)))

    def stack2(csr):
        return PaddedCSR(idx=jnp.stack([csr.idx] * 2),
                         val=jnp.stack([csr.val] * 2),
                         mask=jnp.stack([csr.mask] * 2), n_cols=csr.n_cols)

    tr2 = jnp.zeros((2, 6), jnp.int32)
    both = jax.tree.map(lambda x: jnp.stack([x] * 2), (prior_u, prior_v))
    ded_with = GIBBS.run_gibbs_stacked(keys, stack2(csr_r), stack2(csr_c),
                                       tr2, tr2, cfg, U_prior=both[0],
                                       V_prior=both[1])
    ded_wo = GIBBS.run_gibbs_stacked(keys, stack2(csr_r), stack2(csr_c),
                                     tr2, tr2, cfg)

    # flagged mixed chunk: block 0 fixed priors, block 1 NW hyperprior
    # (dummy zero rows where the flag is off)
    mixed = jax.tree.map(lambda x: jnp.stack([x, jnp.zeros_like(x)]),
                         (prior_u, prior_v))
    res = GIBBS.run_gibbs_stacked(
        keys, stack2(csr_r), stack2(csr_c), tr2, tr2, cfg,
        U_prior=mixed[0], V_prior=mixed[1],
        prior_use=(jnp.asarray([1.0, 0.0]), jnp.asarray([1.0, 0.0])))
    np.testing.assert_array_equal(np.asarray(res.U[0]),
                                  np.asarray(ded_with.U[0]))
    np.testing.assert_array_equal(np.asarray(res.U_post.eta[0]),
                                  np.asarray(ded_with.U_post.eta[0]))
    np.testing.assert_array_equal(np.asarray(res.U[1]),
                                  np.asarray(ded_wo.U[1]))
    np.testing.assert_array_equal(np.asarray(res.V_post.eta[1]),
                                  np.asarray(ded_wo.V_post.eta[1]))


# ---------------------------------------------------------------------------
# critical-path-first priority dispatch (tentpole: ready-queue ordering)
# ---------------------------------------------------------------------------


def test_critical_path_priority_bottom_levels():
    graph = {t.coord: t for _, ts in _graph_for(3, 3) for t in ts}
    est = {c: 1.0 for c in graph}
    est[(1, 0)] = 5.0                      # heavy phase-b row source
    prio = ENG.critical_path_priority(graph, est)
    # bottom levels: interior = own cost; b blocks add their successors'
    # longest chain; (0,0) tops everything
    assert prio[(1, 1)] == pytest.approx(1.0)
    assert prio[(1, 0)] == pytest.approx(6.0)    # 5 + deepest c successor
    assert prio[(0, 1)] == pytest.approx(2.0)
    assert prio[(0, 0)] == pytest.approx(1.0 + 6.0)
    # heavy source outranks every other phase-b block
    assert prio[(1, 0)] > max(prio[c] for c in ((2, 0), (0, 1), (0, 2)))


def test_ready_queue_orders_by_priority_fifo_ties():
    q = ENG._ReadyQueue({(0, 0): 1.0, (1, 0): 5.0, (0, 1): 5.0,
                         (1, 1): 0.0})
    for c in ((0, 0), (1, 0), (0, 1), (1, 1)):
        q.push(c)
    # descending priority, FIFO among ties
    assert [q.pop() for _ in range(len(q))] == \
        [(1, 0), (0, 1), (0, 0), (1, 1)]
    # and without priorities it degenerates to pure FIFO
    q2 = ENG._ReadyQueue(None)
    for c in ((2, 2), (0, 0), (1, 1)):
        q2.push(c)
    assert [q2.pop() for _ in range(3)] == [(2, 2), (0, 0), (1, 1)]


def test_async_priority_dispatch_order(mini_3x3):
    """With priorities on, the async scheduler drains the phase-b ready
    set critical-path-first: dispatch order of phase-b blocks follows
    descending bottom-level (nnz-weighted)."""
    part, cfg, test, key, r_ser = mini_3x3
    ex = ENG.AsyncExecutor(record_trace=True, priority=True)
    r = PP.run_pp(key, part, cfg, test, executor=ex)
    assert abs(r.rmse - r_ser.rmse) < 1e-5
    graph = {t.coord: t for _, ts in ENG.build_phase_graph(part) for t in ts}
    est = {c: float(part.block(*c).coo.nnz + 1) for c in graph}
    prio = ENG.critical_path_priority(graph, est)
    b_coords = [c for c in graph if graph[c].phase in ("b_row", "b_col")]
    order = [c for ev, c, *_ in ex.trace
             if ev == "dispatch" and c in b_coords]
    # phase b becomes ready all at once (single dep on (0,0)), so its
    # dispatch order is exactly the priority order
    assert order == sorted(b_coords, key=lambda c: -prio[c])


# ---------------------------------------------------------------------------
# device-resident aggregation (satellite: no host transfers mid-run)
# ---------------------------------------------------------------------------


def _device_posts(rng, I, J, n, k):
    return [[POST.RowGaussians(
        eta=jnp.asarray(rng.normal(size=(n, k)).astype(np.float32)),
        Lambda=jnp.asarray(rng.normal(size=(n, k, k)).astype(np.float32)))
        for _ in range(J)] for _ in range(I)]


def test_aggregate_axis_no_host_transfers():
    """_aggregate_axis is ONE jitted reduction over device-resident
    posteriors: running it under analysis.guards.no_host_transfers() proves no
    host round-trip happens mid-run (any implicit device↔host copy would
    raise)."""
    rng = np.random.default_rng(7)
    I, J, n, k = 2, 3, 4, 3
    part = types.SimpleNamespace(I=I, J=J)
    posts = _device_posts(rng, I, J, n, k)
    jax.block_until_ready(PP._aggregate_axis(part, posts, axis="row"))  # warm
    with GUARDS.no_host_transfers():
        agg = PP._aggregate_axis(part, posts, axis="row")
    jax.block_until_ready(agg)
    assert isinstance(agg.eta, jax.Array)


def test_aggregate_axis_jaxpr_no_blowup():
    """PR-1 idiom (roofline.jaxpr_cost.iter_avals): the jitted divide-away
    reduction may not materialize anything beyond the stacked input — its
    largest aval is exactly the (J, n, K, K) per-group Lambda stack."""
    from repro.roofline.jaxpr_cost import iter_avals, jaxpr_cost

    rng = np.random.default_rng(8)
    I, J, n, k = 3, 4, 5, 3
    posts = tuple(tuple(row) for row in _device_posts(rng, I, J, n, k))
    jaxpr = jax.make_jaxpr(
        lambda p: PP._aggregate_axis_jit(p, "row"))(posts)
    cap = J * n * k * k           # one row-group's stacked Lambda leaves
    assert max(int(np.prod(a.shape)) for a in iter_avals(jaxpr)
               if a.shape) <= cap
    # and it is pure arithmetic: FLOPs bounded by a few passes over inputs
    cost = jaxpr_cost(jaxpr)
    assert cost["flops"] <= 16 * I * J * n * k * k


# ---------------------------------------------------------------------------
# donation (satellite: padded input buffers are donated to XLA)
# ---------------------------------------------------------------------------


def test_run_gibbs_donation_matches_and_aliases():
    """donate=True must not change the chain (same executable semantics)
    and must alias U0/V0 onto the U/V outputs — the donated initializations
    are invalidated at dispatch."""
    from repro.data.sparse import coo_to_padded_csr

    coo, p = SYN.generate("mini", seed=9)
    csr_r = coo_to_padded_csr(coo)
    csr_c = coo_to_padded_csr(coo.transpose())
    cfg = BMF.BMFConfig(K=4, n_samples=3, burnin=1)
    tr = jnp.zeros((5,), jnp.int32)
    tc = jnp.zeros((5,), jnp.int32)
    from repro.core import bmf as BMFmod
    key = jax.random.key(3)
    U0, V0 = BMFmod.init_factors(jax.random.key(4), csr_r.n_rows,
                                 csr_c.n_rows, cfg.K)
    ref = GIBBS.run_gibbs(key, csr_r, csr_c, tr, tc, cfg,
                          U0=U0, V0=V0, donate=False)

    U0d, V0d = BMFmod.init_factors(jax.random.key(4), csr_r.n_rows,
                                   csr_c.n_rows, cfg.K)
    don = GIBBS.run_gibbs(key, csr_r, csr_c, tr, tc, cfg,
                          U0=U0d, V0=V0d, donate=True)
    assert U0d.is_deleted() and V0d.is_deleted()   # aliased in place
    np.testing.assert_array_equal(np.asarray(ref.U), np.asarray(don.U))
    np.testing.assert_array_equal(np.asarray(ref.U_post.eta),
                                  np.asarray(don.U_post.eta))


# ---------------------------------------------------------------------------
# timing semantics (satellite: critical path, not even bucket splits)
# ---------------------------------------------------------------------------


def test_modeled_parallel_is_dependency_aware():
    res = PP.PPResult(
        rmse=0.0, U_agg=None, V_agg=None, per_block_rmse=np.zeros((2, 2)),
        wall_time_s=0.0, phase_times_s={}, n_test=0,
        block_times_s={(0, 0): 1.0, (1, 0): 2.0, (0, 1): 3.0, (1, 1): 1.0})
    # longest chain: (0,0) -> (0,1) -> (1,1) = 1 + 3 + 1
    assert res.critical_path_s() == pytest.approx(5.0)
    # enough workers: b blocks overlap, c starts when BOTH its sources are
    # done (not at a phase barrier) -> equals the critical path
    assert res.modeled_parallel_s(16) == pytest.approx(5.0)
    # one worker degenerates to the serial sum
    assert res.modeled_parallel_s(1) == pytest.approx(7.0)


# ---------------------------------------------------------------------------
# sharded executor (subprocess: needs a faked multi-device mesh)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    from repro.core import bmf as BMF, pp as PP
    from repro.core.partition import partition
    from repro.data import synthetic as SYN
    from repro.data.sparse import train_test_split

    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    cfg = BMF.BMFConfig(K=p.K, n_samples=8, burnin=2)
    part = partition(train, 3, 2)
    key = jax.random.key(1)
    r_stk = PP.run_pp(key, part, cfg, test, executor="stacked")
    r_shd = PP.run_pp(key, part, cfg, test, executor="sharded")
    print(json.dumps({"stacked": r_stk.rmse, "sharded": r_shd.rmse,
                      "n_devices": len(jax.devices())}))
""")


@pytest.mark.slow
def test_sharded_matches_stacked():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = __import__("json").loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 4
    # same chains, sharded placement: parity (the 3x2 grid exercises both
    # uneven bucket padding — phase b has 3 blocks over 4 devices — and
    # multi-block-per-device batches)
    assert abs(rec["stacked"] - rec["sharded"]) < 1e-4, rec


ASYNC_STREAMS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    from repro.core import bmf as BMF, pp as PP
    from repro.core.partition import partition
    from repro.data import synthetic as SYN
    from repro.data.sparse import train_test_split

    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    cfg = BMF.BMFConfig(K=p.K, n_samples=6, burnin=2)
    part = partition(train, 3, 2)
    key = jax.random.key(1)
    r_ser = PP.run_pp(key, part, cfg, test, executor="serial")
    r_asy = PP.run_pp(key, part, cfg, test, executor="async")
    print(json.dumps({"serial": r_ser.rmse, "async": r_asy.rmse,
                      "n_devices": len(jax.devices())}))
""")


@pytest.mark.slow
def test_async_streams_on_faked_mesh():
    """Per-device streams: with 4 faked devices the async executor places
    each dispatch round-robin and device_puts propagated priors across
    streams — RMSE parity with serial must survive the placement."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", ASYNC_STREAMS_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = __import__("json").loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 4
    assert abs(rec["serial"] - rec["async"]) < 1e-4, rec


# ---------------------------------------------------------------------------
# occupancy-sorted partition (satellite: data.sparse wiring)
# ---------------------------------------------------------------------------


def test_partition_occupancy_sorts_within_stripes():
    coo, _ = SYN.generate("mini", seed=5)
    part = partition(coo, 2, 2, occupancy_sort=True)
    from repro.data.sparse import apply_permutation
    pc = apply_permutation(coo, part.row_perm, part.col_perm)
    counts = np.bincount(pc.row, minlength=coo.n_rows)
    for lo, hi in zip(part.row_splits[:-1], part.row_splits[1:]):
        stripe = counts[lo:hi]
        assert (np.diff(stripe) <= 0).all(), stripe[:10]
    ccounts = np.bincount(pc.col, minlength=coo.n_cols)
    for lo, hi in zip(part.col_splits[:-1], part.col_splits[1:]):
        assert (np.diff(ccounts[lo:hi]) <= 0).all()


def test_partition_occupancy_preserves_balance_and_nnz():
    coo, _ = SYN.generate("mini", seed=6)
    from repro.core.partition import nnz_balance_stats
    p_sorted = partition(coo, 2, 2, occupancy_sort=True)
    p_plain = partition(coo, 2, 2, occupancy_sort=False)
    # stripe membership untouched -> identical per-block nnz balance
    assert nnz_balance_stats(p_sorted) == nnz_balance_stats(p_plain)
    assert sum(b.coo.nnz for b in p_sorted.all_blocks()) == coo.nnz
