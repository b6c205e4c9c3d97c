"""The static invariant analyzer (repro.analysis): registry mechanics,
each pass firing on a seeded-violation fixture AND staying quiet on the
clean twin, the nested-jaxpr traversal it runs on, and the engine's
pre-dispatch graph-validation hook."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis as LINT
from repro.analysis import registry as REG
from repro.analysis.hlo_passes import alias_param_ids, default_budget
from repro.analysis.jaxpr_passes import materialization_budget
from repro.analysis.trace_passes import check_graph
from repro.core import bmf as BMF
from repro.core import engine as ENG
from repro.core import gibbs as GIBBS
from repro.core import pp as PP
from repro.core.partition import partition
from repro.data import synthetic as SYN
from repro.data.sparse import train_test_split
from repro.roofline import jaxpr_cost as JCOST

S = jax.ShapeDtypeStruct
f32 = jnp.float32


def violations_of(art, pass_name):
    return [v for v in LINT.analyze(art) if v.pass_name == pass_name]


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------


def test_registry_rejects_duplicates_and_bad_kinds():
    with pytest.raises(ValueError, match="duplicate"):
        REG.register(REG.Pass("materialization", "jaxpr", "", lambda a: []))
    with pytest.raises(ValueError, match="unknown artifact kind"):
        REG.register(REG.Pass("fresh-name", "mlir", "", lambda a: []))
    with pytest.raises(KeyError, match="unknown pass"):
        REG.get_pass("no-such-pass")


def test_registry_lists_every_shipped_pass():
    names = {p.name for p in LINT.passes()}
    assert {"materialization", "dtype-promotion", "host-callback",
            "collective-confinement", "donation-effectiveness",
            "recompilation-budget", "happens-before", "window-occupancy",
            "graph-validation"} <= names
    for p in LINT.passes():
        assert p.kind in REG.KINDS and p.doc


def test_analyze_runs_only_matching_kind():
    art = REG.PlanArtifact(label="p", signatures=["a"] * 3, cap=8)
    for v in LINT.analyze(art):
        assert LINT.get_pass(v.pass_name).kind == "plan"


def test_violation_roundtrip():
    v = REG.Violation("p", "a", "broken", "fix it")
    assert v.as_dict() == {"pass": "p", "artifact": "a",
                           "message": "broken", "fix_hint": "fix it"}
    assert "fix it" in str(v)


# ---------------------------------------------------------------------------
# jaxpr passes
# ---------------------------------------------------------------------------

# block dims where the dense (N, M, K) factor tensor clearly exceeds the
# budget while the fused padded-plane gather stays inside it
N, M, MP, K = 64, 64, 8, 8
BUDGET = materialization_budget(N, M, MP, MP, K)


def _naive_jaxpr():
    """The formulation the pass exists to catch: materializes the dense
    (N, M, K) gathered-factor tensor before reducing."""
    def f(U, V, R):
        G = U[:, None, :] * V[None, :, :]            # (N, M, K) — the bug
        return jnp.sum(G * R[:, :, None], axis=1)
    return jax.jit(f).trace(S((N, K), f32), S((M, K), f32),
                            S((N, M), f32)).jaxpr


def _fused_jaxpr():
    """The padded-CSR formulation: per-row gathers of width MP only."""
    def f(U, V, idx, vals):
        Vg = V[idx]                                   # (N, MP, K)
        return jnp.einsum("nmk,nm->nk", Vg, vals) + U
    return jax.jit(f).trace(S((N, K), f32), S((M, K), f32),
                            S((N, MP), jnp.int32), S((N, MP), f32)).jaxpr


def test_materialization_fires_on_dense_gather():
    art = REG.JaxprArtifact(label="naive", jaxpr=_naive_jaxpr(),
                            bytes_budget=BUDGET)
    vs = violations_of(art, "materialization")
    assert vs and f"[{N}, {M}, {K}]" in vs[0].message


def test_materialization_quiet_on_fused_gather():
    art = REG.JaxprArtifact(label="fused", jaxpr=_fused_jaxpr(),
                            bytes_budget=BUDGET)
    assert not violations_of(art, "materialization")


def test_materialization_sees_inside_scan_bodies():
    """A dense tensor hiding inside a scanned sweep body is still caught —
    the traversal recurses into the scan jaxpr."""
    def f(U, V, R):
        def sweep(carry, _):
            G = U[:, None, :] * V[None, :, :]        # (N, M, K) in the body
            return carry + jnp.sum(G * R[:, :, None], axis=1), None
        out, _ = jax.lax.scan(sweep, jnp.zeros((N, K), f32), None, length=3)
        return out
    jx = jax.jit(f).trace(S((N, K), f32), S((M, K), f32),
                          S((N, M), f32)).jaxpr
    art = REG.JaxprArtifact(label="scanned-naive", jaxpr=jx,
                            bytes_budget=BUDGET)
    assert violations_of(art, "materialization")


def test_materialization_skipped_without_budget():
    art = REG.JaxprArtifact(label="naive", jaxpr=_naive_jaxpr())
    assert not violations_of(art, "materialization")


def test_dtype_promotion_fires_on_f64():
    with jax.enable_x64(True):
        jx = jax.jit(lambda x: x * np.float64(2.0)).trace(
            S((4,), jnp.float64)).jaxpr
    art = REG.JaxprArtifact(label="x64", jaxpr=jx)
    assert violations_of(art, "dtype-promotion")
    assert not violations_of(
        REG.JaxprArtifact(label="x64-ok", jaxpr=jx, allow_f64=True),
        "dtype-promotion")


def test_dtype_promotion_fires_on_low_precision_cholesky():
    def f(A):
        L = jax.lax.linalg.cholesky(A)
        return jnp.sum(L)
    jx = jax.jit(f).trace(S((4, 4), jnp.bfloat16)).jaxpr
    vs = violations_of(REG.JaxprArtifact(label="bf16-chol", jaxpr=jx),
                       "dtype-promotion")
    assert vs and "cholesky" in vs[0].message
    jx32 = jax.jit(f).trace(S((4, 4), f32)).jaxpr
    assert not violations_of(REG.JaxprArtifact(label="f32-chol", jaxpr=jx32),
                             "dtype-promotion")


def test_host_callback_fires_inside_jit():
    def f(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2
    jx = jax.jit(f).trace(S((4,), f32)).jaxpr
    vs = violations_of(REG.JaxprArtifact(label="cb", jaxpr=jx),
                       "host-callback")
    assert vs and "debug_print" in vs[0].message
    jx_clean = jax.jit(lambda x: x * 2).trace(S((4,), f32)).jaxpr
    assert not violations_of(REG.JaxprArtifact(label="ok", jaxpr=jx_clean),
                             "host-callback")


# ---------------------------------------------------------------------------
# satellite: the nested-jaxpr traversal itself (roofline.jaxpr_cost)
# ---------------------------------------------------------------------------


def _shapes(jx):
    return {tuple(a.shape) for a in JCOST.iter_avals(jx)}


def test_iter_avals_recurses_into_scan_body():
    def f(x):
        def body(c, _):
            w = jnp.ones((17, 23), f32)                # (17,23) body-only
            return c + (c @ w @ w.T), None
        out, _ = jax.lax.scan(body, x, None, length=2)
        return out
    jx = jax.jit(f).trace(S((5, 17), f32)).jaxpr
    assert (17, 23) in _shapes(jx)


def test_iter_avals_recurses_into_while_body():
    def f(x):
        def cond(c):
            return c[0] < 3
        def body(c):
            i, v = c
            return i + 1, v + jnp.zeros((11, 13), f32).sum()
        return jax.lax.while_loop(cond, body, (0, x))
    jx = jax.jit(f).trace(S((), f32)).jaxpr
    assert (11, 13) in _shapes(jx)


def test_iter_avals_recurses_into_cond_branches():
    def f(p, x):
        return jax.lax.cond(p,
                            lambda v: jnp.zeros((7, 29), f32).sum() + v,
                            lambda v: v * 2.0, x)
    jx = jax.jit(f).trace(S((), jnp.bool_), S((), f32)).jaxpr
    assert (7, 29) in _shapes(jx)


def test_iter_avals_recurses_into_pjit_subjaxpr():
    @jax.jit
    def inner(x):
        return x @ jnp.ones((19, 31), f32)
    jx = jax.jit(lambda x: inner(x) + 1.0).trace(S((3, 19), f32)).jaxpr
    assert (19, 31) in _shapes(jx)


def test_iter_eqns_finds_primitive_inside_scan():
    def f(A):
        def body(c, _):
            return jax.lax.linalg.cholesky(c), None
        out, _ = jax.lax.scan(body, A, None, length=2)
        return out
    jx = jax.jit(f).trace(S((4, 4), f32)).jaxpr
    assert any(e.primitive.name == "cholesky" for e in JCOST.iter_eqns(jx))


# ---------------------------------------------------------------------------
# hlo passes
# ---------------------------------------------------------------------------

_HLO_TEMPLATE = """HloModule lint_fixture

ENTRY %main (p0: f32[4]) -> f32[8] {{
  %p0 = f32[4]{{0}} parameter(0)
{body}
}}
"""


def _hlo_with(lines):
    return _HLO_TEMPLATE.format(body="\n".join(f"  {ln}" for ln in lines))


def test_confinement_fires_on_block_axis_crossing():
    hlo = _hlo_with([
        "%ag = f32[8]{0} all-gather(f32[4]{0} %p0), "
        "replica_groups={{0,2},{1,3}}, dimensions={0}",
    ])
    art = REG.HLOArtifact(label="crossing", hlo_text=hlo, comm="gather",
                          allowed_groups=[[0, 1], [2, 3]])
    vs = violations_of(art, "collective-confinement")
    assert any("crosses the 'block' axis" in v.message for v in vs)


def test_confinement_fires_over_comm_budget():
    hlo = _hlo_with([
        "%ag1 = f32[8]{0} all-gather(f32[4]{0} %p0), "
        "replica_groups={{0,1},{2,3}}, dimensions={0}",
        "%ag2 = f32[8]{0} all-gather(f32[4]{0} %p0), "
        "replica_groups={{0,1},{2,3}}, dimensions={0}",
    ])
    art = REG.HLOArtifact(label="over-budget", hlo_text=hlo, comm="gather",
                          allowed_groups=[[0, 1], [2, 3]])
    vs = violations_of(art, "collective-confinement")
    assert any("budget" in v.message for v in vs)


def test_confinement_fires_on_any_collective_in_block_only_mode():
    hlo = _hlo_with([
        "%ar = f32[4]{0} all-reduce(f32[4]{0} %p0), "
        "replica_groups={{0,1}}, to_apply=%add",
    ])
    art = REG.HLOArtifact(label="block-only", hlo_text=hlo, comm=None)
    assert violations_of(art, "collective-confinement")


def test_confinement_quiet_within_groups_and_budget():
    hlo = _hlo_with([
        "%ag = f32[8]{0} all-gather(f32[4]{0} %p0), "
        "replica_groups={{0,1},{2,3}}, dimensions={0}",
    ])
    art = REG.HLOArtifact(label="confined", hlo_text=hlo, comm="gather",
                          allowed_groups=[[0, 1], [2, 3]])
    assert not violations_of(art, "collective-confinement")


def test_default_budget_rejects_unknown_comm():
    with pytest.raises(ValueError, match="unknown comm mode"):
        default_budget("broadcast")


def _compiled_hlo(fn, *avals, donate=None):
    jf = jax.jit(fn, donate_argnums=donate) if donate is not None \
        else jax.jit(fn)
    with GIBBS._quiet_donation():
        return jf.trace(*avals).lower().compile().as_text()


def test_donation_fires_when_nothing_aliases():
    # sum: f32[64] -> f32[] — the donated buffer cannot alias the output
    hlo = _compiled_hlo(lambda x: jnp.sum(x), S((64,), f32), donate=0)
    art = REG.HLOArtifact(label="dead-donation", hlo_text=hlo,
                          param_labels=["x"], donated=["x"],
                          must_alias=["x"])
    vs = violations_of(art, "donation-effectiveness")
    assert vs and "input_output_alias" in vs[0].message


def test_donation_quiet_on_real_alias():
    hlo = _compiled_hlo(lambda x: x * 2.0, S((64,), f32), donate=0)
    assert alias_param_ids(hlo) == [0]
    art = REG.HLOArtifact(label="live-donation", hlo_text=hlo,
                          param_labels=["x"], donated=["x"],
                          must_alias=["x"])
    assert not violations_of(art, "donation-effectiveness")


def test_donation_release_only_is_not_a_violation():
    # y is consumed but shape-mismatched with the output, so its donation
    # can only release the buffer, never alias it
    hlo = _compiled_hlo(lambda x, y: x * 2.0 + jnp.sum(y),
                        S((64,), f32), S((32,), f32), donate=(0, 1))
    art = REG.HLOArtifact(label="release", hlo_text=hlo,
                          param_labels=["x", "y"], donated=["x", "y"],
                          must_alias=["x"], release_only=["y"])
    assert not violations_of(art, "donation-effectiveness")
    # ... but an undocumented unusable donation fires
    art2 = REG.HLOArtifact(label="undocumented", hlo_text=hlo,
                           param_labels=["x", "y"], donated=["x", "y"],
                           must_alias=["x"])
    vs = violations_of(art2, "donation-effectiveness")
    assert vs and "unusable" in vs[0].message


def test_recompilation_budget():
    many = [("c", (i, 7, 3)) for i in range(12)]
    vs = violations_of(REG.PlanArtifact(label="explode", signatures=many,
                                        cap=8), "recompilation-budget")
    assert vs and "12 distinct" in vs[0].message
    few = [("c", (5, 7, 3)), ("a", (5, 7, 3))] * 10
    assert not violations_of(REG.PlanArtifact(label="ok", signatures=few,
                                              cap=8), "recompilation-budget")


# ---------------------------------------------------------------------------
# trace passes
# ---------------------------------------------------------------------------

A, B, C = (0, 0), (0, 1), (1, 1)
DEPS = {A: [], B: [A], C: [A, B]}


def test_happens_before_clean_trace():
    trace = [("dispatch", A), ("resolve", A), ("dispatch", B),
             ("resolve", B), ("dispatch", C), ("resolve", C)]
    art = REG.TraceArtifact(label="ok", trace=trace, deps=DEPS)
    assert not violations_of(art, "happens-before")


def test_happens_before_fires_on_dispatch_before_dep():
    trace = [("dispatch", A), ("dispatch", B), ("resolve", A),
             ("resolve", B), ("dispatch", C), ("resolve", C)]
    art = REG.TraceArtifact(label="early", trace=trace, deps=DEPS)
    vs = violations_of(art, "happens-before")
    assert vs and "before dep" in vs[0].message


def test_happens_before_watchdog_protocol():
    # expire -> redispatch -> resolve is the legal watchdog path
    ok = [("dispatch", A), ("expire", A), ("redispatch", A), ("resolve", A)]
    assert not violations_of(
        REG.TraceArtifact(label="wd", trace=ok, deps={A: []}),
        "happens-before")
    # expire -> terminal resolve (degraded path) is legal too
    degraded = [("dispatch", A), ("expire", A), ("resolve", A)]
    assert not violations_of(
        REG.TraceArtifact(label="deg", trace=degraded, deps={A: []}),
        "happens-before")
    # a second dispatch NOT ordered after an expire fires
    double = [("dispatch", A), ("dispatch", A), ("resolve", A)]
    vs = violations_of(
        REG.TraceArtifact(label="dbl", trace=double, deps={A: []}),
        "happens-before")
    assert any("twice" in v.message for v in vs)
    # redispatch with no expired attempt fires
    rogue = [("dispatch", A), ("redispatch", A), ("resolve", A)]
    vs = violations_of(
        REG.TraceArtifact(label="rogue", trace=rogue, deps={A: []}),
        "happens-before")
    assert any("without an expired attempt" in v.message for v in vs)


def test_happens_before_fires_on_unresolved_block():
    trace = [("dispatch", A), ("resolve", A), ("dispatch", B)]
    vs = violations_of(
        REG.TraceArtifact(label="lost", trace=trace, deps={A: [], B: [A]}),
        "happens-before")
    assert any("never resolved" in v.message for v in vs)


def test_happens_before_group_events_clean():
    """The full elastic vocabulary in legal order: a steal of a staged
    block, a speculate/cancel twin pair, and a quarantine after expiry —
    all on (event, coord, group) entries — stays quiet."""
    trace = [("dispatch", A, 0), ("resolve", A, 0),
             ("steal", B, 1), ("dispatch", B, 1),       # staged -> stolen
             ("speculate", B, 0),                       # straggler hedge
             ("cancel", B, 0),                          # loser side
             ("resolve", B, 1),
             ("dispatch", C, 1), ("expire", C, 1),
             ("quarantine", C, 1),                      # group 1 drained
             ("redispatch", C, 0), ("resolve", C, 0)]
    art = REG.TraceArtifact(label="elastic-ok", trace=trace, deps=DEPS)
    assert not violations_of(art, "happens-before")


def test_happens_before_fires_on_dispatch_to_quarantined_group():
    trace = [("dispatch", A, 0), ("expire", A, 0), ("quarantine", A, 0),
             ("redispatch", A, 1), ("resolve", A, 1),
             ("dispatch", B, 0),                        # group 0 is dead
             ("resolve", B, 0)]
    vs = violations_of(
        REG.TraceArtifact(label="necro", trace=trace, deps={A: [], B: [A]}),
        "happens-before")
    assert any("quarantined group 0" in v.message for v in vs)
    # ...and so does routing the watchdog redispatch back to it
    back = [("dispatch", A, 0), ("expire", A, 0), ("quarantine", A, 0),
            ("redispatch", A, 0), ("resolve", A, 0)]
    vs = violations_of(
        REG.TraceArtifact(label="necro2", trace=back, deps={A: []}),
        "happens-before")
    assert any("quarantined group 0" in v.message for v in vs)


def test_happens_before_speculative_twin_protocol():
    # a resolve with the twin pair still open fires
    open_twin = [("dispatch", A, 0), ("speculate", A, 1),
                 ("resolve", A, 0)]
    vs = violations_of(
        REG.TraceArtifact(label="twin-open", trace=open_twin, deps={A: []}),
        "happens-before")
    assert any("open speculative twin" in v.message for v in vs)
    # a cancel with no speculate behind it fires
    rogue_cancel = [("dispatch", A, 0), ("cancel", A, 0),
                    ("redispatch", A, 1), ("resolve", A, 1)]
    vs = violations_of(
        REG.TraceArtifact(label="rogue-cancel", trace=rogue_cancel,
                          deps={A: []}),
        "happens-before")
    assert any("without an open speculative twin" in v.message for v in vs)
    # speculating a block that is not in flight fires
    cold = [("dispatch", A, 0), ("resolve", A, 0), ("speculate", A, 1),
            ("cancel", A, 1)]
    vs = violations_of(
        REG.TraceArtifact(label="cold-spec", trace=cold, deps={A: []}),
        "happens-before")
    assert any("speculated while not in flight" in v.message for v in vs)
    # a run ending with both twins live fires
    dangling = [("dispatch", A, 0), ("speculate", A, 1)]
    vs = violations_of(
        REG.TraceArtifact(label="dangling", trace=dangling, deps={A: []}),
        "happens-before")
    assert any("uncollapsed speculative twin" in v.message for v in vs)


def test_happens_before_fires_on_steal_of_inflight_block():
    trace = [("dispatch", A, 0), ("steal", A, 1), ("resolve", A, 0)]
    vs = violations_of(
        REG.TraceArtifact(label="hot-steal", trace=trace, deps={A: []}),
        "happens-before")
    assert any("stolen while in flight" in v.message for v in vs)


def test_happens_before_fires_on_double_quarantine():
    trace = [("dispatch", A, 0), ("expire", A, 0), ("quarantine", A, 0),
             ("quarantine", A, 0), ("redispatch", A, 1), ("resolve", A, 1)]
    vs = violations_of(
        REG.TraceArtifact(label="dbl-q", trace=trace, deps={A: []}),
        "happens-before")
    assert any("quarantined twice" in v.message for v in vs)


def test_window_occupancy():
    over = [("dispatch", A), ("dispatch", B), ("dispatch", C),
            ("resolve", A), ("resolve", B), ("resolve", C)]
    art = REG.TraceArtifact(label="burst", trace=over,
                            deps={A: [], B: [], C: []}, window_bound=2)
    vs = violations_of(art, "window-occupancy")
    assert vs and "exceeds the window bound" in vs[0].message
    ok = [("dispatch", A), ("resolve", A), ("dispatch", B), ("resolve", B)]
    assert not violations_of(
        REG.TraceArtifact(label="paced", trace=ok, deps={A: [], B: []},
                          window_bound=2, reported_peak=1),
        "window-occupancy")
    # the executor's own counter over the bound fires even if the trace
    # looks paced
    assert violations_of(
        REG.TraceArtifact(label="counter", trace=ok, deps={A: [], B: []},
                          window_bound=2, reported_peak=5),
        "window-occupancy")


# ---------------------------------------------------------------------------
# graph validation (pass + the engine's pre-dispatch hook)
# ---------------------------------------------------------------------------


def test_graph_validation_detects_cycle_and_dangling():
    vs = check_graph({A: [B], B: [A]})
    assert any("cycle" in v.message for v in vs)
    vs = check_graph({A: [(9, 9)]})
    assert any("neither in the graph nor pre-resolved" in v.message
               for v in vs)
    # a pre-resolved dep (checkpoint resume) is satisfied
    assert not check_graph({A: [(9, 9)]}, resolved=[(9, 9)])
    assert not check_graph(DEPS)


def test_graph_pass_runs_via_registry():
    art = REG.GraphArtifact(label="cyclic", deps={A: [B], B: [A]})
    assert violations_of(art, "graph-validation")


def test_engine_refuses_invalid_phase_graph(monkeypatch):
    """run_phase_graph validates the (pruned) graph through the analyzer
    before any dispatch: a rewired prior_from that forms a cycle is
    refused up front instead of hanging the scheduler."""
    coo, p = SYN.generate("mini", seed=13)
    train, test = train_test_split(coo, 0.15, seed=14)
    cfg = BMF.BMFConfig(K=p.K, n_samples=2, burnin=1)
    part = partition(train, 2, 2)

    def cyclic_graph(part_):
        t00 = ENG.BlockTask(0, 0, "a", (1, 1), None)      # cycle: a <-> c
        t11 = ENG.BlockTask(1, 1, "c", (0, 0), (0, 0))
        return [("a", [t00]), ("c", [t11])]

    monkeypatch.setattr(ENG, "build_phase_graph", cyclic_graph)
    with pytest.raises(ValueError, match="invalid phase graph"):
        PP.run_pp(jax.random.key(0), part, cfg, test, executor="serial")


# ---------------------------------------------------------------------------
# integration: the real chain lowerings are clean
# ---------------------------------------------------------------------------


def test_real_chain_artifacts_are_clean():
    """The reference single-block chain, traced through the lowering hook,
    passes every jaxpr/hlo pass — the per-executor version of this runs in
    bmf_lint --all-executors (CI's lint-invariants gate)."""
    cfg = BMF.BMFConfig(K=8, n_samples=2, burnin=1)
    tc = GIBBS.trace_chain(cfg, 48, 32, 12, 16, 40, donate=True)
    budget = materialization_budget(48, 32, 12, 16, 8)
    jart = REG.JaxprArtifact(label="chain/jaxpr", jaxpr=tc.traced.jaxpr,
                             bytes_budget=budget)
    assert not LINT.analyze(jart)
    with GIBBS._quiet_donation():
        hlo = tc.traced.lower().compile().as_text()
    donated = tuple(tc.donated_labels)
    must = set(tc.must_alias)
    hart = REG.HLOArtifact(label="chain/hlo", hlo_text=hlo, comm=None,
                           param_labels=tc.param_labels, donated=donated,
                           must_alias=tc.must_alias,
                           release_only=tuple(lb for lb in donated
                                              if lb not in must))
    assert not LINT.analyze(hart)
