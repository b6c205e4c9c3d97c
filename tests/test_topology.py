"""Unified 2-D ('block', 'data') topology layer: Topology object
semantics, the composed stacked 2-D chain, group dispatch, and donation
through the data-sharded group chain.

Multi-device behavior runs in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count so faked meshes never
leak into the main test process (same pattern as test_distributed_bmf).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import engine as ENG
from repro.core.distributed import make_block_mesh
from repro.core.topology import Topology

ROOT = Path(__file__).resolve().parents[1]


def _run(script: str, timeout: int = 500):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Topology object (single-device: structure + validation only)
# ---------------------------------------------------------------------------


def test_topology_shape_validation():
    with pytest.raises(ValueError):
        Topology(block=0, data=1)
    with pytest.raises(ValueError):
        Topology(block=1, data=2)          # 1 local device: too few
    t = Topology(block=1, data=1)
    assert t.n_devices == 1
    assert t.groups() == (t.devices,)
    assert t.describe().startswith("topology 1x1")


def test_topology_from_spec_coercions():
    t = Topology.from_spec(None)
    assert t.block == len(jax.devices()) and t.data == 1
    assert Topology.from_spec(t) is t
    t2 = Topology.from_spec((1, 1))
    assert (t2.block, t2.data) == (1, 1)
    # legacy 1-D 'block' mesh
    t3 = Topology.from_spec(make_block_mesh(1))
    assert (t3.block, t3.data) == (1, 1)
    with pytest.raises(ValueError):
        Topology.from_spec(jax.make_mesh((1, 1), ("a", "b")))


def test_topology_meshes_unify_block_mesh():
    """distributed.make_block_mesh is the data==1 degenerate form of the
    topology mesh — same devices, same axis name."""
    t = Topology(block=1, data=1)
    bm = t.block_mesh()
    assert tuple(bm.axis_names) == ("block",)
    assert bm == make_block_mesh(1)
    assert tuple(t.mesh.axis_names) == ("block", "data")
    g2 = t.group_mesh_2d(0)
    assert g2.devices.shape == (1, 1)
    assert tuple(g2.axis_names) == ("block", "data")


def test_topology_executor_wiring_errors():
    with pytest.raises(ValueError):
        ENG.make_executor("stacked", topology=Topology(1, 1))
    with pytest.raises(ValueError):
        ENG.make_executor(ENG.StackedExecutor(), topology=Topology(1, 1))
    # serial with a block>1 topology is meaningless
    with pytest.raises(ValueError):
        ENG.make_executor("serial", topology=(2, 1))


def test_executors_consume_topology_single_device():
    """On one device every executor accepts the degenerate topology and
    keeps its legacy behavior."""
    t = Topology(block=1, data=1)
    assert ENG.make_executor("serial", topology=t).topology is t
    sh = ENG.make_executor("sharded", topology=t)
    assert sh.topology is t and sh.block_mesh is not None
    asy = ENG.make_executor("async", topology=t)
    assert asy.topology is t and len(asy.devices) == 1
    st = ENG.make_executor("streaming", topology=t, window=3)
    assert st.topology is t and st.window == 3
    with pytest.raises(ValueError):
        ENG.StreamingExecutor(topology=Topology(1, 1), comm="psum")


# ---------------------------------------------------------------------------
# composed 2-D chain parity (subprocess, faked 4-device mesh)
# ---------------------------------------------------------------------------

CHAIN_2D_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import bmf as BMF, gibbs as GIBBS, distributed as DIST
    from repro.core.topology import Topology
    from repro.data import synthetic as SYN
    from repro.data.sparse import coo_to_padded_csr, PaddedCSR, \\
        train_test_split

    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    csr_r = coo_to_padded_csr(train)
    csr_c = coo_to_padded_csr(train.transpose())
    cfg = BMF.BMFConfig(K=6, n_samples=8, burnin=3)
    keys = jax.random.split(jax.random.key(7), 2)
    tr = jnp.stack([jnp.asarray(test.row)] * 2)
    tc = jnp.stack([jnp.asarray(test.col)] * 2)
    tv = np.asarray(test.val)

    def stack2(c):
        return PaddedCSR(idx=jnp.stack([c.idx] * 2),
                         val=jnp.stack([c.val] * 2),
                         mask=jnp.stack([c.mask] * 2), n_cols=c.n_cols)

    def rmse(res):
        pred = np.asarray(res.acc.pred_sum[0]
                          / jnp.maximum(res.acc.pred_cnt[0], 1))
        return float(np.sqrt(np.mean((pred - tv) ** 2)))

    topo = Topology(block=2, data=2)
    S, N, D = topo.data, csr_r.n_rows, csr_c.n_rows
    N_pad = ((N + S - 1) // S) * S
    m_c = int(csr_c.idx.shape[1])
    ref = GIBBS.run_gibbs_stacked(keys, stack2(csr_r), stack2(csr_c),
                                  tr, tc, cfg)
    out = {"ref": rmse(ref)}
    res = DIST.run_gibbs_stacked_2d(keys, stack2(csr_r), stack2(csr_c),
                                    tr, tc, cfg, topo, comm="gather")
    out["gather"] = rmse(res)
    out["gather_U_diff"] = float(jnp.abs(ref.U - res.U).max())
    csrt1 = DIST.shard_transposed_planes(train.row, train.col, train.val,
                                         S, N_pad, D, m_c)
    csrt = tuple(np.stack([x] * 2) for x in csrt1)
    res = DIST.run_gibbs_stacked_2d(keys, stack2(csr_r), stack2(csr_c),
                                    tr, tc, cfg, topo, comm="psum",
                                    csrt=csrt)
    out["psum"] = rmse(res)
    D_pad = ((D + S - 1) // S) * S
    csrt1 = DIST.shard_transposed_planes(train.row, train.col, train.val,
                                         S, N_pad, D_pad, m_c)
    csrt = tuple(np.stack([x] * 2) for x in csrt1)
    res = DIST.run_gibbs_stacked_2d(keys, stack2(csr_r), stack2(csr_c),
                                    tr, tc, cfg, topo, comm="scatter",
                                    csrt=csrt)
    out["scatter"] = rmse(res)

    # single-block group dispatch == run_gibbs under the same key
    r1 = GIBBS.run_gibbs(jax.random.key(5), csr_r, csr_c,
                         jnp.asarray(test.row), jnp.asarray(test.col), cfg)
    r2 = DIST.run_gibbs_group(jax.random.key(5), csr_r, csr_c,
                              jnp.asarray(test.row),
                              jnp.asarray(test.col), cfg, topo, group=1)
    out["group_U_diff"] = float(jnp.abs(r1.U - r2.U).max())
    out["n_devices"] = len(jax.devices())
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_stacked_2d_chain_parity_and_modes():
    """'gather' reproduces the single-level stacked chain (fp-level);
    'psum' matches statistically tightly (stats reassociation only);
    'scatter' stays a valid sampler; the B=1 group dispatch matches
    run_gibbs."""
    rec = _run(CHAIN_2D_SCRIPT)
    assert rec["n_devices"] == 4
    assert abs(rec["gather"] - rec["ref"]) < 1e-4, rec
    assert rec["gather_U_diff"] < 1e-3, rec
    assert abs(rec["psum"] - rec["ref"]) < 1e-3, rec
    assert abs(rec["scatter"] - rec["ref"]) < 0.15, rec
    assert rec["group_U_diff"] < 1e-3, rec


# ---------------------------------------------------------------------------
# donation through the data-sharded group chain (subprocess, 8 devices)
# ---------------------------------------------------------------------------

DONATE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro import analysis as LINT
    from repro.core import bmf as BMF, gibbs as GIBBS, distributed as DIST
    from repro.core.topology import Topology
    from repro.data import synthetic as SYN
    from repro.data.sparse import train_test_split, coo_to_padded_csr

    topo = Topology(block=1, data=8)
    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    csr_r = coo_to_padded_csr(train)
    csr_c = coo_to_padded_csr(train.transpose())
    cfg = BMF.BMFConfig(K=p.K, n_samples=6, burnin=2)
    tr, tc = jnp.asarray(test.row), jnp.asarray(test.col)

    ref = DIST.run_gibbs_group(jax.random.key(0), csr_r, csr_c, tr, tc,
                               cfg, topo, donate=False)
    don = DIST.run_gibbs_group(jax.random.key(0), csr_r, csr_c, tr, tc,
                               cfg, topo, donate=True)

    # the donated executable run_gibbs_group dispatches at these shapes,
    # through the analyzer's donation contract: V0 must alias the V
    # output in place, the other donated leaves are released at dispatch
    tchain = DIST.trace_chain_2d(
        cfg, topo, csr_r.n_rows, csr_c.n_rows, csr_r.idx.shape[1],
        csr_c.idx.shape[1], len(test.row), batch=1, comm="gather",
        donate=True, u_prior=False, v_prior=False)
    with GIBBS._quiet_donation():
        hlo = tchain.traced.lower().compile().as_text()
    donated = tuple(tchain.donated_labels)
    vs = LINT.analyze(LINT.HLOArtifact(
        label="group_chain_donated", hlo_text=hlo, comm="gather",
        allowed_groups=[list(range(8))],
        param_labels=tchain.param_labels, donated=donated,
        must_alias=tchain.must_alias,
        release_only=tuple(lb for lb in donated
                           if lb not in tchain.must_alias)))
    print(json.dumps({
        "n_devices": len(jax.devices()),
        "must_alias": list(tchain.must_alias),
        "has_alias": "input_output_alias=" in hlo,
        "violations": [str(v) for v in vs],
        "U_equal": bool(np.array_equal(np.asarray(ref.U),
                                       np.asarray(don.U))),
        "post_equal": bool(np.array_equal(np.asarray(ref.U_post.eta),
                                          np.asarray(don.U_post.eta))),
        "rmse_ref": float(GIBBS.rmse_from_acc(ref.acc,
                                              jnp.asarray(test.val))),
        "rmse_don": float(GIBBS.rmse_from_acc(don.acc,
                                              jnp.asarray(test.val))),
    }))
""")


@pytest.mark.slow
def test_distributed_sweep_donation_alias_and_invalidate():
    """Mirrors the gibbs donation tests for the data-sharded group chain
    (``distributed.run_gibbs_group``): donate=True must not change the
    chain, and its executable must alias the donated V0 onto the V
    output in place — XLA recycles the factor buffer across sweeps —
    while the other donated leaves are released at dispatch."""
    rec = _run(DONATE_SCRIPT)
    assert rec["n_devices"] == 8
    assert rec["has_alias"] and rec["must_alias"] == ["V0"], rec
    assert not rec["violations"], rec
    assert rec["U_equal"] and rec["post_equal"], rec
    assert rec["rmse_ref"] == rec["rmse_don"], rec
