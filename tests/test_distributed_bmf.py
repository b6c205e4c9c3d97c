"""The data-sharded chain (``distributed.run_gibbs_group`` on an 8-device
group) must draw the reference chain's numbers, and the
limited-communication property must hold.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the 512-device dry-run flag never leaks into the main test process.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import bmf as BMF, gibbs as GIBBS, distributed as DIST
    from repro.core.topology import Topology
    from repro.data import synthetic as SYN
    from repro.data.sparse import train_test_split, coo_to_padded_csr

    COMM = "gather"
    topo = Topology(block=1, data=8)
    coo, p = SYN.generate("mini", seed=3)
    train, test = train_test_split(coo, 0.15, seed=4)
    csr_r = coo_to_padded_csr(train)
    csr_c = coo_to_padded_csr(train.transpose())
    cfg = BMF.BMFConfig(K=p.K, n_samples=40, burnin=15)

    csrt = None
    if COMM != "gather":
        # per-shard transposed planes: items x each shard's local users
        S, N, D = topo.data, csr_r.n_rows, csr_c.n_rows
        N_pad = -(-N // S) * S
        D_pad = -(-D // S) * S if COMM == "scatter" else D
        csrt = DIST.shard_transposed_planes(
            train.row, train.col, train.val, S, N_pad, D_pad,
            int(csr_c.idx.shape[1]))
    res_d = DIST.run_gibbs_group(
        jax.random.key(0), csr_r, csr_c,
        jnp.asarray(test.row), jnp.asarray(test.col), cfg, topo,
        comm=COMM, csrt=csrt)
    rmse_d = float(GIBBS.rmse_from_acc(res_d.acc, jnp.asarray(test.val)))

    res_s = GIBBS.run_gibbs(jax.random.key(0), csr_r, csr_c,
                            jnp.asarray(test.row), jnp.asarray(test.col), cfg)
    rmse_s = float(GIBBS.rmse_from_acc(res_s.acc, jnp.asarray(test.val)))

    comm = DIST.sweep_comm_bytes(csr_r.n_cols, cfg.K)
    print(json.dumps({"rmse_dist": rmse_d, "rmse_single": rmse_s,
                      "comm_bytes_per_sweep": comm,
                      "n_devices": len(jax.devices()),
                      "U_shape": list(np.asarray(res_d.U).shape),
                      "U_shape_single": list(np.asarray(res_s.U).shape)}))
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_distributed_matches_single():
    rec = _run(SCRIPT)
    assert rec["n_devices"] == 8
    assert rec["U_shape"] == rec["U_shape_single"], rec
    # comm 'gather' is the reference chain sharded 8 ways: same key, same
    # draws — parity to the composed executors' tolerance
    assert abs(rec["rmse_dist"] - rec["rmse_single"]) < 1e-4, rec
    # limited communication: ~D*(K^2+K) floats per sweep, independent of nnz
    assert rec["comm_bytes_per_sweep"] < 200_000, rec


SCRIPT_SCATTER = SCRIPT.replace('COMM = "gather"', 'COMM = "scatter"')


@pytest.mark.slow
def test_scatter_v_matches_single():
    """Beyond-paper scatter-V variant (§Perf H6): each shard samples its
    own item rows under its own key, so the draws differ from the
    reference chain's — statistical parity (3.1e-4 apart on this seed)."""
    rec = _run(SCRIPT_SCATTER)
    assert rec["n_devices"] == 8
    assert rec["U_shape"] == rec["U_shape_single"], rec
    assert abs(rec["rmse_dist"] - rec["rmse_single"]) < 0.01, rec
